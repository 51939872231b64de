"""The plain reference: exact Euclidean search over z-normalised rows.

Float64 brute force, independent of the code under test: its own
z-normalisation, its own distances, nothing imported from ``src/``.

Scanning millions of rows in float64 on the host takes longer than a
run's window, so the scan has two stages:

1. On the device, float32 squared distances at ``Precision.HIGHEST``
   (matmul form) to every row, and the ``CANDIDATES`` nearest rows of
   each query.
2. On the host, float64 distances (difference form) of those candidates.

Stage 1 errs by far less than ``F32_MARGIN`` in d² (z-normalised rows
at n = 256 have ‖q‖² = ‖u‖² = n; the float32 error of the matmul form is
about n·2⁻²³·2n ≈ 0.016 at worst).  Every row whose float64 d² lies
below the candidates' last float32 d² minus the margin is therefore a
candidate, and ``Reference.covered`` tells up to which d² the candidate
list is complete.  A comparison that needs more raises instead of
guessing.

The same stage-1 code, with both operands rounded to bfloat16, is the
control: the reference put in the program's place one precision below
float32.  ``check`` must find it wrong.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

CANDIDATES = 256        # rows kept per query by the float32 stage
F32_MARGIN = 0.25       # d² slack over the float32 stage's error
QUERY_BLOCK = 16        # queries per device block
BIG = 1e30              # gap of a structurally wrong answer

def band_d2(n: int) -> float:
    """Half-width, in d², of the float32 band around a boundary inside
    which a correct float32 engine may disagree with float64:
    n·2⁻²³·(‖q‖² + ‖u‖²) with ‖q‖² = ‖u‖² = n, the worst-case float32
    error of an n-term dot.  Printed beside the readings; the limits are
    set from readings, not from this bound."""
    return n * 2.0 ** -23 * (2.0 * n)


#: Limits of the numbers ``check`` compares (PERF.md, "Correctness",
#: gives the readings each was set from).
LIMITS = {
    "unanswered": 0.0,
    "answer_gap_d2": 0.01,
    "dist_err_d2": 0.01,
}


def _znorm32(x):
    x = x - x.mean(axis=-1, keepdims=True)
    return x / jnp.maximum(x.std(axis=-1, keepdims=True), 1e-8)


@functools.partial(jax.jit, static_argnames=("kk", "bf16"))
def _nearest(db, q, kk: int, bf16: bool):
    """(d², ids) of the ``kk`` nearest rows of each query, float32 (or
    both operands rounded to bfloat16 when ``bf16``)."""
    qz = _znorm32(q)
    if bf16:
        xs = db.astype(jnp.bfloat16)
        xn = jnp.sum(jnp.square(xs.astype(jnp.float32)), axis=-1)
        prec = jax.lax.Precision.DEFAULT
    else:
        xs = db
        xn = jnp.sum(db * db, axis=-1)
        prec = jax.lax.Precision.HIGHEST

    def block(qb):
        if bf16:
            qb = qb.astype(jnp.bfloat16)
        qn = jnp.sum(jnp.square(qb.astype(jnp.float32)), axis=-1)
        dot = jnp.dot(qb, xs.T, precision=prec,
                      preferred_element_type=jnp.float32)
        d2 = jnp.maximum(qn[:, None] - 2.0 * dot + xn[None, :], 0.0)
        neg, ids = jax.lax.top_k(-d2, kk)
        return -neg, ids

    Q = qz.shape[0]
    blocks = qz.reshape(Q // QUERY_BLOCK, QUERY_BLOCK, -1)
    d2, ids = jax.lax.map(block, blocks)
    return d2.reshape(Q, kk), ids.reshape(Q, kk)


def nearest(db, queries: np.ndarray, kk: int, bf16: bool = False):
    """Host (d², ids) of the ``kk`` nearest rows, in stage-1 precision."""
    q = np.asarray(queries, np.float32)
    pad = -len(q) % QUERY_BLOCK
    qp = np.concatenate([q, np.repeat(q[:1], pad, axis=0)]) if pad else q
    d2, ids = jax.device_get(_nearest(db, jnp.asarray(qp), int(kk),
                                      bool(bf16)))
    return np.asarray(d2)[:len(q)], np.asarray(ids)[:len(q)]


def znorm64(x: np.ndarray) -> np.ndarray:
    x = np.asarray(x, np.float64)
    x = x - x.mean(axis=-1, keepdims=True)
    return x / np.maximum(x.std(axis=-1, keepdims=True), 1e-8)


def f64_d2(query: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """Float64 squared distances of z-normalised ``rows`` to ``query``."""
    diff = znorm64(rows) - znorm64(query[None, :])
    return np.einsum("ij,ij->i", diff, diff)


@dataclasses.dataclass
class Reference:
    """Per query: candidate ids in ascending float64 d² (ties to the lower
    id), their d², and ``covered``: every row with a smaller float64 d²
    is among the candidates."""

    ids: list
    d2: list
    covered: np.ndarray
    extra: dict            # (query, row) -> float64 d² of other rows

    def row_d2(self, qi: int, rows: np.ndarray) -> np.ndarray:
        pos = {int(r): float(d) for r, d in zip(self.ids[qi], self.d2[qi])}
        return np.array([pos[int(r)] if int(r) in pos
                         else self.extra[(qi, int(r))] for r in rows])


def build_reference(db, queries: np.ndarray, served: list) -> Reference:
    """Reference for ``queries`` (host, raw as submitted).  ``served[i]``
    lists the row ids answered for query i; rows outside the candidates
    get their float64 d² too, so every served row can be judged."""
    d2f, ids = nearest(db, queries, CANDIDATES)
    rows = np.asarray(jax.device_get(db[jnp.asarray(ids.reshape(-1))]))
    rows = rows.reshape(ids.shape + (db.shape[1],))
    out_ids, out_d2 = [], []
    for i, q in enumerate(queries):
        d2 = f64_d2(q, rows[i])
        order = np.lexsort((ids[i], d2))
        out_ids.append(ids[i][order])
        out_d2.append(d2[order])
    covered = d2f[:, -1] - F32_MARGIN
    pairs = sorted({(i, int(r)) for i, got in enumerate(served)
                    for r in np.asarray(got, np.int64).reshape(-1)
                    if 0 <= r < db.shape[0]} -
                   {(i, int(r)) for i in range(len(queries))
                    for r in out_ids[i]})
    extra = {}
    if pairs:
        got = np.asarray(jax.device_get(
            db[jnp.asarray([r for _, r in pairs])]))
        for (i, r), row in zip(pairs, got):
            extra[(i, r)] = float(f64_d2(queries[i], row[None, :])[0])
    return Reference(out_ids, out_d2, covered, extra)


class ReferenceTooShort(RuntimeError):
    """The candidate list does not reach the d² a comparison needs."""


def _gaps(ref: Reference, qi: int, kind: str, k: int, eps: float,
          ids: np.ndarray, dist: np.ndarray, n_rows: int):
    """(answer gap, distance error) of one answer, in d²."""
    ids = np.asarray(ids, np.int64).reshape(-1)
    dist = np.asarray(dist, np.float64).reshape(-1)
    if ids.size != dist.size or len(set(ids.tolist())) != ids.size or \
            ((ids < 0) | (ids >= n_rows)).any():
        return BIG, BIG
    cand, cd2 = ref.ids[qi], ref.d2[qi]
    got = ref.row_d2(qi, ids)
    err = float(np.max(np.abs(dist * dist - got))) if ids.size else 0.0
    if kind == "knn":
        if ids.size != min(k, n_rows):
            return BIG, err
        kth = cd2[k - 1]
        if kth > ref.covered[qi]:
            raise ReferenceTooShort(f"query {qi}: k={k} needs d² {kth}")
        excess = float(np.max(got - kth, initial=0.0))
        inside = set(ids.tolist())
        missed = [kth - d for r, d in zip(cand, cd2)
                  if d < kth and int(r) not in inside]
        return max(excess, max(missed, default=0.0)), err
    e2 = eps * eps
    if e2 > ref.covered[qi]:
        raise ReferenceTooShort(f"query {qi}: ε² {e2} beyond the candidates")
    want = {int(r) for r, d in zip(cand, cd2) if d <= e2}
    have = set(ids.tolist())
    gap = 0.0
    for r, d in zip(ids, got):
        if int(r) not in want:
            gap = max(gap, d - e2)
    for r, d in zip(cand, cd2):
        if int(r) in want and int(r) not in have:
            gap = max(gap, e2 - d)
    return gap, err


def check(ref: Reference, asks: list, answers: list, n_rows: int,
          unanswered: int) -> dict:
    """The numbers compared, each ``{"value", "limit"}``.

    ``asks[j]`` is ``(query index, kind, k, ε)``; ``answers[j]`` is
    ``(ids, distances)`` as served.  ``unanswered`` counts requests due in
    the window that never got an OK answer.  The answer gap is the widest,
    in d², by which a served row lies beyond the true k-th neighbour or
    on the wrong side of ε², or a missing true row lies inside; the
    distance error is the widest gap between a served distance² and the
    row's float64 d²."""
    gap = err = 0.0
    for (qi, kind, k, eps), (ids, dist) in zip(asks, answers):
        g, e = _gaps(ref, qi, kind, k, eps, ids, dist, n_rows)
        gap, err = max(gap, g), max(err, e)
    values = {"unanswered": float(unanswered), "answer_gap_d2": float(gap),
              "dist_err_d2": float(err)}
    return {name: {"value": values[name], "limit": LIMITS[name]}
            for name in LIMITS}


def passes(numbers: dict) -> bool:
    return all(v["value"] <= v["limit"] for v in numbers.values())


def control_answers(db, queries: np.ndarray, asks: list) -> list:
    """The reference in the program's place, in bfloat16: each ask
    answered from bfloat16 distances, with no float64 stage."""
    d2b, ids = nearest(db, queries, CANDIDATES, bf16=True)
    out = []
    for qi, kind, k, eps in asks:
        if kind == "knn":
            sel = np.arange(k)
        else:
            sel = np.flatnonzero(d2b[qi] <= eps * eps)
        out.append((ids[qi][sel], np.sqrt(d2b[qi][sel])))
    return out
