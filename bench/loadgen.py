"""The general traffic generator: one mix file in, one schedule out.

A mix (``bench/traffic/<name>.json``) holds only parameters:

``arrival``      ``"poisson"``: open loop at ``rate_qps``;
``rate_qps``     mean offered rate;
``knn_share``    share of k-NN requests, the rest ε-range;
``knn_k``        the k values, used in equal shares;
``range_rank``   ε sits between this neighbour and the next;
``noise_sigma``  query noise σ, cycled over the pool;
``pool``         distinct queries per seed, drawn uniformly;
``source``, ``assumed``  where the mix comes from, and what it adds
                 beyond its sources (read by people, not here).

Every seed gets the same work: the same number of requests, the same
arrival times, and the same set of kinds and k values, in another order.
Open-loop gaps are the quantiles of an exponential distribution,
shuffled by one fixed draw (the same for every run seed) and scaled so
that the arrivals fill the window; the run's seed orders which request
comes at each arrival.  How the warm-up replays the mix follows from the
service (``harness.Session``), not from the mix.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from data import substream

ARRIVAL_DRAW = 12           # the one draw that shuffles the arrival gaps


@dataclasses.dataclass
class Schedule:
    """One request per entry; ``due`` is seconds after the window
    opens."""

    due: np.ndarray          # (N,) float64
    query: np.ndarray        # (N,) pool index
    knn: np.ndarray          # (N,) bool
    k: np.ndarray            # (N,) int (0 for range)

    def __len__(self) -> int:
        return len(self.due)


def count(mix: dict, seconds: float) -> int:
    return max(1, int(round(float(mix["rate_qps"]) * seconds)))


def _gaps(mix: dict, n: int, seconds: float) -> np.ndarray:
    """(n,) arrival offsets in [0, seconds), the same for every seed."""
    if mix["arrival"] != "poisson":
        raise ValueError(f"unknown arrival process {mix['arrival']!r}")
    u = (np.arange(n) + 0.5) / n
    gaps = substream(ARRIVAL_DRAW, "arrivals").permutation(-np.log1p(-u))
    t = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return t * (seconds / (t[-1] + gaps[-1]))


def schedule(mix: dict, seed: int, seconds: float) -> Schedule:
    n = count(mix, seconds)
    rng = substream(seed, "schedule")
    due = _gaps(mix, n, seconds)
    n_knn = int(round(n * float(mix["knn_share"])))
    knn = rng.permutation(np.arange(n) < n_knn)
    ks = np.resize(np.asarray(mix["knn_k"], np.int64), n_knn)
    k = np.zeros(n, np.int64)
    k[knn] = rng.permutation(ks)
    pool = int(mix["pool"])
    query = np.concatenate([rng.permutation(pool)
                            for _ in range(-(-n // pool))])[:n]
    return Schedule(due=due, query=query, knn=knn, k=k)


def _compositions(picks_range, picks_knn, ks, qb: int) -> list:
    """Bursts of ``qb`` requests: range only, k-NN only at each k, and
    half k-NN, half range at each k."""
    rng = np.resize(picks_range, qb)
    knn = np.resize(picks_knn, qb)
    out = [[(int(p), False, 0) for p in rng]]
    for k in ks:
        out.append([(int(p), True, k) for p in knn])
        out.append([(int(knn[i]), True, k) if i % 2 == 0
                    else (int(rng[i]), False, 0) for i in range(qb)])
    return out


def warmup_bursts(mix: dict, sigma: np.ndarray, max_batch: int,
                  levels, hard: dict | None = None) -> list:
    """The warm-up slice of the mix: for every batch size 1, 2, 4, …,
    ``max_batch``, bursts of range requests, of k-NN requests at each k,
    and of half of each, on the queries of each noise level in
    ``levels`` and, where given, on the ``hard`` queries ({"range":
    [...], "knn": [...]}) that formed the widest outputs.  Each burst is
    a list of (query, is_knn, k)."""
    sizes = []
    b = 1
    while b <= max_batch:
        sizes.append(b)
        b *= 2
    ks = sorted(set(int(x) for x in mix["knn_k"]))
    groups = [(np.flatnonzero(np.isclose(sigma, s)),) * 2 for s in levels]
    if hard:
        groups.append((np.asarray(hard["range"]), np.asarray(hard["knn"])))
    return [burst for rng, knn in groups for qb in sizes
            for burst in _compositions(rng, knn, ks, qb)]


@dataclasses.dataclass
class Sent:
    """What the generator did: the requests in schedule order, when each
    was due and when it was submitted (``time.perf_counter``)."""

    requests: list
    due: np.ndarray
    submit: np.ndarray


def submit(svc, query: np.ndarray, is_knn: bool, k: int, eps: float):
    if is_knn:
        return svc.submit_knn(query, int(k))
    return svc.submit_range(query, float(eps))


def drive(svc, sched: Schedule, pool: np.ndarray, eps: np.ndarray,
          t_open: float) -> Sent:
    """Send the schedule, open loop: each request at ``t_open + due``,
    whatever the service is doing."""
    n = len(sched)
    reqs = [None] * n
    due = t_open + sched.due
    sub = np.zeros(n)
    for i in range(n):
        wait = due[i] - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        sub[i] = time.perf_counter()
        reqs[i] = submit(svc, pool[sched.query[i]], bool(sched.knn[i]),
                         int(sched.k[i]), float(eps[sched.query[i]]))
    return Sent(reqs, due, sub)
