"""Pluggable representation registry (DESIGN.md §11).

A cascade *representation* is a first-class registered object: it knows
how to symbolize database series and queries (host f64 and device f32
twins), how to compute its provably-sound lower bound against the stored
column, what store column it occupies (name / dtype / quantizability),
and what its exclusion and query-transform op costs are — so
``core/fastsax.py``, ``core/search.py``, ``core/engine.py``,
``core/dist_search.py``, ``core/subseq.py``, ``index/store.py``,
``index/quantized.py`` and ``serve/service.py`` consume a *stack* of
registered names generically instead of hard-coding words + residuals.

Soundness contract (the conformance suite in
``tests/test_representations.py`` enforces this for every registration
automatically): for any z-normalised series ``u`` and query ``q``,

    lower_bound(u, q) ≤ d(u, q)            (true Euclidean distance)

so ``lower_bound > ε  ⇒  d > ε`` and a kill can never drop a true
answer.  The two paper representations are the first registrations:

  * ``linfit_residual`` — the optimal per-segment first-degree residual
    gap |d(u,ū) − d(q,q̄)| (paper eq. 9, exclusion condition C9).
  * ``sax_word`` — MINDIST over the SAX word (paper eq. 10, C10).

``trend_slope`` is the first post-paper registration: per-segment slope
symbols from the same least-squares fit as ``polyfit.linfit_coeffs``,
with a MINDIST-style slope bound (proof sketch in DESIGN.md §11; the
pruning-power comparison on trending data is EXPERIMENTS.md
§Representations).

Every stack must contain both paper representations — they are the
backbone the engines' seed phases, storage layout and padding sentinels
are built on; registered extras *augment* the cascade.  Gap-kind
representations run before word-kind ones within each level (the C9 →
C10 order), and their kills are counted under the historical
``excluded_c9`` / ``excluded_c10`` telemetry fields by kind.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable

import numpy as np

from . import cost_model as cm
from . import polyfit
from .paa import paa, paa_np
from .sax import discretize, discretize_np, mindist_table


@dataclasses.dataclass(frozen=True)
class ColumnSpec:
    """Store-column schema of one representation.

    ``prefix`` names the per-level store column (``{prefix}_N{N}.npy``);
    ``dtypes`` is the accepted-on-load dtype contract (first entry is
    written); ``per_segment`` distinguishes (B, N) symbol columns from
    (B,) scalar columns; ``quantizable`` gates the memory-tiered index
    (int8 symbol columns are lossless; see ``index/quantized.py``).
    """

    prefix: str
    dtypes: tuple
    per_segment: bool
    quantizable: bool


class Representation:
    """Base class / protocol for a registered cascade representation.

    Subclasses define the class attributes and override the symbolize /
    bound hooks.  ``kind`` is ``"gap"`` (scalar column, C9-style
    |a − b| > ε exclusion) or ``"word"`` (per-segment symbol column,
    C10-style squared-bound > ε² exclusion).  ``canonical_field`` names
    the dedicated index field the column lives in (``"residuals"`` /
    ``"words"``) for the two paper representations; extras ride in the
    generic ``extra`` containers keyed by representation name.
    """

    name: str = ""
    kind: str = "word"               # "gap" | "word"
    canonical_field: str | None = None
    column: ColumnSpec = None
    residual_rule: str = ""

    # -- offline/online symbolization ------------------------------------
    def symbolize_np(self, series: np.ndarray, N: int,
                     alphabet: int) -> np.ndarray:
        """Host f64 column for a (B, n) batch (or (n,) query)."""
        raise NotImplementedError

    def query_repr_np(self, q: np.ndarray, N: int, alphabet: int):
        """Host query-side value: scalar float (gap) or (N,) i32 (word)."""
        raise NotImplementedError

    def symbolize_dev(self, x, N: int, alphabet: int):
        """Device f32 column for a (B, n) or (Q, n) batch (jnp)."""
        raise NotImplementedError

    # -- lower bounds / exclusion ----------------------------------------
    def host_gap(self, col: np.ndarray, qval) -> np.ndarray:
        """Gap-kind lower bound (distance units) — gap-kind reps only."""
        raise NotImplementedError

    def host_bound_sq(self, col: np.ndarray, qval, *, n: int, N: int,
                      alphabet: int) -> np.ndarray:
        """Word-kind squared lower bound — word-kind reps only."""
        raise NotImplementedError

    def host_lower_bound(self, col: np.ndarray, qval, *, n: int, N: int,
                         alphabet: int) -> np.ndarray:
        """Lower bound in distance units, either kind (conformance API)."""
        if self.kind == "gap":
            return self.host_gap(col, qval)
        return np.sqrt(self.host_bound_sq(col, qval, n=n, N=N,
                                          alphabet=alphabet))

    def dev_gap(self, col, qcol):
        """(Q, B) device gap — gap-kind reps only (jnp)."""
        raise NotImplementedError

    def dev_bound_sq(self, col, qcol, *, n: int, N: int, tab):
        """(Q, B) device squared bound — word-kind reps only (jnp)."""
        raise NotImplementedError

    # -- cost-model hooks -------------------------------------------------
    def exclude_cost(self, n: int, N: int, alphabet: int) -> dict:
        """Per-candidate op dict of one exclusion test at this level."""
        raise NotImplementedError

    def query_cost(self, n: int, N: int, alphabet: int) -> dict:
        """Per-query op dict of the online transform at this level."""
        raise NotImplementedError

    # -- subsequence (amortised window) hook ------------------------------
    # Optional: symbolize every window of a stream from the cumsum window
    # stats (see core/subseq._window_level).  Representations that cannot
    # be synthesised from window stats leave this as None and the subseq
    # builder fails loudly.
    window_symbolize_np: Callable | None = None


# ---------------------------------------------------------------------------
# Registry.
# ---------------------------------------------------------------------------

_REGISTRY: dict = {}

#: The paper's two-representation cascade — the backbone every stack
#: must contain (seed phase, storage layout and pad sentinels build on
#: it) and the default when a manifest or caller names no stack.
DEFAULT_STACK = ("linfit_residual", "sax_word")
REQUIRED_NAMES = frozenset(DEFAULT_STACK)


def register(rep: Representation) -> Representation:
    """Register a representation instance under its ``name`` (unique)."""
    if not rep.name:
        raise ValueError("representation must have a non-empty name")
    if rep.name in _REGISTRY:
        raise ValueError(f"representation {rep.name!r} already registered")
    if rep.kind not in ("gap", "word"):
        raise ValueError(f"{rep.name}: kind must be 'gap' or 'word', "
                         f"got {rep.kind!r}")
    if rep.column is None:
        raise ValueError(f"{rep.name}: missing ColumnSpec")
    _REGISTRY[rep.name] = rep
    return rep


def get(name: str) -> Representation:
    """Look up a registered representation; loud failure on unknown."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unregistered representation {name!r} — registered: "
            f"{registered_names()}") from None


def registered_names() -> tuple:
    """All registered names, registration order."""
    return tuple(_REGISTRY)


def validate_stack(stack) -> tuple:
    """Validate a level stack: registered names, the paper backbone
    present, no duplicates, gap-kind before word-kind (the C9 → C10
    cascade order).  Returns the stack as a tuple of names."""
    stack = tuple(stack)
    if len(set(stack)) != len(stack):
        raise ValueError(f"duplicate representation in stack {stack}")
    reps = [get(name) for name in stack]       # loud on unregistered
    missing = REQUIRED_NAMES - set(stack)
    if missing:
        raise ValueError(
            f"stack {stack} is missing the paper backbone "
            f"representation(s) {sorted(missing)} — every stack must "
            f"contain {DEFAULT_STACK}")
    seen_word = False
    for rep in reps:
        if rep.kind == "word":
            seen_word = True
        elif seen_word:
            raise ValueError(
                f"stack {stack}: gap-kind {rep.name!r} after a word-kind "
                "representation — gap-kind levels run first (C9 → C10)")
    return stack


def stack_reps(stack) -> tuple:
    """The validated stack resolved to representation objects."""
    return tuple(get(name) for name in validate_stack(stack))


def extra_names(stack) -> tuple:
    """Stack names beyond the canonical paper pair, in stack order."""
    return tuple(n for n in validate_stack(stack)
                 if get(n).canonical_field is None)


# ---------------------------------------------------------------------------
# Registry-owned linear-fit residual entrypoint (the one deduplicated
# implementation; ``kernels/ref.py`` and the engines delegate here or to
# ``core/polyfit.py`` — parity pinned in tests/test_representations.py).
# ---------------------------------------------------------------------------


def linfit_residual_sq(x, n_segments: int, backend: str = "numpy"):
    """Squared per-segment linear-fit residual ‖u − ū‖², dispatched.

    ``backend="numpy"`` is the f64 host twin (op-counted engine),
    ``"xla"`` the jnp form (device engines), ``"pallas"`` the fused
    kernel (``kernels/ops.linfit_residual_sq``).  All three evaluate the
    same closed form (DESIGN.md §1) and agree to f32 rounding.
    """
    if backend == "numpy":
        return polyfit.linfit_residual_sq_np(np.asarray(x), n_segments)
    if backend == "xla":
        return polyfit.linfit_residual_sq(x, n_segments)
    if backend == "pallas":
        from ..kernels import ops as kernel_ops
        return kernel_ops.linfit_residual_sq(x, n_segments)
    raise ValueError(f"unknown linfit backend {backend!r} "
                     "(want numpy|xla|pallas)")


# ---------------------------------------------------------------------------
# The registrations.
# ---------------------------------------------------------------------------


class LinfitResidualRepr(Representation):
    """Paper C9: residual distance to the optimal per-segment LS line.

    Column: (B,) f64 ``d(u, ū_l)``.  Bound: the reverse triangle
    inequality on the optimal-projection property (paper eq. 9) —
    ``|d(u,ū) − d(q,q̄)| ≤ d(u,q)`` because both series project onto the
    same piecewise-linear class.
    """

    name = "linfit_residual"
    kind = "gap"
    canonical_field = "residuals"
    column = ColumnSpec(prefix="resid", dtypes=("float64", "float32"),
                        per_segment=False, quantizable=True)
    residual_rule = ("gap = |d(u,ū) − d(q,q̄)|; kill iff gap > ε "
                     "(paper eq. 9, condition C9)")

    def symbolize_np(self, series, N, alphabet):
        return polyfit.linfit_residual_np(series, N).astype(np.float64)

    def query_repr_np(self, q, N, alphabet):
        return float(polyfit.linfit_residual_np(q, N))

    def symbolize_dev(self, x, N, alphabet):
        import jax.numpy as jnp
        return polyfit.linfit_residual(x, N).astype(jnp.float32)

    def host_gap(self, col, qval):
        return np.abs(col - qval)

    def dev_gap(self, col, qcol):
        import jax.numpy as jnp
        return jnp.abs(col[None, :] - qcol[:, None])

    def exclude_cost(self, n, N, alphabet):
        return cm.c9_cost()

    def query_cost(self, n, N, alphabet):
        return cm.linfit_residual_cost(n, N)


class SaxWordRepr(Representation):
    """Paper C10: MINDIST over the SAX word (symbols of the PAA means).

    Column: (B, N) i32 symbols.  Bound: MINDIST (paper eq. 3) —
    ``(n/N)·Σᵢ tab[u_i, q_i]² ≤ d(u,q)²`` through the PAA distance.
    """

    name = "sax_word"
    kind = "word"
    canonical_field = "words"
    column = ColumnSpec(prefix="words", dtypes=("int32",),
                        per_segment=True, quantizable=True)
    residual_rule = ("MINDIST²(sax(u), sax(q)) = (n/N)·Σ tab[uᵢ,qᵢ]²; "
                     "kill iff MINDIST² > ε² (paper eq. 10, C10)")

    def symbolize_np(self, series, N, alphabet):
        return discretize_np(paa_np(series, N), alphabet)

    def query_repr_np(self, q, N, alphabet):
        return discretize_np(paa_np(q, N), alphabet)

    def symbolize_dev(self, x, N, alphabet):
        return discretize(paa(x, N), alphabet)

    def host_bound_sq(self, col, qval, *, n, N, alphabet):
        tab = mindist_table(alphabet)
        cell = tab[col, np.asarray(qval)[None, :]]
        return (n / N) * np.sum(cell * cell, axis=-1)

    def dev_bound_sq(self, col, qcol, *, n, N, tab):
        import jax.numpy as jnp
        cell = tab[col[None, :, :], qcol[:, None, :]]
        return (n / N) * jnp.sum(cell * cell, axis=-1)

    def exclude_cost(self, n, N, alphabet):
        return cm.mindist_cost(N)

    def query_cost(self, n, N, alphabet):
        return _merge_costs(cm.paa_cost(n, N),
                            cm.discretize_cost(N, alphabet))


def _trend_scaled_slope_np(series: np.ndarray, N: int) -> np.ndarray:
    """Per-segment slope·√Sxx of the LS line, host f64 twin."""
    n = series.shape[-1]
    if n % N != 0:
        raise ValueError(f"n_segments must divide n: n={n}, N={N}")
    L = n // N
    segs = series.reshape(*series.shape[:-1], N, L)
    if L == 1:
        return np.zeros(segs.shape[:-1], dtype=np.float64)
    xc = np.arange(L, dtype=np.float64) - (L - 1) / 2.0
    sxx = float(np.sum(xc * xc))
    return (segs @ xc) / np.sqrt(sxx)


class TrendSlopeRepr(Representation):
    """Trend-aware level: symbols of the per-segment LS *slope*.

    Column: (B, N) i32 symbols of ``slope·√Sxx`` (the slope of
    ``polyfit.linfit_coeffs`` scaled into distance units) discretized
    with the standard Gaussian breakpoints.  Bound (DESIGN.md §11):
    the orthogonal projection onto the per-segment linear class gives

        d(u,q)² ≥ Σᵢ [ Lᵢ·Δmeanᵢ² + Sxx·Δslopeᵢ² ] ≥ Σᵢ (Δ(slopeᵢ·√Sxx))²

    and per segment, symbols differing by more than one bin imply
    ``|Δ(slope·√Sxx)| ≥ tab[uᵢ, qᵢ]`` — so ``Σᵢ tab[uᵢ,qᵢ]² ≤ d(u,q)²``
    (no n/N factor: the slope deviations are already in distance units).
    Complementary to ``sax_word`` (which sees only segment *means*) on
    trending data — see EXPERIMENTS.md §Representations.
    """

    name = "trend_slope"
    kind = "word"
    canonical_field = None
    column = ColumnSpec(prefix="twords", dtypes=("int32",),
                        per_segment=True, quantizable=True)
    residual_rule = ("TLB²(u, q) = Σ tab[tsym(u)ᵢ, tsym(q)ᵢ]² with "
                     "tsym = discretize(slope·√Sxx); kill iff TLB² > ε²")

    def symbolize_np(self, series, N, alphabet):
        return discretize_np(_trend_scaled_slope_np(series, N), alphabet)

    def query_repr_np(self, q, N, alphabet):
        return discretize_np(_trend_scaled_slope_np(q, N), alphabet)

    def symbolize_dev(self, x, N, alphabet):
        import jax.numpy as jnp
        n = x.shape[-1]
        L = n // N
        segs = x.reshape(*x.shape[:-1], N, L)
        if L == 1:
            scaled = jnp.zeros(segs.shape[:-1], dtype=x.dtype)
        else:
            xc, sxx = polyfit._centred_abscissa(L)
            scaled = polyfit._dot_abscissa(segs, xc) / jnp.sqrt(sxx)
        return discretize(scaled, alphabet)

    def host_bound_sq(self, col, qval, *, n, N, alphabet):
        tab = mindist_table(alphabet)
        cell = tab[col, np.asarray(qval)[None, :]]
        return np.sum(cell * cell, axis=-1)

    def dev_bound_sq(self, col, qcol, *, n, N, tab):
        import jax.numpy as jnp
        cell = tab[col[None, :, :], qcol[:, None, :]]
        return jnp.sum(cell * cell, axis=-1)

    def exclude_cost(self, n, N, alphabet):
        return dict(lookup=N, mul=N, add=N - 1, cmp=1)

    def query_cost(self, n, N, alphabet):
        return dict(mul=n, add=n - N, div=N, sqrt=1,
                    cmp=N * math.ceil(math.log2(alphabet)))

    @staticmethod
    def window_symbolize_np(ws) -> np.ndarray:
        """Amortised window symbols from the cumsum stats: the scaled
        slope of the z window is ``sxy_raw / (σ·√Sxx)`` (the affine map
        z = (y − μ)/σ leaves Sxy/√Sxx scaled by 1/σ; the −μ shift only
        moves the mean)."""
        if ws.L == 1:
            # Same symbol the direct path assigns to a zero slope
            # (discretize(0)) — an L==1 level has no slope information,
            # and matching symbols make the bound identically zero.
            scaled = np.zeros(ws.sum_y.shape, dtype=np.float64)
        else:
            scaled = ws.sxy / (ws.sd[..., None] * np.sqrt(ws.sxx))
        return discretize_np(scaled, ws.alphabet)


def _merge_costs(*dicts) -> dict:
    out: dict = {}
    for d in dicts:
        for op, c in d.items():
            out[op] = out.get(op, 0) + c
    return out


register(LinfitResidualRepr())
register(SaxWordRepr())
register(TrendSlopeRepr())
