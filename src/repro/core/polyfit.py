"""Optimal per-segment first-degree approximation (paper §3).

Each series is split into N segments; each segment is replaced by its L2
least-squares straight line.  Because the fit is the *optimal* member of the
piecewise-linear-on-this-segmentation class, d(u,ū) ≤ d(u,v̄) for any other
member v̄ of the class — the key fact behind the paper's exclusion condition
(eq. 6).  The residual distance d(u,ū) is computed in closed form:

    with centred abscissa xc = x − (L−1)/2,  Sxx = Σ xc²:
      mean  = Σy / L
      slope = Σ xc·y / Sxx
      ‖resid‖² = Σy² − L·mean² − slope²·Sxx

No iterative solver; one pass over the data; batched over (series × segment).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _dot_abscissa(segs: jnp.ndarray, xc: jnp.ndarray) -> jnp.ndarray:
    """Σ_l segs[..., l]·xc[l] at full f32 precision: a default-precision
    contraction runs as one bf16 pass on the TPU, which would perturb the
    residuals the C9 lower bound compares."""
    return jnp.einsum("...l,l->...", segs, xc,
                      precision=jax.lax.Precision.HIGHEST)


def _centred_abscissa(seg_len: int):
    xc = jnp.arange(seg_len, dtype=jnp.float32) - (seg_len - 1) / 2.0
    sxx = jnp.sum(xc * xc)
    return xc, sxx


def linfit_coeffs(x: jnp.ndarray, n_segments: int):
    """Per-segment LS line.  x: (..., n) -> (mean, slope): (..., N) each."""
    n = x.shape[-1]
    if n % n_segments != 0:
        raise ValueError(f"n_segments must divide n: n={n}, N={n_segments}")
    L = n // n_segments
    segs = x.reshape(*x.shape[:-1], n_segments, L)
    xc, sxx = _centred_abscissa(L)
    mean = segs.mean(axis=-1)
    if L == 1:
        slope = jnp.zeros_like(mean)
    else:
        slope = _dot_abscissa(segs, xc) / sxx
    return mean, slope


def linfit_reconstruct(mean: jnp.ndarray, slope: jnp.ndarray, seg_len: int) -> jnp.ndarray:
    """(..., N) coeffs -> (..., N·L) piecewise-linear reconstruction ū."""
    xc, _ = _centred_abscissa(seg_len)
    rec = mean[..., None] + slope[..., None] * xc
    return rec.reshape(*mean.shape[:-1], mean.shape[-1] * seg_len)


def linfit_residual_sq(x: jnp.ndarray, n_segments: int) -> jnp.ndarray:
    """Squared residual distance d(u,ū)² = Σ_seg ‖resid‖².  x: (..., n) -> (...)."""
    n = x.shape[-1]
    L = n // n_segments
    segs = x.reshape(*x.shape[:-1], n_segments, L)
    xc, sxx = _centred_abscissa(L)
    sum_y = segs.sum(axis=-1)
    sum_y2 = jnp.sum(segs * segs, axis=-1)
    mean = sum_y / L
    if L <= 2:
        # L==1: exact fit; L==2: a line through 2 points is exact.
        per_seg = jnp.zeros_like(mean) if L == 1 else jnp.maximum(
            sum_y2 - L * mean * mean
            - (_dot_abscissa(segs, xc) ** 2) / sxx, 0.0)
    else:
        sxy = _dot_abscissa(segs, xc)
        per_seg = jnp.maximum(sum_y2 - L * mean * mean - (sxy * sxy) / sxx, 0.0)
    return per_seg.sum(axis=-1)


def linfit_residual(x: jnp.ndarray, n_segments: int) -> jnp.ndarray:
    """d(u,ū): Euclidean distance from each series to its optimal projection."""
    return jnp.sqrt(linfit_residual_sq(x, n_segments))


# NumPy twins (sequential op-count engine) ----------------------------------

def linfit_residual_sq_np(x: np.ndarray, n_segments: int) -> np.ndarray:
    """Squared residual distance, host dtype-preserving twin of
    :func:`linfit_residual_sq` — the registry's ``backend="numpy"``
    dispatch target (``core/representation.linfit_residual_sq``)."""
    n = x.shape[-1]
    if n % n_segments != 0:
        raise ValueError(f"n_segments must divide n: n={n}, N={n_segments}")
    L = n // n_segments
    segs = x.reshape(*x.shape[:-1], n_segments, L)
    xc = np.arange(L, dtype=np.float64) - (L - 1) / 2.0
    sxx = float(np.sum(xc * xc))
    sum_y = segs.sum(axis=-1)
    sum_y2 = np.sum(segs * segs, axis=-1)
    mean = sum_y / L
    if L <= 2:
        per_seg = np.zeros_like(mean)
        if L == 2:
            sxy = segs @ xc
            per_seg = np.maximum(sum_y2 - L * mean * mean - (sxy * sxy) / sxx, 0.0)
    else:
        sxy = segs @ xc
        per_seg = np.maximum(sum_y2 - L * mean * mean - (sxy * sxy) / sxx, 0.0)
    return per_seg.sum(axis=-1)


def linfit_residual_np(x: np.ndarray, n_segments: int) -> np.ndarray:
    return np.sqrt(linfit_residual_sq_np(x, n_segments))
