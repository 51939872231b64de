"""The tiered screen's sort-free selection equals the ``lax.top_k`` forms.

Three parts, each against the sort it replaced, kept here as the oracle:

  * compaction by prefix count (``engine._compact_mask``) returns the same
    ``idx``, ``valid`` and ``overflow`` as ``lax.top_k`` over the keys
    ``B − i`` of the keep mask, bit for bit — the slots past a row's
    count included;
  * the k-th smallest by bisection (``engine._kth_smallest_bisect``)
    equals ``engine._kth_smallest`` bit for bit;
  * the capacity chosen from the survivor count
    (``engine._screen_and_compact``) is the one the 4× escalation loop
    stopped at, with that loop's result, overflow past ``max_doublings``
    included.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core import engine  # noqa: E402


def _compact_top_k(keep, capacity):
    """The sorting compaction the counting one replaced."""
    B = keep.shape[-1]
    keys = jnp.where(keep, B - jnp.arange(B, dtype=jnp.int32)[None, :], 0)
    top, idx = jax.lax.top_k(keys, capacity)
    return idx, top > 0, keep.sum(axis=-1) > capacity


def _escalate_top_k(keep, cap, max_doublings):
    """The 4× escalation loop the count-first capacity replaced."""
    B = keep.shape[-1]
    idx, valid, overflow = _compact_top_k(keep, cap)
    for _ in range(max_doublings):
        if cap >= B or not bool(np.asarray(overflow).any()):
            break
        cap = min(B, cap * 4)
        idx, valid, overflow = _compact_top_k(keep, cap)
    return idx, valid, overflow


def _mask(B, counts, seed=0):
    """(len(counts), B) mask with counts[r] set positions, at random."""
    rng = np.random.default_rng(seed)
    keep = np.zeros((len(counts), B), bool)
    for r, c in enumerate(counts):
        keep[r, rng.choice(B, size=c, replace=False)] = True
    return jnp.asarray(keep)


def _assert_same(got, want):
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_array_equal(g, w)


COMPACT_CASES = {
    # name: (B, per-row set counts, capacity)
    "random": (1000, [0, 3, 17, 40, 64, 200], 64),
    "random_wide": (40_000, [1, 90, 700, 2_000], 1_024),
    "all_false": (300, [0, 0, 0], 16),
    "all_true": (300, [300, 300], 64),
    "count_eq_c": (513, [32, 32, 5], 32),
    "count_eq_c_plus_1": (513, [33, 32, 0], 32),
    "c_eq_b": (97, [0, 40, 97], 97),
    "one_position": (1, [0, 1], 1),
    "word_edges": (65, [31, 32, 33, 64, 65], 33),
}


@pytest.mark.parametrize("case", sorted(COMPACT_CASES))
def test_counting_compaction_matches_top_k(case):
    B, counts, capacity = COMPACT_CASES[case]
    keep = _mask(B, counts, seed=len(case))
    _assert_same(engine._compact_mask(keep, capacity),
                 _compact_top_k(keep, capacity))


KTH_CASES = {
    # name: ((Q, M) rows, k)
    "ties": ([[2.0, 1.0, 2.0, 1.0, 2.0, 3.0]] * 2, 3),
    "inf_rows": ([[np.inf] * 5, [1.0, np.inf, np.inf, np.inf, np.inf]], 2),
    "zeros": ([[0.0, -0.0, 0.0, 1.0], [-0.0, -0.0, 0.0, 0.0]], 2),
    "k_1": ([[5.0, 3.0, 4.0, 3.0], [0.0, 7.0, 7.0, 1.0]], 1),
    "k_m": ([[5.0, 3.0, np.inf, -3.0], [0.0, 7.0, 7.0, 1.0]], 4),
    "signs": ([[-1.0, -2.5, 1e-40, -1e-40, 0.0, 2.0, -np.inf]], 3),
}


def _random_kth_case(seed):
    rng = np.random.default_rng(seed)
    x = rng.gamma(2.0, 3.0, size=(5, 3001)).astype(np.float32)
    x[rng.random(x.shape) < 0.6] = np.inf       # screened-out rows
    x[:, :40] = x[:, 40:80]                      # duplicated bounds
    x[4] = np.inf
    return x, 16


@pytest.mark.parametrize("case", sorted(KTH_CASES) + ["random"])
def test_bisection_kth_matches_top_k(case):
    if case == "random":
        x, k = _random_kth_case(7)
    else:
        rows, k = KTH_CASES[case]
        x = np.asarray(rows, np.float32)
    x = jnp.asarray(x)
    got = jax.jit(engine._kth_smallest_bisect, static_argnums=1)(x, k)
    want = engine._kth_smallest(x, k)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got).view(np.int32),
                                  np.asarray(want).view(np.int32))


LADDER_CASES = {
    # name: (B, per-row set counts, first capacity, max_doublings,
    #        whether a row overflows)
    "fits_first": (2_000, [10, 60], 64, 8, False),
    "two_rungs": (2_000, [10, 900], 64, 8, False),
    "stops_at_b": (700, [10, 699], 64, 8, False),
    "first_is_b": (300, [300, 12], 300, 8, False),
    "past_max_doublings": (5_000, [3, 4_000], 16, 2, True),
    "no_doublings": (500, [100], 64, 0, True),
    "empty": (500, [0, 0], 64, 8, False),
}


@pytest.mark.parametrize("case", sorted(LADDER_CASES))
def test_count_first_cap_matches_escalation(case):
    B, counts, cap, max_doublings, overflows = LADDER_CASES[case]
    keep = _mask(B, counts, seed=len(case))
    want = _escalate_top_k(keep, cap, max_doublings)
    got = engine._screen_and_compact(lambda: keep, cap, B, max_doublings)
    _assert_same(got, want)
    need = max(counts)
    assert engine._ladder_cap(cap, B, max_doublings, need) == \
        want[0].shape[-1]
    assert bool(np.asarray(got[2]).any()) == overflows
