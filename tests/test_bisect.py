"""``excluded_find`` (two sequential bisections) against the nested search.

The children's C2 draw samples the inverse CDF of ``g(p) = CL(p) -
CE(cross(p))`` over a CSR range ``[plo, phi)`` whose excluded pair slots
``pair_pos[qlo:qhi]`` carry zero effective weight.  The nested form runs
``seg_lower_bound`` for ``cross`` inside every step of ``monotone_find``;
``excluded_find`` must return the same position for every target.

Both searches probe ``_two_piece``'s one-gather CDF, which must give the
four-lookup sum it folds, to the integer, wherever it is probed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bisect import (converge_iters, excluded_find, monotone_find,
                               seg_lower_bound)
from repro.core.sampler import _two_piece

PAD = 5      # foreign slots before and after the segment
SLOTS = 20 + 2 * PAD
R = 64       # targets per case: every r in [0, W) (W <= 60 here)


def _case(name):
    """``(n, excluded offsets, own weights, prev weights, mid offset)``."""
    r = np.random.default_rng(3)
    w = r.integers(1, 4, 12)
    v = r.integers(1, 4, 12)
    return {
        "no_pairs": (12, [], w, v, 6),
        "all_but_one_excluded": (12, [i for i in range(12) if i != 7], w, v,
                                 5),
        "excluded_at_both_ends": (12, [0, 4, 11], w, v, 8),
        "zero_weight_not_excluded": (12, [2, 3, 9],
                                     np.array([0, 2, 1, 1, 0, 0, 3, 0, 1, 2,
                                               0, 0]),
                                     np.array([1, 0, 1, 2, 0, 3, 0, 0, 2, 1,
                                               0, 1]), 4),
        "run_straddles_break": (12, [1, 3, 5, 6, 7, 10], w, v, 6),
        "break_at_start": (12, [0, 1, 8], w, v, 0),
        "break_at_end": (12, [2, 9, 10], w, v, 12),
        "length_one": (1, [], w[:1], v[:1], 1),
        "length_one_excluded": (1, [0], w[:1], v[:1], 0),
        "every_slot_excluded": (12, list(range(12)), w, v, 6),
    }[name]


def _arrays(n, excl, w_own, w_prev, mid):
    """Embed the segment and its pair list among foreign slots and pairs,
    at fixed shapes so that one compiled search serves every case."""
    own = np.ones(SLOTS, np.int64)
    prev = np.full(SLOTS, 2, np.int64)
    own[PAD:PAD + n] = w_own[:n]
    prev[PAD:PAD + n] = w_prev[:n]
    plo, phi, pmid = PAD, PAD + n, PAD + mid
    excl = np.asarray(excl, np.int64) + PAD
    pair_pos = np.full(SLOTS + 3, SLOTS - 1, np.int64)
    pair_pos[:2] = [0, 2]
    pair_pos[2:2 + len(excl)] = excl
    qlo, qhi = 2, 2 + len(excl)
    qmid = qlo + int(np.sum(excl < pmid))

    def ps(x):
        return np.concatenate([[0], np.cumsum(x)])
    return (ps(own), ps(prev), ps(own[pair_pos]), ps(prev[pair_pos]),
            pair_pos.astype(np.int32), plo, phi, pmid, qlo, qhi, qmid)


@jax.jit
def _searches(ps_own, ps_prev, ps_pair_own, ps_pair_prev, pair_pos, plo,
              phi, pmid, qlo, qhi, qmid):
    """(W, nested result, excluded_find result, g(p), g(p + 1))."""
    it = converge_iters(SLOTS)
    CL = _two_piece(ps_own, ps_prev, plo, pmid)
    CE = _two_piece(ps_pair_own, ps_pair_prev, qlo, qmid)
    W = CL(phi) - CE(qhi)
    rx = jnp.minimum(jnp.arange(R, dtype=jnp.int64), jnp.maximum(W - 1, 0))
    b = lambda x: jnp.full((R,), x, jnp.int64)  # noqa: E731

    def g(p):
        cross = seg_lower_bound(pair_pos, b(qlo), b(qhi), p, iters=it)
        return CL(p) - CE(cross)

    want = monotone_find(g, b(plo), b(phi), rx, iters=it)
    got = excluded_find(CL, CE, pair_pos, b(plo), b(phi), b(qlo), b(qhi),
                        rx, iters=it)
    return W, g(b(phi)), want, got, g(got), g(got + 1)


@pytest.mark.parametrize("name", [
    "no_pairs", "all_but_one_excluded", "excluded_at_both_ends",
    "zero_weight_not_excluded", "run_straddles_break", "break_at_start",
    "break_at_end", "length_one", "length_one_excluded",
    "every_slot_excluded", "random"])
def test_excluded_find_matches_nested(name):
    if name == "random":
        r = np.random.default_rng(7)
        cases = []
        for _ in range(200):
            n = int(r.integers(1, 21))
            excl = np.flatnonzero(r.random(n) < r.random())
            cases.append((n, excl, r.integers(0, 4, n), r.integers(0, 4, n),
                          int(r.integers(0, n + 1))))
    else:
        cases = [_case(name)]
    for case in cases:
        W, g_phi, want, got, g_lo, g_hi = map(
            np.asarray, _searches(*_arrays(*case)))
        assert W < R and (g_phi == W).all()
        np.testing.assert_array_equal(got, want, err_msg=f"{name}: {case}")
        if W:
            # every drawn position carries positive effective weight
            assert (g_hi > g_lo).all()


def _prefixes(values, n, rng):
    """Two int64 rows of length ``n + 1`` (own, prev); ``near_2_62`` starts
    at 2^62 and climbs past 2^63, so the sums wrap."""
    if values == "small":
        return [np.concatenate([[0], np.cumsum(rng.integers(0, 4, n))])
                for _ in range(2)]
    if values == "near_2_62":
        return [(1 << 62) + np.concatenate(
            [[0], np.cumsum(rng.integers(1 << 59, 1 << 60, n))])
            for _ in range(2)]
    info = np.iinfo(np.int64)
    return [rng.integers(info.min, info.max, n + 1, dtype=np.int64,
                         endpoint=True) for _ in range(2)]


def _four_gather(own, prev, lo, mid, p):
    """``(PSo[min(p,mid)] - PSo[lo]) + (PSp[max(p,mid)] - PSp[mid])`` in
    numpy int64, which wraps as the device does."""
    return ((own[np.minimum(p, mid)] - own[lo])
            + (prev[np.maximum(p, mid)] - prev[mid]))


N = 9
#: ``(lo, mid, hi)`` of one scalar window each; ``exhaustive`` takes every
#: ``lo <= mid <= hi`` in ``[0, N]`` as lanes of one batch
LAYOUTS = {
    "interior": (2, 5, 8),
    "lo_eq_mid": (3, 3, 7),
    "mid_eq_hi": (1, 6, 6),
    "empty": (4, 4, 4),
    "whole": (0, 4, N),
}


@pytest.mark.parametrize("values", ["small", "near_2_62", "full_range"])
@pytest.mark.parametrize("layout", [*LAYOUTS, "exhaustive"])
def test_two_piece_one_gather_matches_four(layout, values):
    """The folded one-gather ``_two_piece`` gives the four-gather sum, to
    the integer, at every ``p`` in ``[lo, hi]`` (``p == mid`` included)."""
    with np.errstate(over="ignore"):
        own, prev = _prefixes(values, N, np.random.default_rng(11))
        if layout == "exhaustive":
            lanes = np.array([(lo, mid, p)
                              for lo in range(N + 1)
                              for mid in range(lo, N + 1)
                              for hi in range(mid, N + 1)
                              for p in range(lo, hi + 1)], np.int64).T
            lo, mid, p = lanes
        else:
            lo, mid, hi = LAYOUTS[layout]
            p = np.arange(lo, hi + 1, dtype=np.int64)
        want = _four_gather(own, prev, lo, mid, p)
    C = _two_piece(jnp.asarray(own), jnp.asarray(prev), jnp.asarray(lo),
                   jnp.asarray(mid))
    np.testing.assert_array_equal(np.asarray(C(jnp.asarray(p))), want)
