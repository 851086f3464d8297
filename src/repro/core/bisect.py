"""Branchless fixed-trip binary searches over sorted segments (vectorized).

``jnp.searchsorted`` only bisects a whole array; TIMEST needs millions of
simultaneous bisections *into CSR segments* (temporal out/in/pair lists,
Def. 4.1/4.2) and into *weighted CDFs with excluded sub-sequences*
(Claim 4.8's ``Lambda \\ El``).  All searches below are data-parallel over
arbitrary query batch shapes and run a fixed number of iterations so they
vectorize/jit cleanly (and map 1:1 onto the Pallas `segment_bisect` kernel).

Iteration count: 40 covers segments up to 2^40 elements (m < 10^12).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

ITERS = 40


def converge_iters(n: int) -> int:
    """A fixed trip count that converges any bisection over at most ``n``
    positions: each step at least halves the segment, so ``bit_length(n)``
    steps do; one more and a floor of 8 keep every caller on one count."""
    return max(8, int(n).bit_length() + 1)


def seg_lower_bound(vals: jnp.ndarray, lo: jnp.ndarray, hi: jnp.ndarray,
                    target: jnp.ndarray, iters: int = ITERS) -> jnp.ndarray:
    """Smallest ``p in [lo, hi]`` with ``vals[p] >= target`` (``hi`` if none).

    ``vals`` must be non-decreasing inside every queried ``[lo, hi)`` segment.
    ``lo/hi/target`` broadcast together; gathers are clamped so ``lo == hi``
    (empty segment) is safe.
    """
    lo = jnp.asarray(lo)
    hi = jnp.asarray(hi)
    nmax = vals.shape[0] - 1

    def body(_, c):
        l, h = c
        mid = (l + h) >> 1
        v = vals[jnp.clip(mid, 0, nmax)]
        active = l < h
        go_right = active & (v < target)
        l2 = jnp.where(go_right, mid + 1, l)
        h2 = jnp.where(active & ~go_right, mid, h)
        return (l2, h2)

    l, _ = jax.lax.fori_loop(0, iters, body, (lo, hi))
    return l


def seg_upper_bound(vals: jnp.ndarray, lo: jnp.ndarray, hi: jnp.ndarray,
                    target: jnp.ndarray, iters: int = ITERS) -> jnp.ndarray:
    """Smallest ``p in [lo, hi]`` with ``vals[p] > target`` (``hi`` if none)."""
    lo = jnp.asarray(lo)
    hi = jnp.asarray(hi)
    nmax = vals.shape[0] - 1

    def body(_, c):
        l, h = c
        mid = (l + h) >> 1
        v = vals[jnp.clip(mid, 0, nmax)]
        active = l < h
        go_right = active & (v <= target)
        l2 = jnp.where(go_right, mid + 1, l)
        h2 = jnp.where(active & ~go_right, mid, h)
        return (l2, h2)

    l, _ = jax.lax.fori_loop(0, iters, body, (lo, hi))
    return l


def monotone_find(g, lo: jnp.ndarray, hi: jnp.ndarray, r: jnp.ndarray,
                  iters: int = ITERS) -> jnp.ndarray:
    """Generalized inverse CDF: smallest ``p in [lo, hi)`` with ``g(p+1) > r``.

    ``g`` is any (vectorized) non-decreasing integer function of position with
    ``g(lo) == 0``; requires ``0 <= r < g(hi)``.  Used for weighted sampling
    where ``g`` is a prefix-sum *difference* (Lambda minus the excluded pair
    sub-list), which is not a plain array — hence the callback form.

    Invariant maintained: ``g(l) <= r < g(h)``; returns ``l`` with
    ``g(l) <= r < g(l+1)`` — the sampled position (its effective weight is
    positive, so excluded/zero-weight slots are never returned).
    """
    lo = jnp.asarray(lo)
    hi = jnp.asarray(hi)

    def body(_, c):
        l, h = c
        mid = (l + h) >> 1
        take_right = (h - l > 1) & (g(mid) <= r)
        l2 = jnp.where(take_right, mid, l)
        h2 = jnp.where((h - l > 1) & ~take_right, mid, h)
        return (l2, h2)

    l, _ = jax.lax.fori_loop(0, iters, body, (lo, hi))
    return l


def excluded_find(CL, CE, pair_pos: jnp.ndarray, plo: jnp.ndarray,
                  phi: jnp.ndarray, qlo: jnp.ndarray, qhi: jnp.ndarray,
                  r: jnp.ndarray, iters: int = ITERS) -> jnp.ndarray:
    """Inverse CDF of ``g(p) = CL(p) - CE(cross(p))`` over ``[plo, phi)``.

    ``cross(p)`` is the smallest ``k in [qlo, qhi]`` with
    ``pair_pos[k] >= p``: the positions ``pair_pos[qlo:qhi]`` (strictly
    increasing, inside ``[plo, phi)``) are excluded slots whose ``CE``
    weight equals their ``CL`` weight, so each has effective weight 0.
    Returns what ``monotone_find(g, plo, phi, r)`` returns, with two
    bisections one after the other instead of one nested in the other:

    A. ``kappa``: the smallest ``k in [qlo, qhi]`` with ``k == qhi`` or
       ``g(pair_pos[k]) = CL(pair_pos[k]) - CE(k) > r``, so the answer
       lies in the run of slots between ``pair_pos[kappa - 1]`` and
       ``pair_pos[kappa]``, where ``cross`` is ``kappa``;
    B. ``monotone_find`` of ``h(p) = CL(p) - CE(kappa)`` over the whole
       ``[plo, phi)``: ``h`` is non-decreasing, equals ``g`` on that run
       and its end, lies at or below ``g`` before it and above ``r`` after
       it, so its one crossing of ``r`` is ``g``'s.  With the same bounds
       as the nested search it also returns the same when ``g(phi) == 0``.

    ``CL`` and ``CE`` are the sampler's one-gather probes
    (``core.sampler._two_piece``: their loop-invariant lookups are done
    when they are built), so a step of phase A gathers three times
    (``pair_pos``, ``CL``, ``CE``) and a step of phase B once.
    """
    qlo, qhi = jnp.asarray(qlo), jnp.asarray(qhi)
    nmax = pair_pos.shape[0] - 1

    def body(_, c):
        l, h = c
        mid = (l + h) >> 1
        pos = pair_pos[jnp.clip(mid, 0, nmax)].astype(qlo.dtype)
        active = l < h
        go_right = active & (CL(pos) - CE(mid) <= r)
        l2 = jnp.where(go_right, mid + 1, l)
        h2 = jnp.where(active & ~go_right, mid, h)
        return (l2, h2)

    kappa, _ = jax.lax.fori_loop(0, iters, body, (qlo, qhi))
    ce = CE(kappa)
    return monotone_find(lambda p: CL(p) - ce, plo, phi, r, iters=iters)


@partial(jax.jit, static_argnames=("side",))
def _ss(vals, targets, side):
    return jnp.searchsorted(vals, targets, side=side)
