"""Pure-jnp oracle: the exact-int64 sampler on precomputed draws.

Same math as ``core.sampler``'s XLA path (int64 prefixes, core.bisect
searches) but consuming the kernel's randomness inputs ``(x, uhi, ulo)``
instead of drawing from a key — so parity tests can pin down whether a
mismatch lives in the kernel arithmetic or in the draw preparation.
"""
from __future__ import annotations

import jax.numpy as jnp

from ...core.bisect import (converge_iters, monotone_find, seg_lower_bound,
                            seg_upper_bound)
from ...core.sampler import bisect_iters
from ...core.spanning_tree import BEFORE, OUT, SpanningTree
from .kernel import randint_from_bits


def _two_piece(ps_own, ps_prev, lo, mid):
    """Cumulative-in-window weight, four lookups a probe:
    ``C(p) = (PSo[min(p,mid)] - PSo[lo]) + (PSp[max(p,mid)] - PSp[mid])``
    (the oracle keeps its own copy of the formula, independent of the
    sampler's one-gather form)."""
    def C(p):
        return ((ps_own[jnp.minimum(p, mid)] - ps_own[lo])
                + (ps_prev[jnp.maximum(p, mid)] - ps_prev[mid]))
    return C


def tree_sampler_ref(tree: SpanningTree, dev, wts, x, uhi, ulo):
    """Exact-int64 reference of the fused kernel; returns the sampler dict."""
    S = tree.num_edges
    nv = tree.motif.num_vertices
    t = dev["t"]
    it = bisect_iters(t.shape[0])
    delta = jnp.asarray(wts.delta, jnp.int64)
    wd = jnp.asarray(wts.wd, jnp.int64)
    r = tree.root
    K = x.shape[0]

    itq = converge_iters(wts.q_pad)
    win = seg_upper_bound(wts.ps_win, jnp.zeros((K,), jnp.int64),
                          jnp.full((K,), wts.q, jnp.int64), x,
                          iters=itq) - 1
    win = jnp.clip(win, 0, wts.q - 1)
    resid = x - wts.ps_win[win]

    lo = wts.win_lo[win]
    mid = wts.win_mid[win]
    hi = wts.win_hi[win]
    Cc = _two_piece(wts.ps_acc_own[r], wts.ps_acc_prev[r], lo, mid)
    e0 = monotone_find(lambda p: Cc(p), lo, hi, resid, iters=it)

    edges = [None] * S
    edges[r] = e0

    for s in tree.topo_down:
        e = edges[s]
        u = dev["src"][e].astype(jnp.int64)
        v = dev["dst"][e].astype(jnp.int64)
        te = t[e]
        for d in tree.deps[s]:
            c = d.child
            meet = u if d.meet_end == 0 else v
            if d.alpha == OUT:
                ptr, csr_t = dev["out_ptr"], dev["out_t"]
                csr_edge, pair_pos = dev["out_edge"], dev["pair_pos_out"]
            else:
                ptr, csr_t = dev["in_ptr"], dev["in_t"]
                csr_edge, pair_pos = dev["in_edge"], dev["pair_pos_in"]
            p0 = ptr[meet]
            p1 = ptr[meet + 1]
            if d.beta == BEFORE:
                tlo = jnp.maximum(te - delta, win * wd)
                thi = te
            else:
                tlo = te
                thi = jnp.minimum(te + delta, (win + 2) * wd - 1)
            brk = (win + 1) * wd
            plo = seg_lower_bound(csr_t, p0, p1, tlo, iters=it)
            phi = seg_upper_bound(csr_t, p0, p1, thi, iters=it)
            pmid = jnp.clip(seg_lower_bound(csr_t, p0, p1, brk,
                                            iters=it), plo, phi)
            CL = _two_piece(wts.ps_acc_own[c], wts.ps_acc_prev[c],
                            plo, pmid)

            if wts.use_c2:
                if d.alpha == OUT:
                    pid = (dev["pair_id"] if d.meet_end == 0
                           else dev["rev_pair_id"])[e]
                else:
                    pid = (dev["rev_pair_id"] if d.meet_end == 0
                           else dev["pair_id"])[e]
                pid = pid.astype(jnp.int64)
                has = pid >= 0
                pid0 = jnp.maximum(pid, 0)
                q0 = dev["pair_ptr"][pid0]
                q1 = jnp.where(has, dev["pair_ptr"][pid0 + 1], q0)
                pt = dev["pair_t"]
                qlo = seg_lower_bound(pt, q0, q1, tlo, iters=it)
                qhi = seg_upper_bound(pt, q0, q1, thi, iters=it)
                qmid = jnp.clip(seg_lower_bound(pt, q0, q1, brk,
                                                iters=it), qlo, qhi)
                CE = _two_piece(wts.ps_pair_own[c], wts.ps_pair_prev[c],
                                qlo, qmid)

                def g(p, CL=CL, CE=CE, pair_pos=pair_pos, qlo=qlo,
                      qhi=qhi, it=it):
                    cross = seg_lower_bound(pair_pos, qlo, qhi, p,
                                            iters=it)
                    return CL(p) - CE(cross)
            else:
                def g(p, CL=CL):
                    return CL(p)

            Wx = g(phi)
            span = jnp.maximum(Wx, 1)
            rx = randint_from_bits(uhi[:, c].astype(jnp.uint64),
                                   ulo[:, c].astype(jnp.uint64),
                                   span).astype(jnp.int64)
            pstar = monotone_find(g, plo, phi, rx, iters=it)
            edges[c] = csr_edge[pstar].astype(jnp.int64)

    E = jnp.stack(edges, axis=1)
    cols = []
    for vtx in range(nv):
        s_loc, end = tree.vertex_source[vtx]
        arr = dev["src"] if end == 0 else dev["dst"]
        cols.append(arr[E[:, s_loc]].astype(jnp.int64))
    phi_v = jnp.stack(cols, axis=1)
    return dict(edges=E, window=win, phi_v=phi_v)
