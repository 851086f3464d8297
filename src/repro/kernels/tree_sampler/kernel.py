"""Fused spanning-tree sampler: the whole per-sample pipeline in ONE
``pallas_call`` (paper Alg. 3, the TIMEST hot loop).

The XLA path in ``core/sampler.py`` dispatches dozens of small HBM-bound
gather chains per sample batch: a window bisection, a two-piece
center-edge inverse-CDF, then per-child nested bisections with the
Claim-4.8 pair-list exclusion.  This kernel executes the entire top-down
walk per sample block while the CSR time arrays and every per-tree-edge
prefix sum stay VMEM-resident:

1. window  ``i ~ W_i / W``   — bisect the f32 window-prefix CDF;
2. center  ``e0 ~ w_{c,e}``  — two-piece (own|prev) inverse-CDF over the
   window's contiguous edge-id range;
3. children, static ``tree.topo_down`` schedule baked in at trace time:
   branchless fixed-trip bisections over the alpha-CSR segment of the
   meet vertex, then the generalized inverse-CDF of
   ``g(p) = Lambda_prefix(p) - El_prefix(cross(p))`` where ``cross`` is a
   nested bisection into the parallel-edge pair sub-sequence.

Exactness contract: weights are f32 but every prefix is an integer match
count; while all prefix tops stay below 2^24 every comparison the
bisections make is exact, so the kernel's trajectory — and therefore the
sampled edge ids — is **bit-identical** to the exact-int64 XLA path
(``ops.pallas_sampler_eligible`` gates this; ``estimate`` falls back).

Status: CPU-interpret only until ROADMAP S2.  Compiled for a v5e
(m = 65536, M5-3) Mosaic fails while lowering the first bisection
(``kernels/bisect.py``) with a ``RecursionError`` under x64, and the
kernel does uint64 arithmetic in ``randint_from_bits``, which the TPU
has no native units for.  The default ``xla`` backend is the chip path.

Randomness contract: the kernel draws nothing itself.  The window/center
target ``x`` is precomputed outside (its span ``W`` is known on the XLA
side) and each child receives the two raw 64-bit draws of
``jax.random.randint``'s key split; ``randint_from_bits`` replays jax's
exact double-width modular reduction against the in-kernel span
``max(g(phi), 1)``, so the child draws are bit-identical too.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...core.spanning_tree import BEFORE, OUT, SpanningTree
from ..bisect import seg_bisect as _seg_bisect

_I32 = jnp.int32
_F32 = jnp.float32


def randint_from_bits(hi, lo, span):
    """Replay ``jax.random.randint(key, shape, 0, span, int64)`` from the
    two raw 64-bit draws of its internal key split.

    jax's ``_randint`` reduces 128 random bits modulo ``span`` via
    ``((hi % s) * (2^64 % s) + lo % s) % s`` with ``2^64 % s`` computed as
    ``(2^32 % s)^2 % s``.  Identical uint64 arithmetic here; for
    ``span < 2^24`` every intermediate product stays below 2^48.
    """
    span = span.astype(jnp.uint64)
    c = jnp.asarray(1 << 32, jnp.uint64) % span
    mult = (c * c) % span
    return ((hi % span) * mult + (lo % span)) % span


def _monotone(g, lo, hi, r, *, iters: int):
    """core.bisect.monotone_find, VMEM edition (same trajectory)."""

    def body(_, c):
        l, h = c
        mid = (l + h) >> 1
        take_right = (h - l > 1) & (g(mid) <= r)
        l2 = jnp.where(take_right, mid, l)
        h2 = jnp.where((h - l > 1) & ~take_right, mid, h)
        return (l2, h2)

    l, _ = jax.lax.fori_loop(0, iters, body, (lo, hi))
    return l


def _two_piece(pso, psp, lo, mid):
    """C(p) = (PSo[min(p,mid)] - PSo[lo]) + (PSp[max(p,mid)] - PSp[mid])."""
    nmax = pso.shape[0] - 1

    def C(p):
        a = jnp.take(pso, jnp.clip(jnp.minimum(p, mid), 0, nmax))
        b = jnp.take(psp, jnp.clip(jnp.maximum(p, mid), 0, nmax))
        return ((a - jnp.take(pso, jnp.clip(lo, 0, nmax)))
                + (b - jnp.take(psp, jnp.clip(mid, 0, nmax))))

    return C


def build_schedule(tree: SpanningTree):
    """Flatten the static top-down child schedule for trace-time baking.

    One tuple per dependency, in sampling order:
    ``(parent, child, meet_end, alpha, beta, use_rev_pid)`` where
    ``use_rev_pid`` picks ``rev_pair_id`` over ``pair_id`` for the
    Claim-4.8 exclusion list (the parallel edges to the *other* endpoint).
    """
    steps = []
    for s in tree.topo_down:
        for d in tree.deps[s]:
            if d.alpha == OUT:
                use_rev = d.meet_end != 0
            else:
                use_rev = d.meet_end == 0
            steps.append((s, d.child, d.meet_end, d.alpha, d.beta, use_rev))
    return tuple(steps)


def _sampler_kernel(t_ref, src_ref, dst_ref, out_ptr_ref, in_ptr_ref,
                    out_t_ref, in_t_ref, out_edge_ref, in_edge_ref,
                    ppos_out_ref, ppos_in_ref, pair_ptr_ref, pair_t_ref,
                    pair_id_ref, rev_pair_id_ref, ps_win_ref, win_lo_ref,
                    win_mid_ref, win_hi_ref, ps_own_ref, ps_prev_ref,
                    pp_own_ref, pp_prev_ref, x_ref, uhi_ref, ulo_ref,
                    edges_ref, win_ref, *, root: int, schedule, use_c2: bool,
                    it: int, itq: int, delta: int, wd: int, S: int):
    m = t_ref.shape[0]
    x = x_ref[...]                       # [bk] i32 window/center target
    xf = x.astype(_F32)
    ps_win = ps_win_ref[...]
    q = win_lo_ref.shape[0]

    # -- 1. window ---------------------------------------------------------
    zeros = jnp.zeros_like(x)
    win = _seg_bisect(ps_win, zeros, jnp.full_like(x, q), xf,
                      upper=True, iters=itq) - 1
    win = jnp.clip(win, 0, q - 1)
    resid = xf - jnp.take(ps_win, win)

    # -- 2. center edge ----------------------------------------------------
    lo = jnp.take(win_lo_ref[...], win)
    mid = jnp.take(win_mid_ref[...], win)
    hi = jnp.take(win_hi_ref[...], win)
    ps_own = ps_own_ref[...]             # [S, m+1] f32
    ps_prev = ps_prev_ref[...]
    Cc = _two_piece(ps_own[root], ps_prev[root], lo, mid)
    e0 = _monotone(Cc, lo, hi, resid, iters=it)

    edges = [None] * S
    edges[root] = e0

    # -- 3. children, top-down (static schedule) ---------------------------
    t_all = t_ref[...]
    uhi = uhi_ref[...]                   # [bk, S] u64 raw child draws
    ulo = ulo_ref[...]
    for (s, c, meet_end, alpha, beta, use_rev) in schedule:
        e = edges[s]
        meet = jnp.take(src_ref[...] if meet_end == 0 else dst_ref[...], e)
        meet = meet.astype(_I32)
        te = jnp.take(t_all, e)
        if alpha == OUT:
            ptr, csr_t = out_ptr_ref[...], out_t_ref[...]
            csr_edge, pair_pos = out_edge_ref[...], ppos_out_ref[...]
        else:
            ptr, csr_t = in_ptr_ref[...], in_t_ref[...]
            csr_edge, pair_pos = in_edge_ref[...], ppos_in_ref[...]
        p0 = jnp.take(ptr, meet)
        p1 = jnp.take(ptr, meet + 1)
        if beta == BEFORE:
            tlo = jnp.maximum(te - delta, win * wd)
            thi = te
        else:
            tlo = te
            thi = jnp.minimum(te + delta, (win + 2) * wd - 1)
        brk = (win + 1) * wd
        plo = _seg_bisect(csr_t, p0, p1, tlo, upper=False, iters=it)
        phi = _seg_bisect(csr_t, p0, p1, thi, upper=True, iters=it)
        pmid = jnp.clip(_seg_bisect(csr_t, p0, p1, brk, upper=False,
                                    iters=it), plo, phi)
        CL = _two_piece(ps_own[c], ps_prev[c], plo, pmid)

        if use_c2:
            pid_all = rev_pair_id_ref[...] if use_rev else pair_id_ref[...]
            pid = jnp.take(pid_all, e)
            has = pid >= 0
            pid0 = jnp.maximum(pid, 0)
            pair_ptr = pair_ptr_ref[...]
            q0 = jnp.take(pair_ptr, pid0)
            q1 = jnp.where(has, jnp.take(pair_ptr, pid0 + 1), q0)
            pt = pair_t_ref[...]
            qlo = _seg_bisect(pt, q0, q1, tlo, upper=False, iters=it)
            qhi = _seg_bisect(pt, q0, q1, thi, upper=True, iters=it)
            qmid = jnp.clip(_seg_bisect(pt, q0, q1, brk, upper=False,
                                        iters=it), qlo, qhi)
            CE = _two_piece(pp_own_ref[...][c], pp_prev_ref[...][c],
                            qlo, qmid)

            def g(p, CL=CL, CE=CE, pair_pos=pair_pos, qlo=qlo, qhi=qhi):
                cross = _seg_bisect(pair_pos, qlo, qhi, p, upper=False,
                                    iters=it)
                return CL(p) - CE(cross)
        else:
            def g(p, CL=CL):
                return CL(p)

        Wx = g(phi)                      # f32, exact integer under the gate
        span = jnp.maximum(Wx.astype(_I32), 1)
        rx = randint_from_bits(uhi[:, c], ulo[:, c], span).astype(_F32)
        pstar = _monotone(g, plo, phi, rx, iters=it)
        edges[c] = jnp.take(csr_edge, jnp.clip(pstar, 0, m - 1)).astype(_I32)

    edges_ref[...] = jnp.stack([edges[s].astype(_I32) for s in range(S)],
                               axis=1)
    win_ref[...] = win.astype(_I32)


def tree_sampler_call(arrays: dict, x, uhi, ulo, *, root: int, schedule,
                      use_c2: bool, it: int, itq: int, delta: int, wd: int,
                      S: int, bk: int = 1024, interpret: bool = False):
    """One-dispatch sampling of ``K = len(x)`` partial matches.

    ``arrays`` holds the kernel-resident graph/weight structure (i32
    indices/times, f32 prefixes — see ``ops._device_prep``); ``x`` [K] i32
    window/center targets, ``uhi``/``ulo`` [K, S] u64 raw child draws.
    Returns ``(edges [K, S] i32, window [K] i32)``.
    """
    from ..padding import pad_block

    K = x.shape[0]
    bk = min(bk, max(K, 1))
    (x, uhi, ulo), K = pad_block(bk, x, uhi, ulo)
    Kp = x.shape[0]
    grid = (Kp // bk,)

    names = ("t", "src", "dst", "out_ptr", "in_ptr", "out_t", "in_t",
             "out_edge", "in_edge", "pair_pos_out", "pair_pos_in",
             "pair_ptr", "pair_t", "pair_id", "rev_pair_id", "ps_win",
             "win_lo", "win_mid", "win_hi", "ps_acc_own", "ps_acc_prev",
             "ps_pair_own", "ps_pair_prev")
    ins = [arrays[n] for n in names]

    def full(a):
        nd = a.ndim
        return pl.BlockSpec(a.shape, (lambda i: (0,) * nd))

    in_specs = [full(a) for a in ins]
    in_specs += [pl.BlockSpec((bk,), lambda i: (i,)),
                 pl.BlockSpec((bk, S), lambda i: (i, 0)),
                 pl.BlockSpec((bk, S), lambda i: (i, 0))]
    kern = functools.partial(_sampler_kernel, root=root, schedule=schedule,
                             use_c2=use_c2, it=it, itq=itq, delta=delta,
                             wd=wd, S=S)
    edges, win = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((bk, S), lambda i: (i, 0)),
                   pl.BlockSpec((bk,), lambda i: (i,))],
        out_shape=[jax.ShapeDtypeStruct((Kp, S), _I32),
                   jax.ShapeDtypeStruct((Kp,), _I32)],
        interpret=interpret,
    )(*ins, x, uhi, ulo)
    return edges[:K], win[:K]
