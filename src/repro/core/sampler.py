"""Spanning-tree sampling (paper Alg. 3), vectorized over K samples.

Per Lemma 4.11, every delta-partial match ``phi`` must come out with
probability exactly ``N_phi / W``.  The sampler is **integer-exact**: all
CDFs are int64 prefix sums of match counts, random targets are uniform int64
draws, and positions are found by generalized inverse-CDF bisection — no
floating-point probability ever enters, so the distribution is exact up to
the (negligible, < 2^-40) modulo bias of ``jax.random.randint``.

Pipeline per sample (all steps data-parallel over K):

1. window  ``i  ~  W_i / W``          — bisect the window-prefix CDF;
2. center  ``e0 ~  w_{c,e} / W_i``    — two-piece (own|prev split at the
   ``(i+1)*wd`` breakpoint) CDF over the window's contiguous edge-id range;
   each probe of a two-piece CDF is ONE gather from the own|prev table,
   its offsets gathered once before the bisection (``_two_piece``);
3. children top-down (static tree schedule): candidate list =
   alpha-CSR segment of the meet vertex, window-truncated time bounds,
   minus the parallel-edge pair list (Claim 4.8) — the inverse CDF of
   ``g(p) = Lambda_prefix(p) - El_prefix(cross(p))``, found by two
   bisections in turn (``bisect.excluded_find``): one over the pair list
   for the run between excluded slots that holds the target, then one
   over the CSR range with that run's ``cross`` held fixed.

The phases carry ``jax.named_scope`` names — ``sample/window``,
``sample/center``, ``sample/child`` (every bound, pair and inverse-CDF
bisection of the children) and ``sample/vertex_map`` — and the cohort
reduction ``score``, so a profiler trace of the window program names
its ops by phase.  Scopes are HLO metadata only: the samples are
unchanged.
"""
from __future__ import annotations

from ..knobs import get_knob
from ..util import ensure_x64

ensure_x64()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from .bisect import (converge_iters, excluded_find,  # noqa: E402
                     monotone_find, seg_lower_bound, seg_upper_bound)
from .spanning_tree import BEFORE, OUT, SpanningTree  # noqa: E402


def bisect_iters(m: int) -> int:
    """Adaptive bisection depth: ceil(log2(m))+1 covers any segment of an
    m-edge graph (vs a conservative fixed 40 — §Perf C1).
    ``REPRO_BISECT_ITERS`` overrides (A/B tuning)."""
    return get_knob("REPRO_BISECT_ITERS") or converge_iters(m)


def sampler_backend(backend: str | None = None) -> str:
    """Resolve the sampler backend: explicit arg > env > default "xla".

    "xla"    — the vectorized gather-chain sampler below (default);
    "pallas" — the kernels/tree_sampler fused kernel: the whole per-sample
               pipeline (window draw, center edge, every child bisection)
               in ONE ``pallas_call`` over VMEM-resident CSR times and f32
               prefix sums.  CPU-interpret only: the TPU compiler refuses
               it until ROADMAP S2.  Bit-identical to "xla" while every weight
               prefix stays inside f32's exact-integer range (< 2^24);
               callers gate on ``tree_sampler.ops.pallas_sampler_eligible``
               and fall back to "xla" otherwise (``estimate`` does this).
    """
    b = backend or get_knob("REPRO_SAMPLER_BACKEND")
    if b not in ("xla", "pallas"):
        raise ValueError(f"REPRO_SAMPLER_BACKEND={b!r} (want xla|pallas)")
    return b


def _two_piece(ps_own, ps_prev, lo, mid):
    """Cumulative-in-window weight C(p) built from the own/prev split.

    ``C(p) = (PSo[min(p,mid)] - PSo[lo]) + (PSp[max(p,mid)] - PSp[mid])``;
    positions < mid are in their own window, >= mid in their prev window.
    Only one lookup depends on ``p``: ``PSo[p]`` below ``mid``, ``PSp[p]``
    from it on.  So the three others are gathered here, once, into the
    offsets of the two pieces, and ``C(p)`` is one gather from the table
    ``[PSo | PSp]`` plus its piece's offset.  int64 wraps, so the
    regrouped sums are the same integers.
    """
    n = ps_own.shape[-1]
    table = jnp.concatenate([ps_own, ps_prev])
    base = ps_own[lo]
    off_own = -base
    off_prev = ps_own[mid] - base - ps_prev[mid]

    def C(p):
        own = p < mid
        return (table[jnp.where(own, p, p + n)]
                + jnp.where(own, off_own, off_prev))
    return C


def make_sample_fn(tree: SpanningTree, K: int, backend: str | None = None,
                   guard: bool = True):
    """``fn(dev, wts, key) -> samples`` drawing K partial matches.

    Returns dict with ``edges [K, S]`` (graph edge id per tree-local edge),
    ``window [K]`` and ``phi_v [K, |V|]`` (the vertex map).

    ``backend`` ("xla" | "pallas", default env ``REPRO_SAMPLER_BACKEND``)
    selects the execution path; both draw bit-identical samples.  With
    ``guard=True`` (the default) the pallas path checks eligibility
    (f32-exact weights, int32 time bounds, VMEM budget) per call and falls
    back to xla — callers embedding the fn inside a jit/scan (where the
    host-side check cannot run) pass ``guard=False`` and must gate
    eligibility themselves, as ``estimate()`` does.
    """
    backend = sampler_backend(backend)
    if backend == "pallas":
        from ..kernels.tree_sampler.ops import (make_pallas_sample_fn,
                                                pallas_sampler_eligible)
        p_fn = make_pallas_sample_fn(tree, K)
        if not guard:
            return p_fn
        x_fn = _make_sample_fn_xla(tree, K)

        def fn(dev, wts, key):
            ok, _why = pallas_sampler_eligible(dev, wts)
            return (p_fn if ok else x_fn)(dev, wts, key)

        return fn
    return _make_sample_fn_xla(tree, K)


def make_batched_sample_fn(tree: SpanningTree, K: int,
                           backend: str | None = None):
    """``fn(dev, wts, keys [J, 2]) -> samples`` batched over a leading
    key axis — the engine's cross-job fusion path.

    ``jax.vmap`` of the unguarded single-key fn: J jobs' chunks draw
    through ONE program (arrays come back with a leading ``[J]`` axis),
    each job's samples bit-identical to a solo ``make_sample_fn`` call
    with its key.  Unguarded like ``guard=False`` — the engine resolves
    pallas eligibility per job at plan time, before keys are stacked.
    """
    fn = make_sample_fn(tree, K, backend=backend, guard=False)
    return jax.vmap(fn, in_axes=(None, None, 0))


def make_cohort_count_fn(lane_trees, K: int, Lmax: int = 16,
                         keys: tuple = ("cnt2", "valid", "fail_vmap",
                                        "fail_delta", "fail_order",
                                        "overflow")):
    """Score ONE shared sample batch against every lane motif.

    ``fn(dev, wts, samples) -> {key: [J, M] int64}``: ``samples`` is a
    ``make_batched_sample_fn`` batch (leading ``[J]`` stream axis) and
    lane ``l`` of the ``[M]`` motif axis re-validates the SAME instances
    under its own tree's pi-order and runs its own DeriveCnt DP
    (``core.validate.make_count_fn``), reduced over the chunk axis.

    This is the tree-cohort accept/reject (odeN-style): the instance
    stream is drawn once per (seed, chunk) from the shared tree
    *signature*, and each registered motif derives its accept/reject
    only from that shared sample and its own spec — never from a
    per-motif key (lint rule ``det-cohort-key`` bans folding a motif or
    lane index into a sampling key here).  Because signature-equal trees
    induce the same Alg. 3 instance distribution, every lane's
    ``E[cnt2]`` is its own motif's unbiased count, and its sums are
    bit-identical to a solo run of that motif at the same seed — which
    is what keeps cohort membership invisible in the results.
    """
    from .validate import make_count_fn
    count_fns = tuple(jax.vmap(make_count_fn(t, K, Lmax=Lmax),
                               in_axes=(None, None, 0))
                      for t in lane_trees)

    def fn(dev, wts, samples):
        outs = [cf(dev, wts, samples) for cf in count_fns]
        with jax.named_scope("score"):
            return {k: jnp.stack([o[k].sum(axis=1).astype(jnp.int64)
                                  for o in outs], axis=1)
                    for k in keys}

    return fn


# ---------------------------------------------------------------------------
# witness extraction: deterministic per-chunk reservoir over accepted matches
# ---------------------------------------------------------------------------
#: int64 priority sentinel meaning "no accepted match in this slot" —
#: reservoir rows carrying it are padding the host drops.
WITNESS_SENTINEL = (1 << 63) - 1


def splitmix64(x):
    """Device-side splitmix64 finalizer over uint64 lanes — the same
    bijective 64-bit hash as ``resilience.retry._splitmix64`` on the
    host (uint64 arithmetic wraps mod 2^64, matching the host mask)."""
    x = x + jnp.uint64(0x9E3779B97F4A7C15)
    x = (x ^ (x >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
    return x ^ (x >> jnp.uint64(31))


def witness_priority(seed, j, K: int):
    """Reservoir priorities for chunk ``j``: one int64 in
    ``[0, WITNESS_SENTINEL)`` per sample position, a pure function of
    ``(seed, chunk, position)`` — never the motif, cohort lane or mesh
    shape (the det-cohort-key discipline, applied to witness selection),
    so the surviving witnesses are bit-identical regardless of which
    other motifs joined the job's cohort or how chunks were sharded."""
    base = splitmix64(jnp.asarray(seed, jnp.uint64)
                      ^ splitmix64(jnp.asarray(j, jnp.uint64)))
    h = splitmix64(base ^ jnp.arange(K, dtype=jnp.uint64))
    return jnp.minimum((h >> jnp.uint64(1)).astype(jnp.int64),
                       WITNESS_SENTINEL - 1)


def make_witness_fn(tree: SpanningTree, K: int, Lmax: int = 16,
                    n_wit: int = 8, backend: str | None = None):
    """``fn(dev, wts, key, j, seed) -> dict``: the chunk's top-``n_wit``
    accepted full-match witnesses by deterministic reservoir priority.

    The caller passes the SAME ``fold_in(base_key, j)`` key the counting
    path uses for chunk ``j``, so the witness stream re-draws exactly the
    instances the estimate counted — witness capture is execution-only
    and the count path (and its accumulators) is never touched.  Samples
    are scored with the tree's own count fn; the ``n_wit`` *accepted*
    ones (``valid & ~overflow & cnt2 > 0``) with the smallest
    ``witness_priority`` survive, rejected slots get the sentinel.

    Returns ``prio [n]``, ``eids [n, S]`` (graph edge ids, tree-local
    order), ``src``/``dst``/``t [n, S]`` (gathered on device so the host
    pulls ``n_wit`` rows, never the full edge arrays) and ``cnt2 [n]``
    (the DeriveCnt extension count of each witness's tree instance).
    Unjitted (like ``make_sample_fn`` with ``guard=False``): the engine
    embeds it in its jitted witness window scan.
    """
    from .validate import make_count_fn
    s_fn = make_sample_fn(tree, K, backend=backend, guard=False)
    c_fn = make_count_fn(tree, K, Lmax=Lmax)

    def fn(dev, wts, key, j, seed):
        samples = s_fn(dev, wts, key)
        out = c_fn(dev, wts, samples)
        accepted = out["valid"] & ~out["overflow"] & (out["cnt2"] > 0)
        prio = jnp.where(accepted, witness_priority(seed, j, K),
                         WITNESS_SENTINEL)
        order = jnp.argsort(prio)[:n_wit]
        E = samples["edges"][order]                     # [n_wit, S]
        return dict(prio=prio[order], eids=E,
                    src=dev["src"][E].astype(jnp.int64),
                    dst=dev["dst"][E].astype(jnp.int64),
                    t=dev["t"][E].astype(jnp.int64),
                    cnt2=out["cnt2"][order].astype(jnp.int64))

    return fn


def _make_sample_fn_xla(tree: SpanningTree, K: int):
    """The XLA gather-chain sampler (exact int64 throughout)."""
    S = tree.num_edges
    nv = tree.motif.num_vertices

    def fn(dev, wts, key):
        with jax.named_scope("sample"):
            return draw(dev, wts, key)

    def draw(dev, wts, key):
        t = dev["t"]
        it = bisect_iters(t.shape[0])
        delta = jnp.asarray(wts.delta, jnp.int64)
        wd = jnp.asarray(wts.wd, jnp.int64)
        r = tree.root
        keys = jax.random.split(key, S + 2)

        # -- 1. window ---------------------------------------------------
        with jax.named_scope("window"):
            W = jnp.maximum(wts.W_total, 1)
            x = jax.random.randint(keys[0], (K,), 0, W, dtype=jnp.int64)
            # trip count from the STATIC window-array length (>= the traced
            # real q; extra iterations are converged no-ops) — wts.q itself
            # is traced so epoch snapshots never retrace on window count
            itq = converge_iters(wts.q_pad)
            win = seg_upper_bound(wts.ps_win, jnp.zeros((K,), jnp.int64),
                                  jnp.full((K,), wts.q, jnp.int64), x,
                                  iters=itq) - 1
            win = jnp.clip(win, 0, wts.q - 1)
            resid = x - wts.ps_win[win]

        # -- 2. center edge ----------------------------------------------
        with jax.named_scope("center"):
            lo = wts.win_lo[win]
            mid = wts.win_mid[win]
            hi = wts.win_hi[win]
            Cc = _two_piece(wts.ps_acc_own[r], wts.ps_acc_prev[r], lo, mid)
            e0 = monotone_find(Cc, lo, hi, resid, iters=it)

        edges = [None] * S
        edges[r] = e0

        # -- 3. children, top-down (static schedule) ----------------------
        with jax.named_scope("child"):
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
                        csr_edge = dev["out_edge"]
                        pair_pos = dev["pair_pos_out"]
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
                        CE = _two_piece(wts.ps_pair_own[c],
                                        wts.ps_pair_prev[c], qlo, qmid)
                        # every pair slot in [qlo, qhi) lies in [plo, phi)
                        Wx = CL(phi) - CE(qhi)
                    else:
                        Wx = CL(phi)

                    rx = jax.random.randint(keys[2 + c], (K,), 0,
                                            jnp.maximum(Wx, 1),
                                            dtype=jnp.int64)
                    if wts.use_c2:
                        pstar = excluded_find(CL, CE, pair_pos, plo, phi,
                                              qlo, qhi, rx, iters=it)
                    else:
                        pstar = monotone_find(CL, plo, phi, rx, iters=it)
                    edges[c] = csr_edge[pstar].astype(jnp.int64)

        E = jnp.stack(edges, axis=1)  # [K, S]
        # vertex map from the static vertex_source table
        with jax.named_scope("vertex_map"):
            cols = []
            for vtx in range(nv):
                s_loc, end = tree.vertex_source[vtx]
                arr = dev["src"] if end == 0 else dev["dst"]
                cols.append(arr[E[:, s_loc]].astype(jnp.int64))
            phi_v = jnp.stack(cols, axis=1)  # [K, nv]
        return dict(edges=E, window=win, phi_v=phi_v)

    return jax.jit(fn)
