"""Preprocess sampling weights (paper Alg. 1/2, Claims 4.9/4.10).

TPU-native restructuring of the paper's per-subgraph CPU loop
--------------------------------------------------------------
The paper partitions ``G`` into ``q`` overlapping ``2*delta`` windows
``G_i = [i*d, (i+2)*d)`` and computes, per window, an ``s``-weight for every
edge and every spanning-tree edge ``s``.  Every edge belongs to **exactly two
windows** (``own = floor(t/d)`` and ``prev = own-1``; one at the boundaries),
so instead of materializing ragged per-window subgraphs we keep two dense
weight arrays per tree edge:

* ``w_own[s, e]``  — weight of ``e`` for ``s`` inside window ``floor(t_e/d)``
* ``w_prev[s, e]`` — ditto inside window ``floor(t_e/d) - 1`` (0 if absent)

An interval weight-sum inside window ``i`` then splits at the ``(i+1)*d``
time breakpoint: positions before it read ``w_own`` (their own window is
``i``), positions after read ``w_prev``.  Each sum is four gathers into
exclusive prefix-sum arrays held in CSR order — no ragged shapes, identical
total work (each edge processed exactly twice), and fully vectorized over all
``m`` edges simultaneously.

Weight arithmetic is **exact int64** (weights are match counts; paper Table 7
shows W ~ 1e12..1e15, far beyond f32).  See DESIGN.md for the f32 rebased
scheme documented for TPUs without native int64.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache, partial
from typing import Any

import numpy as np

from ..knobs import get_knob
from ..util import ensure_x64
from .graph import TemporalGraph, pad_bucket
from .spanning_tree import AFTER, BEFORE, IN, OUT, SpanningTree

ensure_x64()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from .bisect import seg_lower_bound, seg_upper_bound  # noqa: E402

# f32 holds integers exactly up to 2^24; the pallas dep-sum backend is only
# trusted while every weight prefix stays below this.
_F32_EXACT_MAX = float(2 ** 24)


def depsum_backend(backend: str | None = None) -> str:
    """Resolve the dep-sum backend: explicit arg > env > default "xla".

    "xla"    — exact int64 bisect + prefix gathers (default);
    "pallas" — the kernels/interval_weight fused kernel on f32-cast
               prefixes (CPU-interpret only: the TPU compiler refuses
               it until ROADMAP S2).  Callers must check the
               returned ``exact`` flag and fall back when counts overflow
               f32's exact-integer range (``preprocess`` does this).
    """
    b = backend or get_knob("REPRO_DEPSUM_BACKEND")
    if b not in ("xla", "pallas"):
        raise ValueError(f"REPRO_DEPSUM_BACKEND={b!r} (want xla|pallas)")
    return b


# ---------------------------------------------------------------------------
# result container
# ---------------------------------------------------------------------------
@dataclass
class Weights:
    """Per-tree-edge weight arrays + the prefix sums the sampler needs.

    ``ps_acc_*[s]`` is the exclusive prefix over ``w_*[s]`` permuted into the
    order the *parent* dependency accesses edge ``s`` through: the root uses
    global (time-sorted) edge order, a child with ``alpha=OUT`` uses the
    out-CSR order, ``alpha=IN`` the in-CSR order.  ``ps_pair_*[s]`` is the
    prefix over pair-CSR order (for the ``\\ El`` exclusion of Claim 4.8).
    """

    tree: SpanningTree
    delta: int
    wd: int           # window stride (== delta normally; C3-off: >= span)
    q: Any            # int64 scalar, TRACED (see note below)
    use_c2: bool
    w_own: Any        # [S, m] int64
    w_prev: Any       # [S, m] int64
    ps_acc_own: Any   # [S, m+1]
    ps_acc_prev: Any  # [S, m+1]
    ps_pair_own: Any  # [S, m+1]
    ps_pair_prev: Any  # [S, m+1]
    W_total: Any      # scalar int64
    ps_win: Any       # [q+1] exclusive prefix of per-window totals W_i
    win_lo: Any       # [q] first edge id with t >= i*d
    win_mid: Any      # [q] first edge id with t >= (i+1)*d
    win_hi: Any       # [q] first edge id with t >= (i+2)*d

    @property
    def W_win(self):
        return self.ps_win[1:] - self.ps_win[:-1]

    @property
    def q_pad(self) -> int:
        """Static window-array length (>= q; == q on unpadded graphs)."""
        return int(self.ps_win.shape[0]) - 1


# ``q`` is a DATA field (a traced int64 scalar), not metadata: epoch
# snapshots of a streaming graph (repro.stream) jitter the real window
# count per advance, and a static q would retrace every compiled window
# program each epoch.  The window arrays are shape-stable instead
# (padded to ``q_pad`` with zero-weight windows when the graph asks for
# it), bisection trip counts derive from ``q_pad``, and the real ``q``
# flows through the programs as a traced cutoff (window draw upper
# bound, N_phi cap in validate).
jax.tree_util.register_dataclass(
    Weights,
    data_fields=["q", "w_own", "w_prev", "ps_acc_own", "ps_acc_prev",
                 "ps_pair_own", "ps_pair_prev", "W_total", "ps_win",
                 "win_lo", "win_mid", "win_hi"],
    meta_fields=["tree", "delta", "wd", "use_c2"])


def access_alpha(tree: SpanningTree) -> list[int]:
    """Direction (OUT/IN/0) through which each tree edge is accessed.

    ``alpha_of[root] = 0`` (accessed via the global time order); every other
    tree edge is accessed through its single parent-dependency direction.
    """
    alpha = [0] * tree.num_edges
    for s in range(tree.num_edges):
        for d in tree.deps[s]:
            alpha[d.child] = d.alpha
    return alpha


def _excl(x):
    """Exclusive prefix sum with a leading zero: [m] -> [m+1]."""
    return jnp.concatenate([jnp.zeros((1,), x.dtype), jnp.cumsum(x)])


def _converge_depth(ptr):
    """Bisection steps that converge in every segment of the CSR ``ptr``:
    each step at least halves a segment, so ``bit_length`` of the longest
    one suffices and further steps change nothing.  Read off the graph
    on the device (a traced trip count), so no graph recompiles the DP."""
    longest = jnp.max(ptr[1:] - ptr[:-1], initial=0).astype(jnp.int64)
    return 64 - jax.lax.clz(longest)


# ---------------------------------------------------------------------------
# the vectorized DP
# ---------------------------------------------------------------------------
def make_preprocess_fn(tree: SpanningTree, use_c2: bool = True,
                       backend: str | None = None):
    """Build ``fn(dev, delta, wd, q) -> weight dict`` for a fixed tree.

    Two jits under the hood: the heavy [S, m] weight DP treats ``q`` as a
    traced scalar (ONE compile per tree serves every delta), and only the
    tiny tree-independent window-totals tail (``_window_totals_fn``) is
    shape-specialized on ``q``.

    ``wd`` is the window stride (Constraint 3): windows are
    ``[i*wd, (i+2)*wd)``.  The paper's algorithm has ``wd == delta``; passing
    ``wd >= time_span`` collapses to a single window (C3 disabled — the
    Table 6 ablation).  ``use_c2=False`` drops the ``\\ El`` exclusion
    (Constraint 2 disabled).

    ``backend`` ("xla" | "pallas", default env ``REPRO_DEPSUM_BACKEND``)
    selects the dep-sum inner loop: exact int64 XLA gathers, or the fused
    kernels/interval_weight Pallas kernel on f32 prefixes.  The returned
    dict carries an ``exact`` scalar flag — on the pallas path it is True
    only while every weight prefix stayed inside f32's exact-integer
    range; callers fall back to "xla" when it comes back False.
    """
    backend = depsum_backend(backend)
    wdt = jnp.float32 if backend == "pallas" else jnp.int64
    S = tree.num_edges
    order = [s for s in reversed(tree.topo_down)]   # children before parents
    alpha_of = access_alpha(tree)

    def dep_sum(dev, delta, wd, w_pair: dict, w_csr: dict, d, t, fl, src,
                dst, window: str):
        """Vectorized Claim 4.9 inner sum for one dependency, all edges.

        ``window``: 'own' (i = fl) or 'prev' (i = fl - 1).  Returns [m]
        in the weight dtype of the selected backend.
        """
        c = d.child
        meet = src if d.meet_end == 0 else dst
        if d.alpha == OUT:
            ptr, csr_t = dev["out_ptr"], dev["out_t"]
        else:
            ptr, csr_t = dev["in_ptr"], dev["in_t"]
        it = _converge_depth(ptr)
        p0 = ptr[meet]
        p1 = ptr[meet + 1]

        i = fl if window == "own" else fl - 1
        if d.beta == BEFORE:
            tlo = jnp.maximum(t - delta, i * wd)
            thi = t
        else:
            tlo = t
            thi = jnp.minimum(t + delta, (i + 2) * wd - 1)
        brk = (i + 1) * wd

        pso, psp = w_csr[c]  # prefix over this child's alpha-CSR order
        if backend == "pallas":
            from ..kernels.interval_weight.ops import interval_weight
            lam = interval_weight(csr_t, pso, psp, p0, p1, tlo, thi, brk)
        else:
            plo = seg_lower_bound(csr_t, p0, p1, tlo, iters=it)
            phi = seg_upper_bound(csr_t, p0, p1, thi, iters=it)
            pmid = jnp.clip(seg_lower_bound(csr_t, p0, p1, brk, iters=it),
                            plo, phi)
            lam = (pso[pmid] - pso[plo]) + (psp[phi] - psp[pmid])
        if not use_c2:
            return lam

        # exclusion: parallel edges to the *other* endpoint of e (Claim 4.8)
        if d.alpha == OUT:
            pid = dev["pair_id"] if d.meet_end == 0 else dev["rev_pair_id"]
        else:
            pid = dev["rev_pair_id"] if d.meet_end == 0 else dev["pair_id"]
        has = pid >= 0
        pid0 = jnp.maximum(pid, 0)
        q0 = dev["pair_ptr"][pid0]
        q1 = jnp.where(has, dev["pair_ptr"][pid0 + 1], q0)
        pt = dev["pair_t"]
        ppo, ppp = w_pair[c]
        if backend == "pallas":
            from ..kernels.interval_weight.ops import interval_weight
            el = interval_weight(pt, ppo, ppp, q0, q1, tlo, thi, brk)
        else:
            itp = _converge_depth(dev["pair_ptr"])
            qlo = seg_lower_bound(pt, q0, q1, tlo, iters=itp)
            qhi = seg_upper_bound(pt, q0, q1, thi, iters=itp)
            qmid = jnp.clip(seg_lower_bound(pt, q0, q1, brk, iters=itp),
                            qlo, qhi)
            el = (ppo[qmid] - ppo[qlo]) + (ppp[qhi] - ppp[qmid])
        return lam - el

    def core(dev, delta, wd, q):
        m = dev["t"].shape[0]
        t = dev["t"]
        src = dev["src"].astype(jnp.int64)
        dst = dev["dst"].astype(jnp.int64)
        delta = jnp.asarray(delta, jnp.int64)
        wd = jnp.asarray(wd, jnp.int64)
        q = jnp.asarray(q, jnp.int64)   # traced: only a scalar cutoff here
        fl = t // wd
        own_ok = fl <= q - 1
        prev_ok = fl >= 1
        if "m_real" in dev:
            # padded snapshot (graph.pad_snapshot): entries at positions
            # >= m_real are pad edges — zero their weights so every
            # prefix sum is flat across the pad suffix and the samplers
            # can never select them (m_real == m on unpadded graphs)
            real = jnp.arange(m, dtype=jnp.int64) < dev["m_real"]
            own_ok = own_ok & real
            prev_ok = prev_ok & real

        w_own_l: list = [None] * S
        w_prev_l: list = [None] * S
        w_csr: dict = {}
        w_pair: dict = {}
        prefix_tops: list = []   # last element of every prefix (f32 audit)

        for s in order:
            wo = jnp.ones((m,), wdt)
            wp = jnp.ones((m,), wdt)
            for d in tree.deps[s]:
                wo = wo * dep_sum(dev, delta, wd, w_pair, w_csr, d, t, fl,
                                  src, dst, "own")
                wp = wp * dep_sum(dev, delta, wd, w_pair, w_csr, d, t, fl,
                                  src, dst, "prev")
            wo = jnp.where(own_ok, wo, 0)
            wp = jnp.where(prev_ok, wp, 0)
            w_own_l[s] = wo
            w_prev_l[s] = wp
            # prefix sums in the order this edge is *accessed* through
            if s == tree.root:
                pass  # global order handled below
            else:
                perm = dev["out_edge"] if alpha_of[s] == OUT else dev["in_edge"]
                w_csr[s] = (_excl(wo[perm]), _excl(wp[perm]))
                w_pair[s] = (_excl(wo[dev["pair_edge"]]),
                             _excl(wp[dev["pair_edge"]]))
                prefix_tops += [w_csr[s][0][-1], w_csr[s][1][-1],
                                w_pair[s][0][-1], w_pair[s][1][-1]]

        r = tree.root
        ps_root_own = _excl(w_own_l[r])
        ps_root_prev = _excl(w_prev_l[r])
        prefix_tops += [ps_root_own[-1], ps_root_prev[-1]]

        # stack: root slot of ps_acc_* holds the *global-order* prefix
        ps_acc_own = []
        ps_acc_prev = []
        ps_pair_own = []
        ps_pair_prev = []
        zeros = jnp.zeros((m + 1,), wdt)
        for s in range(S):
            if s == r:
                ps_acc_own.append(ps_root_own)
                ps_acc_prev.append(ps_root_prev)
                ps_pair_own.append(zeros)
                ps_pair_prev.append(zeros)
            else:
                ps_acc_own.append(w_csr[s][0])
                ps_acc_prev.append(w_csr[s][1])
                ps_pair_own.append(w_pair[s][0])
                ps_pair_prev.append(w_pair[s][1])

        out = dict(
            w_own=jnp.stack(w_own_l), w_prev=jnp.stack(w_prev_l),
            ps_acc_own=jnp.stack(ps_acc_own),
            ps_acc_prev=jnp.stack(ps_acc_prev),
            ps_pair_own=jnp.stack(ps_pair_own),
            ps_pair_prev=jnp.stack(ps_pair_prev))
        if backend == "pallas":
            # exact while no prefix total left f32's integer range: every
            # intermediate value is bounded by some prefix's last element
            # (weights are non-negative), so auditing the tops suffices.
            exact = jnp.max(jnp.stack(prefix_tops)) < _F32_EXACT_MAX
            out = {k: (v.astype(jnp.int64)
                       if v.dtype == jnp.float32 else v)
                   for k, v in out.items()}
            out["exact"] = exact
        else:
            out["exact"] = jnp.asarray(True)
        return out

    core_j = jax.jit(core)
    root = tree.root

    def fn(dev, delta, wd, q, q_pad=None):
        out = dict(core_j(dev, delta, wd, q))
        # the q_pad-SHAPED part is a tiny tail over the root prefixes;
        # keeping it out of the core means one heavy compile per tree
        # serves every delta (q is a traced scalar above AND below —
        # only the bucketed array length q_pad is a static shape, so
        # epoch snapshots sharing a window bucket never recompile)
        out.update(_window_totals_fn(int(q if q_pad is None else q_pad))(
            dev["t"], out["ps_acc_own"][root], out["ps_acc_prev"][root],
            wd, q))
        out["W_total"] = out["ps_win"][-1]
        return out

    fn.core = core_j     # the heavy [S, m] program (off-chip compile tests)
    return fn


@lru_cache(maxsize=64)
def _window_totals_fn(q_pad: int):
    """Per-window totals (Claim 4.10 restricted to window i), jitted per
    static array length ``q_pad``; memoized in a small LRU.

    Tree-independent (inputs are just the root's global-order prefixes),
    so one compile serves every tree and candidate at a given ``q_pad``
    — and it always runs on the exact int64 prefixes (on the pallas path
    the core has already cast back), so ``ps_win``/``W_total`` never
    round even when a window total exceeds an individual prefix top.
    The real window count ``q`` is a traced cutoff: slots ``>= q`` get
    ``W_i = 0``, so ``ps_win`` is flat across them and the window draw
    can never land there (``q_pad == q`` on unpadded graphs).
    """
    def f(t, ps_root_own, ps_root_prev, wd, q):
        wd = jnp.asarray(wd, jnp.int64)
        q = jnp.asarray(q, jnp.int64)
        iarr = jnp.arange(q_pad, dtype=jnp.int64)
        win_lo = jnp.searchsorted(t, iarr * wd, side="left")
        win_mid = jnp.searchsorted(t, (iarr + 1) * wd, side="left")
        win_hi = jnp.searchsorted(t, (iarr + 2) * wd, side="left")
        W_i = ((ps_root_own[win_mid] - ps_root_own[win_lo])
               + (ps_root_prev[win_hi] - ps_root_prev[win_mid]))
        W_i = jnp.where(iarr < q, W_i, 0)
        return dict(ps_win=_excl(W_i), win_lo=win_lo,
                    win_mid=win_mid, win_hi=win_hi)

    return jax.jit(f)


def num_windows(time_span: int, wd: int) -> int:
    """q such that windows [i*wd, (i+2)*wd), i in [0, q) cover every match."""
    return max(1, -(-int(time_span + 1) // int(wd)) - 1)


_PREPROCESS_FN_CACHE: dict = {}


def cached_preprocess_fn(tree: SpanningTree, use_c2: bool = True,
                         backend: str | None = None):
    """Memoized ``make_preprocess_fn``: one heavy trace/compile per
    (tree, use_c2, backend) serving every delta — the batch engine calls
    this per job."""
    key = (tree, use_c2, depsum_backend(backend))
    if key not in _PREPROCESS_FN_CACHE:
        _PREPROCESS_FN_CACHE[key] = make_preprocess_fn(
            tree, use_c2=use_c2, backend=key[2])
    return _PREPROCESS_FN_CACHE[key]


def preprocess(g: TemporalGraph, tree: SpanningTree, delta: int,
               dev: dict | None = None, use_c2: bool = True,
               use_c3: bool = True, backend: str | None = None) -> Weights:
    """Alg. 1: weights + prefix structure for the whole graph.

    On the pallas backend, falls back to the exact int64 XLA path when the
    weight audit reports values outside f32's exact-integer range.
    """
    if dev is None:
        dev = g.device_arrays()
    wd = int(delta) if use_c3 else int(g.time_span) + 1
    q = num_windows(g.time_span, wd)
    # padded snapshots bucket the window arrays too, so the whole Weights
    # pytree keeps stable shapes while the sliding window jitters q
    q_pad = pad_bucket(q) if getattr(g, "pad_windows", False) else q
    backend = depsum_backend(backend)
    out = dict(cached_preprocess_fn(tree, use_c2=use_c2, backend=backend)(
        dev, delta, wd, q, q_pad))
    if not bool(out.pop("exact")):
        out = dict(cached_preprocess_fn(tree, use_c2=use_c2, backend="xla")(
            dev, delta, wd, q, q_pad))
        out.pop("exact")
    return Weights(tree=tree, delta=int(delta), wd=wd,
                   q=jnp.asarray(q, jnp.int64), use_c2=use_c2, **out)


# ---------------------------------------------------------------------------
# numpy reference (direct Alg. 1/2 transcription; tiny graphs only)
# ---------------------------------------------------------------------------
def preprocess_ref(g: TemporalGraph, tree: SpanningTree, delta: int):
    """Per-window brute-force weights.  Returns (w[q,S,m], W_i[q]).

    Quadratic in window size — the oracle for ``preprocess`` tests.
    """
    q = g.num_subgraphs(delta)
    S = tree.num_edges
    m = g.m
    w = np.zeros((q, S, m), dtype=np.int64)
    W_i = np.zeros(q, dtype=np.int64)
    order = list(reversed(tree.topo_down))
    src, dst, t = g.src, g.dst, g.t
    for i in range(q):
        lo_t, hi_t = i * delta, (i + 2) * delta
        eids = np.nonzero((t >= lo_t) & (t < hi_t))[0]
        for s in order:
            for e in eids:
                u, v, te = int(src[e]), int(dst[e]), int(t[e])
                prod = 1
                for d in tree.deps[s]:
                    a, b = (u, v) if d.meet_end == 0 else (v, u)
                    total = 0
                    for e2 in eids:
                        t2 = int(t[e2])
                        if d.alpha == OUT:
                            if int(src[e2]) != a or int(dst[e2]) == b:
                                continue
                        else:
                            if int(dst[e2]) != a or int(src[e2]) == b:
                                continue
                        if d.beta == BEFORE:
                            ok = te - delta <= t2 <= te
                        else:
                            ok = te <= t2 <= te + delta
                        if ok:
                            total += int(w[i, d.child, e2])
                    prod *= total
                w[i, s, e] = prod
        W_i[i] = w[i, tree.root, eids].sum()
    return w, W_i


def count_tree_matches_ref(g: TemporalGraph, tree: SpanningTree, delta: int,
                           window: tuple[int, int] | None = None) -> int:
    """Independent brute-force count of delta-partial matches (Def. 4.6).

    Enumerates homomorphisms edge-by-edge down the tree, checking only the
    *relaxed* constraints C1 (adjacent order + delta) and C2 (distinct far
    endpoints).  Restricted to ``window = (lo, hi)`` timestamps when given.
    Cross-validates Claim 4.10 (sum of center weights == #partial matches).
    """
    src, dst, t = g.src, g.dst, g.t
    lo, hi = window if window is not None else (0, int(t[-1]) + 1)
    eids = np.nonzero((t >= lo) & (t < hi))[0]
    count = 0

    def expand(s: int, e: int) -> int:
        u, v, te = int(src[e]), int(dst[e]), int(t[e])
        total = 1
        for d in tree.deps[s]:
            a, b = (u, v) if d.meet_end == 0 else (v, u)
            sub = 0
            for e2 in eids:
                t2 = int(t[e2])
                if d.alpha == OUT:
                    if int(src[e2]) != a or int(dst[e2]) == b:
                        continue
                else:
                    if int(dst[e2]) != a or int(src[e2]) == b:
                        continue
                if d.beta == BEFORE:
                    if not (te - delta <= t2 <= te):
                        continue
                else:
                    if not (te <= t2 <= te + delta):
                        continue
                sub += expand(d.child, e2)
            total *= sub
            if total == 0:
                return 0
        return total

    for e in eids:
        count += expand(tree.root, int(e))
    return count
