"""Validate sampled trees + DeriveCnt (paper Alg. 4/5), vectorized over K.

Validation (Alg. 4) checks the constraints the sampler relaxed:
  (1) the vertex map is 1-1 (C2 only guarantees *adjacent* distinctness);
  (2) all tree-edge timestamps within ``delta``;
  (3) tree-edge timestamps strictly follow the motif's pi order.
``N_phi`` (the number of 2*wd windows containing the match) divides the
derived count — the Constraint-3 multiplicity correction of Lemma 4.12.

DeriveCnt (Alg. 5 / ListCount of Pan et al. [40]) counts the motif matches
extending a valid tree *without enumeration*: each non-tree motif edge maps
to a fixed vertex pair, so its candidates are a time-bounded slice of that
pair's multi-edge list; the number of strictly-time-increasing combinations
is a linear DP over the (time-sorted) candidate lists.  Lists are padded to
a static ``Lmax``; overflow is *detected and reported*, never silently
truncated (the estimator re-runs with a bigger ``Lmax`` if nonzero).

Bound structure per non-tree rank r (pins = sampled tree-edge timestamps):
  lower: strictly above the nearest lower-rank pin, and (closed) >=
         t(max-rank pin) - delta — which is exactly the global delta bound
         whenever rank q-1 is a tree edge;
  upper: strictly below the nearest higher-rank pin, and (closed) <=
         t(min-rank pin) + delta.
The only constraint this leaves out is the first/last coupling
``t_last <= t_first + delta`` when *both* extreme ranks are non-tree edges;
that case runs a guarded outer loop over the first list (linearity of the
DP in its first layer).
"""
from __future__ import annotations

from ..util import ensure_x64

ensure_x64()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from .bisect import (converge_iters, seg_lower_bound,  # noqa: E402
                     seg_upper_bound)
from .motif import TemporalMotif  # noqa: E402
from .spanning_tree import SpanningTree  # noqa: E402

INF = jnp.iinfo(jnp.int64).max // 4


def make_count_fn(tree: SpanningTree, K: int, Lmax: int = 16):
    """Jitted ``fn(dev, wts, samples) -> dict`` of per-sample counts/flags
    (its ops carry the ``validate`` named scope)."""
    motif = tree.motif
    S = tree.num_edges
    nv = motif.num_vertices
    nq = motif.num_edges

    # ---- static schedule ---------------------------------------------------
    # tree-local indices sorted by motif rank (for the pi check)
    rank_order = sorted(range(S), key=lambda s: tree.edge_ids[s])
    tree_ranks = sorted(tree.edge_ids)
    nt_ranks = [r for r in range(nq) if r not in set(tree.edge_ids)]
    local_of_rank = {tree.edge_ids[s]: s for s in range(S)}
    min_pin_local = local_of_rank[tree_ranks[0]]
    max_pin_local = local_of_rank[tree_ranks[-1]]
    coupled = bool(nt_ranks) and (nt_ranks[0] == 0 and nt_ranks[-1] == nq - 1)

    def pin_below(r):  # tree-local index of nearest pin with smaller rank
        c = [x for x in tree_ranks if x < r]
        return local_of_rank[c[-1]] if c else None

    def pin_above(r):
        c = [x for x in tree_ranks if x > r]
        return local_of_rank[c[0]] if c else None

    def fn(dev, wts, samples):
        with jax.named_scope("validate"):
            return count(dev, wts, samples)

    def count(dev, wts, samples):
        it = converge_iters(dev["t"].shape[0])
        E = samples["edges"]          # [K, S]
        phi_v = samples["phi_v"]      # [K, nv]
        t = dev["t"]
        delta = jnp.asarray(wts.delta, jnp.int64)
        wd = jnp.asarray(wts.wd, jnp.int64)
        ts = t[E]                     # [K, S]

        # ---- Alg. 4 validation ------------------------------------------
        sv = jnp.sort(phi_v, axis=1)
        ok_vmap = jnp.all(sv[:, 1:] != sv[:, :-1], axis=1)
        tmin = ts.min(axis=1)
        tmax = ts.max(axis=1)
        ok_delta = (tmax - tmin) <= delta
        ts_ranked = ts[:, jnp.asarray(rank_order)]
        ok_order = jnp.all(ts_ranked[:, 1:] > ts_ranked[:, :-1], axis=1)
        valid = ok_vmap & ok_delta & ok_order

        # N_phi: #windows [i*wd,(i+2)*wd) containing all tree timestamps
        i_hi = jnp.minimum(wts.q - 1, tmin // wd)
        i_lo = jnp.maximum(0, tmax // wd - 1)
        nphi = jnp.clip(i_hi - i_lo + 1, 1, 2)

        # ---- Alg. 5 DeriveCnt --------------------------------------------
        if not nt_ranks:
            cnt = jnp.ones((K,), jnp.int64)
            overflow = jnp.zeros((K,), bool)
        else:
            n = dev["n"].astype(jnp.int64)
            pk = dev["pair_key"]
            P = pk.shape[0]
            t_min_pin = ts[:, min_pin_local]
            t_max_pin = ts[:, max_pin_local]

            t_lists = []
            len_lists = []
            overflow = jnp.zeros((K,), bool)
            iota = jnp.arange(Lmax, dtype=jnp.int64)
            for r in nt_ranks:
                x, y = motif.edges[r]
                u = phi_v[:, x]
                v = phi_v[:, y]
                key = u * n + v
                pp = jnp.searchsorted(pk, key)
                ppc = jnp.minimum(pp, P - 1)
                exists = pk[ppc] == key
                a = dev["pair_ptr"][ppc]
                b = jnp.where(exists, dev["pair_ptr"][ppc + 1], a)
                pt = dev["pair_t"]
                # closed global bounds
                lo_pos = seg_lower_bound(pt, a, b, t_max_pin - delta,
                                         iters=it)
                hi_pos = seg_upper_bound(pt, a, b, t_min_pin + delta,
                                         iters=it)
                lb = pin_below(r)
                if lb is not None:  # strict > pin
                    lo_pos = jnp.maximum(
                        lo_pos, seg_upper_bound(pt, a, b, ts[:, lb],
                                                iters=it))
                ub = pin_above(r)
                if ub is not None:  # strict < pin
                    hi_pos = jnp.minimum(
                        hi_pos, seg_lower_bound(pt, a, b, ts[:, ub],
                                                iters=it))
                ln = jnp.maximum(hi_pos - lo_pos, 0)
                overflow = overflow | (ln > Lmax)
                ln = jnp.minimum(ln, Lmax)
                pos = lo_pos[:, None] + iota[None, :]
                tk = jnp.where(iota[None, :] < ln[:, None],
                               pt[jnp.clip(pos, 0, pt.shape[0] - 1)], INF)
                t_lists.append(tk)        # [K, Lmax], INF-padded
                len_lists.append(ln)

            def chain(f, start_k):
                """Run DP transitions from layer start_k-1 to the end."""
                for k in range(start_k, len(t_lists)):
                    less = t_lists[k - 1][:, :, None] < t_lists[k][:, None, :]
                    f = jnp.sum(f[:, :, None] * less, axis=1)
                    f = jnp.where(t_lists[k] < INF, f, 0)
                return f

            if len(t_lists) == 1 and not coupled:
                cnt = len_lists[0]
            elif not coupled:
                f0 = (t_lists[0] < INF).astype(jnp.int64)
                cnt = chain(f0, 1).sum(axis=1)
            else:
                # guarded outer loop over the first list (delta coupling)
                cnt = jnp.zeros((K,), jnp.int64)
                for jj in range(Lmax):
                    tj = t_lists[0][:, jj]
                    ok_j = tj < INF
                    if len(t_lists) == 1:
                        # single list that is both first and last rank
                        cnt = cnt + ok_j.astype(jnp.int64)
                        continue
                    f = jnp.zeros((K, Lmax), jnp.int64).at[:, jj].set(1)
                    f = jnp.where(ok_j[:, None], f, 0)
                    f = chain(f, 1)
                    last_ok = t_lists[-1] <= (tj[:, None] + delta)
                    cnt = cnt + jnp.sum(f * last_ok, axis=1)

        cnt = jnp.where(valid & ~overflow, cnt, 0)
        # Constraint-3 correction: divide by N_phi, kept exact via 2x scaling
        cnt2 = jnp.where(nphi == 1, 2 * cnt, cnt)
        return dict(cnt=cnt, cnt2=cnt2, nphi=nphi, valid=valid,
                    ok_vmap=ok_vmap,
                    fail_vmap=~ok_vmap,
                    fail_delta=ok_vmap & ~ok_delta,
                    fail_order=ok_vmap & ok_delta & ~ok_order,
                    overflow=overflow)

    return jax.jit(fn)
