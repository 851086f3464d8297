"""Temporal multigraph container (paper Sec. 4 preliminaries).

Host-side construction in numpy; `.device_arrays()` ships the index structure
to jax.  Everything the TPU-side DP/sampler needs is *sorted + CSR*:

* edge arrays ``src/dst/t`` sorted globally by ``(t, id)``;
* out-CSR: edges grouped by source, time-sorted inside each group;
* in-CSR: ditto by destination;
* pair-CSR: edges grouped by the ordered pair ``(src, dst)`` (the multi-edge
  lists ``El_{u,v}`` of Def. 4.2), time-sorted;
* cross-indices mapping each pair-CSR slot to its position inside the out-CSR
  of ``src`` and the in-CSR of ``dst`` — these drive the masked inverse-CDF
  sampler (``L = Lambda \\ El``, Claim 4.8) without materialising set minus;
* per-edge ``pair_id`` and ``rev_pair_id`` (the pair (dst,src), -1 if absent).

Timestamps are normalised to start at 0 (paper Sec. 4).

Padded snapshots (the streaming seam)
-------------------------------------
``pad_snapshot`` grows a graph's arrays to power-of-two buckets so that a
*sequence* of graphs (the epoch snapshots of ``repro.stream``) presents
stable array shapes to jax — the engine's compiled window programs and
the preprocess DP then re-hit their jit caches across epochs instead of
retracing every advance.  Pad entries are a pure SUFFIX of every array:
pad edges connect two dedicated pad vertices (ids above every real
vertex) at the last real timestamp, so they sort after every real entry
in the global, out-, in- and pair-CSR orders and real entries keep the
exact positions they have in the unpadded graph.  ``m_real`` (shipped as
a traced scalar in ``device_arrays``) lets the weight DP zero pad-edge
weights, which makes every prefix sum flat across the pad suffix — the
inverse-CDF samplers can then never select a pad edge, and estimates on
a padded graph are bit-identical to the unpadded graph's.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any

import numpy as np


@dataclass
class TemporalGraph:
    n: int                      # vertices
    m: int                      # temporal edges
    src: np.ndarray             # [m] int32, sorted by (t, id)
    dst: np.ndarray             # [m] int32
    t: np.ndarray               # [m] int64, non-decreasing, starts at 0
    # out-CSR (grouped by src, time-sorted within a group)
    out_ptr: np.ndarray         # [n+1] int64
    out_edge: np.ndarray        # [m] int32 edge ids
    out_t: np.ndarray           # [m] int64 = t[out_edge]
    # in-CSR (grouped by dst)
    in_ptr: np.ndarray
    in_edge: np.ndarray
    in_t: np.ndarray
    # pair-CSR (grouped by (src,dst))
    num_pairs: int
    pair_key: np.ndarray        # [P] sorted int64 keys src*n+dst
    pair_ptr: np.ndarray        # [P+1]
    pair_edge: np.ndarray       # [m]
    pair_t: np.ndarray          # [m]
    pair_id: np.ndarray         # [m] pair id of each edge
    rev_pair_id: np.ndarray     # [m] pair id of (dst,src) or -1
    pair_pos_out: np.ndarray    # [m] position of pair-CSR slot k inside out-CSR
    pair_pos_in: np.ndarray     # [m] ditto inside in-CSR
    # inverse permutations: position of edge e inside each CSR
    out_pos_of_edge: np.ndarray
    in_pos_of_edge: np.ndarray
    # padding metadata (``pad_snapshot``): None/False on unpadded graphs.
    # ``m_real``/``n_real``/``p_real`` are the live counts; entries past
    # them are zero-weight pad suffixes.  ``pad_windows`` asks
    # ``weights.preprocess`` to bucket the per-window arrays too.
    m_real: int | None = None
    n_real: int | None = None
    p_real: int | None = None
    pad_windows: bool = False

    @property
    def live_m(self) -> int:
        """Real (non-pad) edge count."""
        return self.m if self.m_real is None else self.m_real

    @property
    def live_n(self) -> int:
        return self.n if self.n_real is None else self.n_real

    # ------------------------------------------------------------------
    @staticmethod
    def from_edges(src: np.ndarray, dst: np.ndarray, t: np.ndarray,
                   relabel: bool = True) -> "TemporalGraph":
        src = np.asarray(src)
        dst = np.asarray(dst)
        t = np.asarray(t, dtype=np.int64)
        if not (len(src) == len(dst) == len(t)):
            raise ValueError("edge array length mismatch")
        m = len(src)
        if m == 0:
            raise ValueError("empty graph")
        if np.any(src == dst):
            raise ValueError("self-loops not supported (match prior work)")
        if relabel:
            verts, inv = np.unique(np.concatenate([src, dst]), return_inverse=True)
            src = inv[:m].astype(np.int32)
            dst = inv[m:].astype(np.int32)
            n = len(verts)
        else:
            src = src.astype(np.int32)
            dst = dst.astype(np.int32)
            n = int(max(src.max(), dst.max())) + 1
        t = t - t.min()

        # global sort by (t, src, dst) — gives stable edge ids; repeated
        # (u, v, t) tuples are adjacent in this order
        order = np.lexsort((dst, src, t))
        src, dst, t = src[order], dst[order], t[order]
        # enforce unique (u, v, t) tuples (paper's input model): keep the
        # first of each run
        keep = np.r_[True, (t[1:] != t[:-1]) | (src[1:] != src[:-1])
                     | (dst[1:] != dst[:-1])]
        if not keep.all():
            src, dst, t = src[keep], dst[keep], t[keep]
            m = len(src)
        eid = np.arange(m, dtype=np.int32)

        def csr(group: np.ndarray, size: int):
            o = np.lexsort((eid, t, group))  # (group, t, id): time-sorted in-seg
            ptr = np.zeros(size + 1, dtype=np.int64)
            np.add.at(ptr, group.astype(np.int64) + 1, 1)
            np.cumsum(ptr, out=ptr)
            return ptr, eid[o].astype(np.int32), t[o]

        out_ptr, out_edge, out_t = csr(src, n)
        in_ptr, in_edge, in_t = csr(dst, n)

        # pair-CSR
        pkey = src.astype(np.int64) * n + dst.astype(np.int64)
        uniq_pairs, pair_id = np.unique(pkey, return_inverse=True)
        P = len(uniq_pairs)
        pair_ptr, pair_edge, pair_t = csr(pair_id.astype(np.int32), P)
        # reverse pair lookup
        rkey = dst.astype(np.int64) * n + src.astype(np.int64)
        ridx = np.searchsorted(uniq_pairs, rkey)
        ridx_clip = np.clip(ridx, 0, P - 1)
        rev_pair_id = np.where(uniq_pairs[ridx_clip] == rkey, ridx_clip, -1
                               ).astype(np.int32)

        out_pos_of_edge = np.empty(m, dtype=np.int64)
        out_pos_of_edge[out_edge] = np.arange(m)
        in_pos_of_edge = np.empty(m, dtype=np.int64)
        in_pos_of_edge[in_edge] = np.arange(m)
        pair_pos_out = out_pos_of_edge[pair_edge]
        pair_pos_in = in_pos_of_edge[pair_edge]

        return TemporalGraph(
            n=n, m=m, src=src, dst=dst, t=t,
            out_ptr=out_ptr, out_edge=out_edge, out_t=out_t,
            in_ptr=in_ptr, in_edge=in_edge, in_t=in_t,
            num_pairs=P, pair_key=uniq_pairs, pair_ptr=pair_ptr,
            pair_edge=pair_edge, pair_t=pair_t,
            pair_id=pair_id.astype(np.int32), rev_pair_id=rev_pair_id,
            pair_pos_out=pair_pos_out, pair_pos_in=pair_pos_in,
            out_pos_of_edge=out_pos_of_edge, in_pos_of_edge=in_pos_of_edge)

    # ------------------------------------------------------------------
    @property
    def time_span(self) -> int:
        return int(self.t[-1])

    def num_subgraphs(self, delta: int) -> int:
        """Number of 2*delta overlapping windows [i*d, (i+2)*d), i in [0, q)."""
        return max(1, -(-int(self.t[-1] + 1) // int(delta)) - 1)

    def max_multiplicity(self, delta: int) -> int:
        """sigma_delta — max #edges between an ordered pair within any delta window."""
        best = 1
        for p in range(self.num_pairs if self.p_real is None else self.p_real):
            seg = self.pair_t[self.pair_ptr[p]:self.pair_ptr[p + 1]]
            if len(seg) <= best:
                continue
            j = np.searchsorted(seg, seg - delta, side="left")
            best = max(best, int((np.arange(len(seg)) - j + 1).max()))
        return best

    def device_arrays(self, dtype: Any = None) -> dict[str, Any]:
        """Ship index structure to jax device arrays (int32 where safe)."""
        import jax.numpy as jnp
        use64 = bool(jnp.array(0, dtype=jnp.int64).dtype == jnp.int64)
        it = jnp.int64 if use64 else jnp.int32
        if not use64 and self.time_span > 2**30:
            raise ValueError("enable jax x64 for graphs with time span > 2^30")
        d = dict(
            src=jnp.asarray(self.src), dst=jnp.asarray(self.dst),
            t=jnp.asarray(self.t, dtype=it),
            out_ptr=jnp.asarray(self.out_ptr, dtype=it),
            out_edge=jnp.asarray(self.out_edge),
            out_t=jnp.asarray(self.out_t, dtype=it),
            in_ptr=jnp.asarray(self.in_ptr, dtype=it),
            in_edge=jnp.asarray(self.in_edge),
            in_t=jnp.asarray(self.in_t, dtype=it),
            n=jnp.asarray(self.n, dtype=it),
            pair_key=jnp.asarray(self.pair_key, dtype=jnp.int64 if use64
                                 else jnp.int32),
            pair_ptr=jnp.asarray(self.pair_ptr, dtype=it),
            pair_edge=jnp.asarray(self.pair_edge),
            pair_t=jnp.asarray(self.pair_t, dtype=it),
            pair_id=jnp.asarray(self.pair_id),
            rev_pair_id=jnp.asarray(self.rev_pair_id),
            pair_pos_out=jnp.asarray(self.pair_pos_out, dtype=it),
            pair_pos_in=jnp.asarray(self.pair_pos_in, dtype=it),
            # traced scalar: the weight DP zeroes pad-edge weights past it
            # (== m on unpadded graphs, so the mask is a no-op there)
            m_real=jnp.asarray(self.live_m, dtype=it),
        )
        return d


# ---------------------------------------------------------------------------
# power-of-two padded snapshots (the streaming epoch seam)
# ---------------------------------------------------------------------------
def next_pow2(x: int) -> int:
    return 1 if x <= 1 else 1 << (int(x) - 1).bit_length()


def pad_bucket(x: int, floor: int = 1) -> int:
    """Smallest power-of-two >= max(x, floor)."""
    return max(next_pow2(int(floor)), next_pow2(int(x)))


def pad_snapshot(g: TemporalGraph, *, m_bucket: int | None = None,
                 n_bucket: int | None = None, p_bucket: int | None = None,
                 m_floor: int = 1, n_floor: int = 1, p_floor: int = 1,
                 pad_windows: bool = True) -> TemporalGraph:
    """Pad ``g`` to power-of-two array buckets (see module docstring).

    Pad entries form a pure suffix of every array:

    * ``k = m_bucket - m`` pad edges run from pad vertex ``nb-2`` to
      ``nb-1`` at the last real timestamp — strictly after every real
      edge in the global ``(t, src, dst)`` order, and grouped after every
      real vertex/pair in each CSR;
    * pad vertices ``n .. nb-1`` get empty CSR segments (except the two
      carrying the pad edges);
    * the pad edges form pair id ``P`` (key above every real key); the
      remaining ``p_bucket - P - 1`` pair slots are empty segments under
      sentinel keys ``>= nb*nb``, which no ``u*n + v`` lookup of real
      vertices can ever produce.

    Requires ``n_bucket >= g.n + 2`` (two dedicated pad vertices keep pad
    edges out of every real CSR segment) — the default bucket guarantees
    it.  Weights of pad edges are zeroed by the preprocess DP via the
    ``m_real`` scalar in ``device_arrays``, so estimates on the padded
    graph are bit-identical to the unpadded one.  Idempotent padding of
    an already-padded graph is not supported (pass the unpadded graph).
    """
    if g.m_real is not None:
        raise ValueError("pad_snapshot: graph is already padded")
    n, m, P = g.n, g.m, g.num_pairs
    nb = pad_bucket(n + 2, n_floor) if n_bucket is None else int(n_bucket)
    mb = pad_bucket(m, m_floor) if m_bucket is None else int(m_bucket)
    pb = pad_bucket(P + 1, p_floor) if p_bucket is None else int(p_bucket)
    if nb < n + 2 or mb < m or pb < P + 1:
        raise ValueError(f"pad_snapshot: buckets (m={mb}, n={nb}, p={pb}) "
                         f"too small for graph (m={m}, n={n}, P={P})")
    k = mb - m
    t_max = int(g.t[-1])

    def suffix(a, fill, dtype=None):
        pad = np.full(k, fill, dtype=a.dtype if dtype is None else dtype)
        return np.concatenate([a, pad])

    pad_eids = m + np.arange(k, dtype=np.int64)
    # global edge arrays: pads sort strictly after every real edge
    src = suffix(g.src, nb - 2)
    dst = suffix(g.dst, nb - 1)
    t = suffix(g.t, t_max)
    # out-CSR: pad edges belong to vertex nb-2; others past n are empty
    out_ptr = np.full(nb + 1, m + k, dtype=np.int64)
    out_ptr[:n + 1] = g.out_ptr
    out_ptr[n + 1:nb - 1] = m
    out_edge = suffix(g.out_edge, 0)
    out_edge[m:] = pad_eids
    out_t = suffix(g.out_t, t_max)
    # in-CSR: pad edges belong to vertex nb-1
    in_ptr = np.full(nb + 1, m + k, dtype=np.int64)
    in_ptr[:n + 1] = g.in_ptr
    in_ptr[n + 1:nb] = m
    in_edge = suffix(g.in_edge, 0)
    in_edge[m:] = pad_eids
    in_t = suffix(g.in_t, t_max)
    # pair-CSR: real keys rebased to the padded vertex-id multiplier
    # (order-preserving, so pair ids are unchanged); pad edges form pair
    # P; remaining slots are empty segments under out-of-range sentinels
    pair_key = np.empty(pb, dtype=np.int64)
    pair_key[:P] = (g.pair_key // n) * nb + (g.pair_key % n)
    pair_key[P:] = (np.int64(nb) * np.int64(nb)
                    + np.arange(pb - P, dtype=np.int64))
    if k > 0:
        pair_key[P] = np.int64(nb - 2) * nb + (nb - 1)
    pair_ptr = np.full(pb + 1, m + k, dtype=np.int64)
    pair_ptr[:P + 1] = g.pair_ptr
    pair_edge = suffix(g.pair_edge, 0)
    pair_edge[m:] = pad_eids
    pair_t = suffix(g.pair_t, t_max)
    pair_id = suffix(g.pair_id, P)
    rev_pair_id = suffix(g.rev_pair_id, -1)
    pad_pos = m + np.arange(k, dtype=np.int64)
    pair_pos_out = np.concatenate([g.pair_pos_out, pad_pos])
    pair_pos_in = np.concatenate([g.pair_pos_in, pad_pos])
    out_pos_of_edge = np.concatenate([g.out_pos_of_edge, pad_pos])
    in_pos_of_edge = np.concatenate([g.in_pos_of_edge, pad_pos])

    return replace(
        g, n=nb, m=mb, src=src, dst=dst, t=t,
        out_ptr=out_ptr, out_edge=out_edge, out_t=out_t,
        in_ptr=in_ptr, in_edge=in_edge, in_t=in_t,
        num_pairs=pb, pair_key=pair_key, pair_ptr=pair_ptr,
        pair_edge=pair_edge, pair_t=pair_t, pair_id=pair_id,
        rev_pair_id=rev_pair_id, pair_pos_out=pair_pos_out,
        pair_pos_in=pair_pos_in, out_pos_of_edge=out_pos_of_edge,
        in_pos_of_edge=in_pos_of_edge,
        m_real=m, n_real=n, p_real=P, pad_windows=pad_windows)
