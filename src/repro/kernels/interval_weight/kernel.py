"""TIMEST dep-sum hot loop: fused segment bisect + two-piece prefix gather.

This is the inner operation of both the weight DP (Claim 4.9) and the
sampler (Alg. 3): for a batch of queries (CSR segment [p0, p1), time
bounds [tlo, thi], window breakpoint brk), find

    plo  = lower_bound(csr_t, p0, p1, tlo)
    phi  = upper_bound(csr_t, p0, p1, thi)
    pmid = clip(lower_bound(csr_t, p0, p1, brk), plo, phi)
    out  = (ps_own[pmid] - ps_own[plo]) + (ps_prev[phi] - ps_prev[pmid])

TPU adaptation of the paper's per-edge std::lower_bound: the sorted time
array and both prefix arrays are VMEM-resident (one 2^20-edge time shard
= 4 MiB int32 + 2x8 MiB f32 prefixes, inside the ~16 MiB budget when the
launcher chunks the graph by time range — which TIMEST's Constraint-3
windows already do); queries stream through in ``bq`` blocks; the
bisection is branchless fixed-trip (trip count adapts to the shard size,
``core.bisect.converge_iters(m)``) and fully vectorized across the block,
so each iteration is one VMEM gather + compare + select on an 8x128-lane vector.

Status: CPU-interpret only until ROADMAP S2.  Compiled for a v5e at
m = 65536 the TPU compiler refuses it (``NotImplementedError: Only 2D
gather is supported``: the 1-D ``jnp.take`` gathers here and in
``kernels/bisect.py``).  The default ``xla`` dep-sum is the chip path.

Weights dtype: f32 here (counts < 2^24 exact). The estimator's exact-int64
path stays in XLA; the f32-rebased two-level scheme for larger counts is
documented in DESIGN.md.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...core.bisect import converge_iters
from ..bisect import seg_bisect as _bisect
from ..padding import pad_block


def _iw_kernel(t_ref, pso_ref, psp_ref, p0_ref, p1_ref, tlo_ref, thi_ref,
               brk_ref, o_ref, *, iters: int):
    vals = t_ref[...]
    pso = pso_ref[...]
    psp = psp_ref[...]
    p0 = p0_ref[...]
    p1 = p1_ref[...]
    plo = _bisect(vals, p0, p1, tlo_ref[...], upper=False, iters=iters)
    phi = _bisect(vals, p0, p1, thi_ref[...], upper=True, iters=iters)
    pmid = jnp.clip(_bisect(vals, p0, p1, brk_ref[...], upper=False,
                            iters=iters),
                    plo, phi)
    own = jnp.take(pso, pmid) - jnp.take(pso, plo)
    prev = jnp.take(psp, phi) - jnp.take(psp, pmid)
    o_ref[...] = own + prev


def interval_weight_call(csr_t, ps_own, ps_prev, p0, p1, tlo, thi, brk, *,
                         bq: int = 1024, interpret: bool = False):
    """csr_t [m] int32; ps_* [m+1] f32; queries [Q] int32.

    Ragged query batches are zero-padded to a ``bq`` multiple (empty
    segments) and the padding is sliced off the result.  The bisection
    trip count adapts to the shard size, so any ``m < 2^62`` is covered.
    """
    m = csr_t.shape[0]
    Q = p0.shape[0]
    bq = min(bq, max(Q, 1))
    (p0, p1, tlo, thi, brk), Q = pad_block(bq, p0, p1, tlo, thi, brk)
    Qp = p0.shape[0]
    grid = (Qp // bq,)
    qspec = pl.BlockSpec((bq,), lambda i: (i,))
    full_t = pl.BlockSpec((m,), lambda i: (0,))
    full_p = pl.BlockSpec((m + 1,), lambda i: (0,))
    # trip count from the shard size alone — deliberately NOT the
    # REPRO_BISECT_ITERS sampler A/B knob, which must never be able to
    # under-iterate the weight DP (it would corrupt dep-sums silently)
    iters = converge_iters(m)
    out = pl.pallas_call(
        functools.partial(_iw_kernel, iters=iters),
        grid=grid,
        in_specs=[full_t, full_p, full_p, qspec, qspec, qspec, qspec, qspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((Qp,), ps_own.dtype),
        interpret=interpret,
    )(csr_t, ps_own, ps_prev, p0, p1, tlo, thi, brk)
    return out[:Q]
