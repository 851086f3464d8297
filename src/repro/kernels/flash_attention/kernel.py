"""GQA flash-attention Pallas TPU kernel (online softmax, VMEM tiling).

Grid ``(B, Hq, nq, nk)``, kv innermost ("arbitrary" semantics — the m/l/acc
scratch carries across kv blocks; the other three axes are parallel).
Per grid step the MXU sees [bq, D] x [bk, D]^T and [bq, bk] x [bk, D]
matmuls; bq/bk default 128/256 so both operands are MXU-aligned (128) and
the VMEM working set (q, k, v, acc ~ f32) stays < 1 MiB — far under the
~16 MiB/core VMEM budget, leaving room for double buffering.

GQA: the k/v BlockSpec index_map divides the query-head index by the group
size, so kv blocks are fetched once per kv head and reused by its group.

Causal + sliding-window masking is applied in-kernel via iota comparison.
Fully-masked kv blocks (beyond the causal frontier / outside the window)
are skipped by masking only — block-level pruning is a recorded follow-up
optimization in EXPERIMENTS.md §Perf.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
               scale, causal, window, attn_softcap, bq, bk, nk):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)            # [bq, D]
    k = k_ref[0, 0].astype(jnp.float32)            # [bk, D]
    v = v_ref[0, 0].astype(jnp.float32)            # [bk, D]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if attn_softcap:
        s = attn_softcap * jnp.tanh(s / attn_softcap)
    qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= qpos >= kpos
    if window > 0:
        mask &= qpos - kpos < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=1)
    acc_scr[...] = (acc_scr[...] * corr[:, None]
                    + jax.lax.dot(p.astype(v.dtype), v,
                                  preferred_element_type=jnp.float32))
    m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _emit():
        o_ref[0, 0] = (acc_scr[...]
                       / jnp.maximum(l_scr[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


def flash_attention_bhsd(q, k, v, *, causal=True, window=0,
                         attn_softcap=0.0, bq=128, bk=256,
                         interpret=False):
    """q [B, Hq, Sq, D]; k/v [B, Hkv, Skv, D].  Sq % bq == Skv % bk == 0."""
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    assert Hq % Hkv == 0
    G = Hq // Hkv
    bq = min(bq, Sq)
    bk = min(bk, Skv)
    assert Sq % bq == 0 and Skv % bk == 0, (Sq, bq, Skv, bk)
    nq, nk = Sq // bq, Skv // bk
    grid = (B, Hq, nq, nk)
    kernel = functools.partial(
        _fa_kernel, scale=D ** -0.5, causal=causal, window=window,
        attn_softcap=attn_softcap, bq=bq, bk=bk, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, iq, ik, G=G: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, iq, ik, G=G: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
