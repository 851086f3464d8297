"""Hand-rolled collectives: chunked psum, model-sharded embedding lookup.

These are shard_map-level building blocks: ``psum_chunked`` bounds the
per-collective payload (overlap-friendly; matches the wire behaviour of a
bucketed all-reduce), and ``sharded_embedding_lookup`` is the classic
row-sharded table gather (each shard resolves the indices it owns, one
psum combines) used by both the recsys embedding tables and vocab-sharded
LM embeddings.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def folded_axis_index(mesh, axes) -> jnp.ndarray:
    """Row-major linear shard index over ``axes`` (inside shard_map).

    Folds several mesh axes — e.g. ``("pod", "data")`` — into the single
    0-based index the estimation engine strides its chunk round-robin by;
    with one axis it is just ``jax.lax.axis_index``.
    """
    idx = jax.lax.axis_index(axes[0])
    for a in axes[1:]:
        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
    return idx


def psum_chunked(x: jnp.ndarray, axis_name, n_chunks: int = 1):
    """``jax.lax.psum`` in ``n_chunks`` sequential slabs of the flat payload.

    Numerically identical to a single psum (integer-exact reduction order
    per element); bounds the bytes in flight per collective, which is what
    lets XLA overlap the reduce with compute when bucketed.
    """
    if n_chunks <= 1:
        return jax.lax.psum(x, axis_name)
    flat = x.reshape(-1)
    n = flat.shape[0]
    pad = (-n) % n_chunks
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    chunks = flat.reshape(n_chunks, -1)

    def body(_, c):
        return None, jax.lax.psum(c, axis_name)

    _, red = jax.lax.scan(body, None, chunks)
    return red.reshape(-1)[:n].reshape(x.shape)


def sharded_embedding_lookup(table: jnp.ndarray, idx: jnp.ndarray, mesh,
                             axis: str = "model") -> jnp.ndarray:
    """Row-shard ``table`` over ``axis``; gather ``idx`` (-1 = padding -> 0).

    Each shard serves the indices that fall in its row range and
    contributes zero elsewhere; one psum over ``axis`` assembles the full
    [*, d] result, replicated on every device.
    """
    V = table.shape[0]
    n_shards = int(mesh.shape[axis])
    if V % n_shards != 0:
        raise ValueError(f"table rows {V} must divide axis {axis!r} "
                         f"size {n_shards}")
    rows_local = V // n_shards

    def local(tab, ix):
        shard = jax.lax.axis_index(axis)
        offset = shard * rows_local
        here = (ix >= offset) & (ix < offset + rows_local)
        loc = jnp.clip(ix - offset, 0, rows_local - 1)
        out = jnp.where(here[..., None], tab[loc], 0)
        return jax.lax.psum(out, axis)

    fn = jax.shard_map(local, mesh=mesh, in_specs=(P(axis, None), P()),
                       out_specs=P(), check_vma=False)
    return fn(table, idx)
