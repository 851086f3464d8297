"""GPipe-style pipeline parallelism over the "pod" axis.

``gpipe_forward`` places stage ``s`` of an ``n_stage``-deep network on pod
shard ``s`` and streams microbatches through: at step ``t`` stage ``s``
processes microbatch ``t - s`` and ships its activation to stage ``s + 1``
via ``ppermute`` — the classic fill/steady/drain schedule, ``n_mb +
n_stage - 1`` steps total.  Identical math to running every microbatch
through the stages serially (the test oracle).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def gpipe_forward(stage_fn, stage_params: jnp.ndarray, xs: jnp.ndarray,
                  mesh, axis: str = "pod") -> jnp.ndarray:
    """stage_params [n_stage, ...] sharded over ``axis``; xs [n_mb, B, ...].

    Returns [n_mb, B, ...] — every microbatch after all stages, replicated.
    """
    n_stage = int(mesh.shape[axis])
    if stage_params.shape[0] != n_stage:
        raise ValueError(f"{stage_params.shape[0]} stages on a "
                         f"{n_stage}-deep {axis!r} axis")
    n_mb = xs.shape[0]
    n_steps = n_mb + n_stage - 1
    perm = [(i, (i + 1) % n_stage) for i in range(n_stage)]

    def run(w_local, xs_rep):
        w = w_local[0]                      # this shard's stage weights
        stage = jax.lax.axis_index(axis)
        outs = jnp.zeros_like(xs_rep)       # filled on the last stage only

        def body(carry, t):
            state, outs = carry
            # stage 0 ingests microbatch t (garbage after the fill phase —
            # masked out because it never reaches a valid emit slot)
            x_in = xs_rep[jnp.clip(t, 0, n_mb - 1)]
            inp = jnp.where(stage == 0, x_in, state)
            out = stage_fn(w, inp)
            emit = t - (n_stage - 1)
            ok = (emit >= 0) & (emit < n_mb) & (stage == n_stage - 1)
            upd = jax.lax.dynamic_update_slice(
                outs, out[None], (jnp.clip(emit, 0, n_mb - 1),)
                + (0,) * out.ndim)
            outs = jnp.where(ok, upd, outs)
            state = jax.lax.ppermute(out, axis, perm)
            return (state, outs), None

        state0 = jnp.zeros_like(xs_rep[0])
        (_, outs), _ = jax.lax.scan(body, (state0, outs),
                                    jnp.arange(n_steps))
        # broadcast the last stage's buffer to every shard
        keep = jnp.where(stage == n_stage - 1, 1, 0).astype(outs.dtype)
        return jax.lax.psum(outs * keep, axis)

    w_spec = P(axis, *([None] * (stage_params.ndim - 1)))
    fn = jax.shard_map(run, mesh=mesh, in_specs=(w_spec, P()),
                       out_specs=P(), check_vma=False)
    return fn(stage_params, xs)
