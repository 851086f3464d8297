"""shard_map edge-parallel GNN message passing.

GSPMD's auto-sharding replicates segment-sum message passing (scatter adds
don't propagate shardings well); this module instead places an explicit
edge partition: every shard owns a contiguous slice of the edge set, runs
the model's own ``forward`` on its local edges with ``cfg.shard_axes`` set
(so each ``seg_sum``/``seg_max`` finishes with a psum/pmax over the edge
axes), and the loss comes out numerically identical to the single-device
``gnn.train_loss`` — gradients included.

Partitioning contract (mirrored by ``_batch_specs``):

* non-GraphCast: node arrays (feats/labels/mask) replicated, edge arrays
  (senders/receivers, global node ids) sharded over the non-"model" axes;
* GraphCast ``grid_sharded``: grid-node arrays AND grid-incident edge
  arrays sharded together (grid indices are shard-LOCAL), mesh-node state
  and mesh-mesh edges replicated — so g2m aggregations psum across shards
  while the processor and the m2g decode stay local.

The loss ends in ``pmean`` over *all* mesh axes: forward-invariant (every
shard holds the identical scalar after the psums) and exactly what makes
the replicated-input transpose produce unscaled gradients.
"""
from __future__ import annotations

from dataclasses import replace

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..models import gnn
from .sharding import data_axes


_GRID_KEYS = ("feats", "target", "grid_mask", "g2m_senders",
              "g2m_receivers", "m2g_senders", "m2g_receivers")


def _batch_specs(cfg, batch, da) -> dict:
    """PartitionSpec per batch entry (prefix tree matching the batch)."""
    edge = P(da)
    if cfg.kind == "graphcast":
        return {k: (edge if k in _GRID_KEYS else P()) for k in batch}
    specs = {k: P() for k in batch}
    for k in ("senders", "receivers"):
        if k in batch:
            specs[k] = edge
    return specs


def make_sharded_gnn_loss(cfg, mesh, batch):
    """Build ``loss(params, batch) -> scalar`` == ``gnn.train_loss``."""
    da = data_axes(mesh)
    cfg_sh = replace(cfg, shard_axes=da,
                     grid_sharded=(cfg.kind == "graphcast"))
    specs = _batch_specs(cfg, batch, da)
    all_axes = tuple(mesh.axis_names)

    def local_loss(params, b):
        if cfg.kind == "graphcast":
            out = gnn.forward(cfg_sh, params, b)
            mask = b.get("grid_mask")
            if mask is None:
                mask = jnp.ones((out.shape[0],), out.dtype)
            se = jnp.sum((out - b["target"]) ** 2 * mask[:, None])
            cnt = jnp.sum(mask) * out.shape[1]
            se = jax.lax.psum(se, da)
            cnt = jax.lax.psum(cnt, da)
            loss = se / jnp.maximum(cnt, 1.0)
        else:
            loss = gnn.train_loss(cfg_sh, params, b)
        # identical on every shard; pmean keeps forward value AND gives the
        # transpose the 1/n_shards factor that cancels the replicated-param
        # cotangent psum — exact gradients, no overcount.
        return jax.lax.pmean(loss, all_axes)

    fn = jax.shard_map(local_loss, mesh=mesh, in_specs=(P(), specs),
                       out_specs=P(), check_vma=False)

    def loss_fn(params, b):
        return fn(params, b)

    return loss_fn
