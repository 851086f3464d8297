"""Compile the main path's device programs for a TPU v5e that is described,
not attached.

The TPU compiler is installed even where no chip is, and it refuses what
the chip would refuse: a program over HBM, a kernel over VMEM, an op it
cannot lower.  These tests put the served path's own programs to it at a
realistic graph size — the weight-preprocess DP (``core/weights.py``) and
the engine window program (``core/engine.py``, xla sampler backend) — and
feed its real refusals through the error taxonomy.  Nothing runs: the
inputs are ``ShapeDtypeStruct``s placed on one described chip.

The topology is described inside a module fixture (never at import time):
only one process at a time may load the TPU library, and every test
worker imports this file.  The persistent compilation cache is off around
these compiles, since an entry written here cannot be read back without a
chip.
"""
from __future__ import annotations

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.engine import make_engine_window_fn
from repro.core.motif import get_motif
from repro.core.spanning_tree import candidate_trees
from repro.core.weights import Weights, _window_totals_fn, make_preprocess_fn
from repro.graphs import powerlaw_temporal_graph
from repro.resilience import classify

#: temporal edges of the compiled graph: the smallest size the on-chip
#: smoke run serves (``chip_smoke.py``'s real-size tenant)
M = 1 << 22
#: window-array length: time_span / delta of a long-span graph
Q = 4096
#: HBM of one v5e chip as its compiler counts it (16 GiB less a reserve)
HBM_BYTES = int(15.75 * 2**30)
#: the served path's defaults (``EstimateConfig``)
CHUNK, CHECKPOINT_EVERY = 8192, 64


@pytest.fixture(scope="module")
def topo():
    # the TPU library otherwise writes its logs outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def dev(one_chip):
    """``device_arrays()`` of an M-edge graph as shapes: dtypes come from a
    real (small) graph's upload, lengths scale by which dimension each
    array has (edges, vertices + 1, pairs, pairs + 1)."""
    g = powerlaw_temporal_graph(n=150, m=2_000, time_span=40_000, seed=11)
    dims = {g.m: M, g.n + 1: M // 8 + 1, g.num_pairs: M // 2,
            g.num_pairs + 1: M // 2 + 1}
    assert len(dims) == 4, "pick a graph whose dimensions differ"
    return {k: jax.ShapeDtypeStruct(() if a.ndim == 0
                                    else (dims[a.shape[0]],),
                                    a.dtype, sharding=one_chip)
            for k, a in g.device_arrays().items()}


def _on(sds_tree, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        sds_tree)


def _scalar(sharding):
    return jax.ShapeDtypeStruct((), jnp.int64, sharding=sharding)


def _fits(compiled) -> int:
    ma = compiled.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert 0 < need < HBM_BYTES, need
    return need


@pytest.fixture(scope="module")
def tree():
    return candidate_trees(get_motif("M4-2"))[0]


def test_preprocess_dp_compiles_and_fits_one_chip(dev, one_chip, tree):
    core = make_preprocess_fn(tree).core
    s = _scalar(one_chip)
    compiled = core.lower(dev, s, s, s).compile()
    # the outputs alone are the six [S, m(+1)] int64 weight arrays
    assert compiled.memory_analysis().output_size_in_bytes \
        >= 6 * tree.num_edges * M * 8
    _fits(compiled)


def test_engine_window_program_compiles_and_fits_one_chip(dev, one_chip,
                                                          tree):
    s = _scalar(one_chip)
    core = make_preprocess_fn(tree).core
    out = jax.eval_shape(core, dev, s, s, s)
    root_prefix = jax.ShapeDtypeStruct(out["ps_acc_own"].shape[1:],
                                       out["ps_acc_own"].dtype)
    win = jax.eval_shape(_window_totals_fn(Q), dev["t"], root_prefix,
                         root_prefix, s, s)
    arrays = {k: v for k, v in out.items() if k != "exact"}
    arrays.update(win)
    wts = Weights(tree=tree, delta=10_000, wd=10_000, use_c2=True,
                  **_on(dict(q=jax.ShapeDtypeStruct((), jnp.int64),
                             W_total=jax.ShapeDtypeStruct((), jnp.int64),
                             **arrays), one_chip))
    keys = jax.ShapeDtypeStruct((1, 2), jnp.uint32, sharding=one_chip)
    window = make_engine_window_fn(tree, CHUNK, backend="xla")
    compiled = window.lower(dev, wts, keys, s, n=CHECKPOINT_EVERY).compile()
    _fits(compiled)


def test_compiler_refusals_classify_fatal(one_chip):
    """What the TPU compiler raises for a program over HBM and a kernel
    over VMEM is a deterministic refusal: ``fatal``, never retried."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    big = jax.ShapeDtypeStruct((5 * 2**30,), jnp.float32, sharding=one_chip)
    with pytest.raises(jax.errors.JaxRuntimeError) as hbm:
        jax.jit(lambda a: (a * 2, a + 1)).lower(big).compile()
    assert "RESOURCE_EXHAUSTED" in str(hbm.value)

    def kernel(x_ref, o_ref, s_ref):      # 256 MiB of VMEM scratch
        s_ref[:8, :128] = x_ref[...] * 2
        o_ref[...] = s_ref[:8, :128]

    def scaled(x):
        return pl.pallas_call(
            kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            scratch_shapes=[pltpu.VMEM((8192, 8192), jnp.float32)])(x)

    x = jax.ShapeDtypeStruct((8, 128), jnp.float32, sharding=one_chip)
    with pytest.raises(jax.errors.JaxRuntimeError) as vmem:
        jax.jit(scaled).lower(x).compile()
    assert "RESOURCE_EXHAUSTED" in str(vmem.value)

    for refusal in (hbm.value, vmem.value):
        assert classify(refusal) == "fatal", str(refusal)
