"""Fused Pallas sampler (kernels/tree_sampler) parity + backend seam.

The contract under test: REPRO_SAMPLER_BACKEND=pallas is a pure
execution optimization — the one-dispatch kernel must produce samples
**bit-identical** to the XLA gather-chain path (same edges, window and
vertex map for the same key), across both ``use_c2`` branches, through
``estimate()`` end-to-end, and across a checkpoint resume.
"""
from __future__ import annotations

import jax
import numpy as np
import pytest

from repro.core.estimator import estimate
from repro.core.motif import get_motif
from repro.core.sampler import (_make_sample_fn_xla, make_sample_fn,
                                sampler_backend)
from repro.core.spanning_tree import candidate_trees
from repro.core.weights import preprocess
from repro.graphs import powerlaw_temporal_graph
from repro.kernels.tree_sampler.kernel import randint_from_bits
from repro.kernels.tree_sampler.ops import (make_pallas_sample_fn,
                                            pallas_sampler_eligible,
                                            prepare_draws)
from repro.kernels.tree_sampler.ref import tree_sampler_ref

DELTA = 3_000
K = 513          # deliberately ragged: exercises the shared block padding


@pytest.fixture(scope="module")
def graph():
    return powerlaw_temporal_graph(n=120, m=1_500, time_span=30_000, seed=5)


@pytest.fixture(scope="module")
def dev(graph):
    return graph.device_arrays()


def test_randint_from_bits_replays_jax_randint():
    """The kernel's modular reduction == jax.random.randint, bit for bit."""
    key = jax.random.PRNGKey(123)
    import jax.numpy as jnp
    spans = jnp.asarray([1, 2, 3, 7, 100, 12345, 2 ** 20, (1 << 24) - 1],
                        jnp.int64)
    want = jax.random.randint(key, spans.shape, 0, spans, dtype=jnp.int64)
    k1, k2 = jax.random.split(key)
    hi = jax.random.bits(k1, spans.shape, jnp.uint64)
    lo = jax.random.bits(k2, spans.shape, jnp.uint64)
    got = randint_from_bits(hi, lo, spans).astype(jnp.int64)
    assert (np.asarray(want) == np.asarray(got)).all()


@pytest.mark.parametrize("motif_name", ["M5-3", "M4-2"])
@pytest.mark.parametrize("use_c2", [True, False])
def test_pallas_sampler_bit_identical(graph, dev, motif_name, use_c2):
    """Kernel (interpret) == int64 ref == XLA path: edges, window, phi_v."""
    motif = get_motif(motif_name)
    tree = candidate_trees(motif, n_candidates=1, roots_per_tree=1)[0]
    wts = preprocess(graph, tree, DELTA, dev=dev, use_c2=use_c2)
    ok, why = pallas_sampler_eligible(dev, wts)
    assert ok, why
    key = jax.random.PRNGKey(9)

    s_xla = _make_sample_fn_xla(tree, K)(dev, wts, key)
    # bk < K forces a multi-block grid WITH 255 zero-padded tail rows —
    # the shared pad_block path must not leak into the real samples
    s_pal = make_pallas_sample_fn(tree, K, bk=256)(dev, wts, key)
    x, uhi, ulo = prepare_draws(tree, wts, key, K)
    s_ref = tree_sampler_ref(tree, dev, wts, x, uhi, ulo)

    for k in ("edges", "window", "phi_v"):
        assert (np.asarray(s_xla[k]) == np.asarray(s_ref[k])).all(), \
            f"ref mismatch on {k}"
        assert (np.asarray(s_xla[k]) == np.asarray(s_pal[k])).all(), \
            f"kernel mismatch on {k}"


@pytest.fixture(scope="module")
def heavy_graph():
    """Long pair lists (up to 79 edges): most edges repeat a pair within
    a short span over two windows, so 85 of the 214 pair lists cross a
    window breakpoint and the CSR lists around them carry equal stamps
    of other pairs."""
    return powerlaw_temporal_graph(n=40, m=1_500, time_span=9_000,
                                   multiplicity=0.6, seed=11)


@pytest.mark.parametrize("motif_name", ["M5-3", "M4-2"])
def test_xla_sampler_matches_ref_heavy_pairs(heavy_graph, motif_name):
    """XLA (two sequential C2 bisections) == the nested int64 ref."""
    dev = heavy_graph.device_arrays()
    motif = get_motif(motif_name)
    tree = candidate_trees(motif, n_candidates=1, roots_per_tree=1)[0]
    wts = preprocess(heavy_graph, tree, DELTA, dev=dev, use_c2=True)
    assert int(wts.W_total) > 0
    key = jax.random.PRNGKey(4)

    s_xla = _make_sample_fn_xla(tree, K)(dev, wts, key)
    x, uhi, ulo = prepare_draws(tree, wts, key, K)
    s_ref = tree_sampler_ref(tree, dev, wts, x, uhi, ulo)
    for k in ("edges", "window", "phi_v"):
        assert (np.asarray(s_xla[k]) == np.asarray(s_ref[k])).all(), \
            f"ref mismatch on {k}"


def test_backend_seam_and_guarded_fallback(graph, dev, monkeypatch):
    """Env resolves the backend; the guarded fn falls back outside the
    kernel envelope (here: a zero VMEM budget) with identical samples."""
    monkeypatch.setenv("REPRO_SAMPLER_BACKEND", "pallas")
    assert sampler_backend() == "pallas"
    monkeypatch.setenv("REPRO_SAMPLER_BACKEND", "xla")
    assert sampler_backend() == "xla"
    with pytest.raises(ValueError):
        sampler_backend("mlir")

    motif = get_motif("M4-2")
    tree = candidate_trees(motif, n_candidates=1, roots_per_tree=1)[0]
    wts = preprocess(graph, tree, DELTA, dev=dev)
    ok, why = pallas_sampler_eligible(dev, wts, vmem_budget_bytes=1)
    assert not ok and "VMEM" in why

    monkeypatch.setenv("REPRO_SAMPLER_VMEM_MB", "0")
    fn = make_sample_fn(tree, 64, backend="pallas", guard=True)
    s_guarded = fn(dev, wts, jax.random.PRNGKey(1))   # falls back to xla
    s_xla = _make_sample_fn_xla(tree, 64)(dev, wts, jax.random.PRNGKey(1))
    assert (np.asarray(s_guarded["edges"]) == np.asarray(s_xla["edges"])).all()

    # estimate() downgrades automatically and records the backend used
    res = estimate(graph, motif, DELTA, 256, seed=0, chunk=256,
                   sampler_backend="pallas")
    assert res.sampler_backend == "xla"


def test_estimate_pallas_bit_identical_with_resume(graph, monkeypatch,
                                                   tmp_path):
    """estimate() under REPRO_SAMPLER_BACKEND=pallas == the XLA backend,
    fresh AND resumed from a mid-stream checkpoint."""
    motif = get_motif("M5-3")
    kwargs = dict(seed=0, chunk=256, checkpoint_every=2)

    # explicit arg beats whatever REPRO_SAMPLER_BACKEND the CI run set
    r_xla = estimate(graph, motif, DELTA, 1024, sampler_backend="xla",
                     **kwargs)
    assert r_xla.sampler_backend == "xla"

    monkeypatch.setenv("REPRO_SAMPLER_BACKEND", "pallas")
    r_pal = estimate(graph, motif, DELTA, 1024, **kwargs)
    assert r_pal.sampler_backend == "pallas"
    assert r_pal.estimate == r_xla.estimate
    assert r_pal.cnt2_sum == r_xla.cnt2_sum
    assert r_pal.valid == r_xla.valid
    assert r_pal.fail_vmap == r_xla.fail_vmap

    # resume: a k=512 run leaves a checkpoint at chunk 2; the k=1024 run
    # picks it up mid-stream and must land on the identical estimate
    ckpt = str(tmp_path / "timest.ckpt")
    part = estimate(graph, motif, DELTA, 512, checkpoint_path=ckpt, **kwargs)
    assert part.k == 512
    r_res = estimate(graph, motif, DELTA, 1024, checkpoint_path=ckpt,
                     **kwargs)
    assert r_res.estimate == r_xla.estimate
    assert r_res.cnt2_sum == r_xla.cnt2_sum
    assert r_res.valid == r_xla.valid
