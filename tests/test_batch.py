"""Batched estimation engine (core/batch.py) + dep-sum backend seam.

The contract under test: batching is a pure execution optimization —
``estimate_many`` must return bit-identical ``(estimate, valid,
cnt2_sum)`` to per-job ``estimate()`` calls, through the shared-preprocess
dedup path and on either dep-sum backend.
"""
from __future__ import annotations

import numpy as np
import pytest

from repro.core.batch import BatchPlanner, Job, as_job, estimate_many
from repro.core.estimator import estimate
from repro.core.motif import get_motif
from repro.core.weights import preprocess
from repro.core.spanning_tree import candidate_trees
from repro.graphs import powerlaw_temporal_graph

DELTA = 3_000


@pytest.fixture(scope="module")
def graph():
    return powerlaw_temporal_graph(n=150, m=2_000, time_span=40_000, seed=11)


JOBS = [("M5-3", DELTA, 1024), ("M5-3", DELTA, 2048),
        ("M4-2", DELTA, 1024), ("M4-2", 5_000, 1024)]


def test_estimate_many_matches_sequential(graph):
    """Same seeds => same (estimate, valid, cnt2_sum), job for job."""
    batch = estimate_many(graph, JOBS, seed=0, chunk=256)
    assert len(batch) == len(JOBS)
    for (mn, d, k), rb in zip(JOBS, batch):
        rs = estimate(graph, get_motif(mn), d, k, seed=0, chunk=256)
        assert rb.estimate == rs.estimate
        assert rb.valid == rs.valid
        assert rb.cnt2_sum == rs.cnt2_sum
        assert rb.W == rs.W
        assert rb.tree_edges == rs.tree_edges  # same winning tree


def test_preprocess_dedup(graph):
    """Jobs resolving to the same (tree, delta, wd) preprocess once."""
    planner = BatchPlanner(graph)
    estimate_many(graph, [("M5-3", DELTA, 256)], seed=0, chunk=256,
                  planner=planner)
    calls_first = planner.preprocess_calls
    assert calls_first > 0
    # same motif+delta, different budget: full plan-cache hit
    estimate_many(graph, [("M5-3", DELTA, 512), ("M5-3", DELTA, 256)],
                  seed=0, chunk=256, planner=planner)
    assert planner.preprocess_calls == calls_first
    # same motif, new delta: trees are shared objects, weights are not —
    # every candidate preprocesses again, none hit
    estimate_many(graph, [("M5-3", 5_000, 256)], seed=0, chunk=256,
                  planner=planner)
    assert planner.preprocess_calls == 2 * calls_first
    assert planner.preprocess_hits == 0


def test_concurrent_candidate_preprocess_matches_choose_tree(graph):
    """``plan`` preprocesses its candidates in threads; the winner and
    its arrays equal ``choose_tree``'s one-candidate-at-a-time ranking,
    even with the interpreter switching threads as often as it can."""
    import sys

    from repro.core.estimator import choose_tree
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        planner = BatchPlanner(graph)
        plans = {mn: planner.plan(get_motif(mn), DELTA)
                 for mn in ("M5-3", "M4-2", "triangle")}
    finally:
        sys.setswitchinterval(was)
    assert planner.preprocess_calls == len(planner._weights)
    for mn, (tree, wts) in plans.items():
        ref_tree, ref = choose_tree(graph, get_motif(mn), DELTA)
        assert tree == ref_tree
        for f in ("w_own", "ps_acc_own", "ps_pair_prev", "ps_win",
                  "W_total"):
            np.testing.assert_array_equal(getattr(wts, f), getattr(ref, f))


def test_seed_override_and_job_spec(graph):
    job = as_job(("M4-2", DELTA, 512, 7))
    assert isinstance(job, Job) and job.seed == 7
    rb, = estimate_many(graph, [job], seed=0, chunk=256)
    rs = estimate(graph, get_motif("M4-2"), DELTA, 512, seed=7, chunk=256)
    assert rb.cnt2_sum == rs.cnt2_sum and rb.estimate == rs.estimate


def test_depsum_backend_parity(graph):
    """pallas (interpret on CPU) == exact int64 XLA, array for array."""
    dev = graph.device_arrays()
    for mn in ("M5-3", "M4-2"):
        motif = get_motif(mn)
        for tree in candidate_trees(motif, n_candidates=2,
                                    roots_per_tree=1):
            wx = preprocess(graph, tree, DELTA, dev=dev, backend="xla")
            wp = preprocess(graph, tree, DELTA, dev=dev, backend="pallas")
            for f in ("w_own", "w_prev", "ps_acc_own", "ps_acc_prev",
                      "ps_pair_own", "ps_pair_prev", "ps_win", "W_total"):
                a, b = np.asarray(getattr(wx, f)), np.asarray(getattr(wp, f))
                assert a.dtype == b.dtype and np.array_equal(a, b), \
                    f"{mn} {tree.edge_ids} {f}"


def test_backend_env_and_estimates(graph, monkeypatch):
    """End-to-end estimate under REPRO_DEPSUM_BACKEND=pallas is identical."""
    r_xla = estimate(graph, get_motif("M4-2"), DELTA, 512, seed=3, chunk=256)
    monkeypatch.setenv("REPRO_DEPSUM_BACKEND", "pallas")
    r_pal = estimate(graph, get_motif("M4-2"), DELTA, 512, seed=3, chunk=256)
    assert r_pal.estimate == r_xla.estimate
    assert r_pal.cnt2_sum == r_xla.cnt2_sum
    assert r_pal.W == r_xla.W


def test_pallas_overflow_falls_back_exact(graph, monkeypatch):
    """Weights beyond 2^24 must come from the exact int64 path."""
    from repro.core import weights as W

    captured = {}
    orig = W.cached_preprocess_fn

    def spy(tree, use_c2=True, backend=None):
        captured.setdefault("backends", []).append(W.depsum_backend(backend))
        return orig(tree, use_c2=use_c2, backend=backend)

    monkeypatch.setattr(W, "cached_preprocess_fn", spy)
    # a hub-star motif on a power-law graph has W far beyond 2^24
    g = powerlaw_temporal_graph(n=80, m=4_000, time_span=20_000, seed=5)
    motif = get_motif("M5-1")
    tree = candidate_trees(motif, n_candidates=1, roots_per_tree=1)[0]
    wp = W.preprocess(g, tree, 10_000, backend="pallas")
    wx = W.preprocess(g, tree, 10_000, backend="xla")
    if int(wx.W_total) >= 2 ** 24:          # overflow scenario reached
        assert "xla" in captured["backends"]  # fallback engaged
    assert int(wp.W_total) == int(wx.W_total)
    assert np.array_equal(np.asarray(wp.w_own), np.asarray(wx.w_own))
