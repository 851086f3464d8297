"""The plain reference against the program, and the control against
the check, on a small graph on the CPU."""
import json
import os

import numpy as np
import pytest

from bench import check as checker
from bench import control
from bench import reference as R
from bench import synth
from bench.run import Cell

HERE = os.path.dirname(os.path.abspath(__file__))


def _json(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny():
    return Cell(name="tiny", chips=1, config_name="tiny",
                config=_json("tiny.json"), mix=_json("tiny_mix.json"),
                limits=_json("tiny_limits.json"))


@pytest.fixture(scope="module")
def arrays(tiny):
    return synth.generate(tiny.config["graph"], 5)


@pytest.mark.parametrize("motif", ["M4-2", "M5-3", "M4-3"])
def test_W_of_every_rooted_tree_equals_the_programs(arrays, motif):
    from repro.core.graph import TemporalGraph
    from repro.core.motif import get_motif
    from repro.core.spanning_tree import all_rooted_trees, candidate_trees
    from repro.core.weights import preprocess
    mo = get_motif(motif)
    g = TemporalGraph.from_edges(*arrays)
    prog = {(t.edge_ids, t.edge_ids[t.root]): int(preprocess(g, t, 2000)
                                                  .W_total)
            for t in all_rooted_trees(mo)}
    ref = R.reference_for(R.Graph(*arrays), tuple(mo.edges), 2000)
    got = {(t.edges, t.edges[t.root]): int(R.weights(ref.wn, t)[t.root]
                                           .sum()) for t in ref.trees}
    assert got == prog
    lead = [(c.edge_ids, c.edge_ids[c.root]) for c in candidate_trees(mo)]
    assert [(t.edges, t.edges[t.root]) for t in ref.trees[:len(lead)]] \
        == lead


def test_reference_in_the_programs_place_is_correct(tiny, arrays):
    g = R.Graph(*arrays)
    answers = []
    for motif, delta in tiny.config["standing"]:
        ref = R.reference_for(g, checker.motif_edges(tiny.config, motif),
                              delta)
        tree = ref.trees[0]
        ref.match(-1)
        for seed in range(6):
            d = R.sample(ref.wn, tree, ref.w[tree.shape], 1 << 14,
                         np.random.default_rng(seed), lmax=16)
            answers.append(dict(motif=motif, delta=delta, k=1 << 14, W=d.W,
                                estimate=float(d.x.mean()), failed=False))
    v = checker.check(answers, tiny.config, g, tiny.limits, 5)
    assert v["correct"], v["numbers"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_is_not_correct(tiny, seed):
    v = control.control(tiny, seed, 12)
    assert not v["correct"]
    assert v["numbers"]["z_pool"][0] > 3 * tiny.limits["z_pool"]


@pytest.mark.parametrize("motif", ["scatter-gather"])
def test_estimate_with_several_completion_lists_agrees(motif):
    """Two non-tree edges: the reference's general DeriveCnt against the
    program's estimate, within sampling error."""
    from repro.core.estimator import estimate
    from repro.core.graph import TemporalGraph
    from repro.core.motif import get_motif
    p = dict(n=60, m=6000, pairs=900, time_span=20000, alpha=1.8,
             burstiness=0.6, multiplicity=0.3, n_rings=0, n_smurf=30)
    arrays = synth.generate(p, 5)
    mo = get_motif(motif)
    res = estimate(TemporalGraph.from_edges(*arrays), mo, 3000, 1 << 15,
                   seed=3, chunk=4096)
    ref = R.reference_for(R.Graph(*arrays), tuple(mo.edges), 3000)
    tree = ref.match(res.W)[0]
    d = R.sample(ref.wn, tree, ref.w[tree.shape], 1 << 16,
                 np.random.default_rng(1), lmax=16)
    se = np.sqrt(d.x.var() / (1 << 15) + d.x.var() / (1 << 16))
    assert abs(res.estimate - d.x.mean()) < 4 * se
    assert d.overflow > 0
