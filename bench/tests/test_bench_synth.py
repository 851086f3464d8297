"""The benchmark's graph generators give exact shapes for every seed."""
import json
import os

import numpy as np
import pytest

from bench import synth

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIGS = os.path.join(HERE, "..", "configs")


def _scaled(name: str, f: int = 64) -> dict:
    """A configuration's generator parameters with every scale key / f."""
    with open(os.path.join(CONFIGS, name + ".json")) as fh:
        p = dict(json.load(fh)["graph"])
    for key in ("n", "m", "pairs", "time_span", "n_rings", "n_smurf"):
        if key in p:
            p[key] = p[key] // f
    return p


@pytest.mark.parametrize("f", [64, 16])
def test_exact_sizes_for_two_seeds(f):
    p = _scaled("aml-hi-small", f)
    shapes = []
    for seed in (5, 2**31 + 11):
        src, dst, t = synth.generate(p, seed)
        key = src * p["n"] + dst
        shapes.append((len(t), len(np.unique(np.concatenate([src, dst]))),
                       len(np.unique(key)), int(t.min()), int(t.max())))
        assert len(np.unique(key * (p["time_span"] + 1) + t)) == p["m"]
        assert not np.any(src == dst)
    assert shapes[0] == shapes[1] == (p["m"], p["n"], p["pairs"], 0,
                                      p["time_span"])


def test_same_seed_same_graph_and_cached_file(tmp_path):
    p = _scaled("aml-hi-small")
    a = synth.generate(p, 3)
    path = synth.graph_path(str(tmp_path), "aml", p, 3)
    b = synth.load(path)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    mtime = os.path.getmtime(path)
    assert synth.graph_path(str(tmp_path), "aml", p, 3) == path
    assert os.path.getmtime(path) == mtime


def test_planted_rings_are_cycles_in_time_order():
    p = dict(_scaled("aml-hi-small"), n_smurf=0)
    src, dst, t = synth.generate(p, 9)
    ps, pd, pt = synth._planted(p, synth._rng(9, 1))
    assert len(ps) == 5 * p["n_rings"]
    ring = slice(0, 5)
    assert list(pd[ring]) == list(ps[ring][[1, 2, 3, 4, 0]])
    assert np.all(np.diff(pt[ring]) > 0)


def test_too_few_pairs_to_cover_the_vertices_is_refused():
    p = dict(_scaled("aml-hi-small"), pairs=_scaled("aml-hi-small")["n"] // 3)
    with pytest.raises(ValueError, match="cannot cover"):
        synth.generate(p, 4)
