"""The one traffic generator: a mix without a stated error sends the
requests it always sent, byte for byte, and a screen mix states its
target and cap on every request, cycling through every (motif, target)
with the motif varying first.  The screen cell finds its files."""
import json
import os

import pytest

from bench.run import Cell
from bench.server import child_env
from bench.traffic import Record, Requests, _mix

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
SEED = 3141592653


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _parents_request(mix, standing, seed, client, i):
    """The request as the generator wrote it before mixes could state an
    error."""
    motif, delta = standing[(client + i) % len(standing)]
    return dict(id=f"c{client}.{i}", motif=motif, delta=int(delta),
                k=int(mix["k"]), seed=_mix(seed, 0, client, i))


@pytest.mark.parametrize("mix,config", [
    (("bench", "traffic", "batch.json"), ("bench", "configs",
                                          "aml-hi-small.json")),
    (("bench", "traffic", "batch.mesh4.json"), ("bench", "configs",
                                                "aml-hi-small.json")),
    (("bench", "tests", "data", "tiny_mix.json"), ("bench", "tests", "data",
                                                   "tiny.json")),
])
def test_batch_requests_are_byte_identical_to_the_parents(mix, config):
    mix, standing = _json(ROOT, *mix), _json(ROOT, *config)["standing"]
    reqs = Requests(mix, standing, SEED)
    for client in range(int(mix["clients"]) + 1):
        for i in range(7):
            assert json.dumps(reqs.request(client, i)) == json.dumps(
                _parents_request(mix, standing, SEED, client, i))


def test_screen_requests_state_their_error():
    cell = Cell.load("wiki.screen", False)
    mix, standing = cell.mix, cell.config["standing"]
    targets = mix["target_rse"]
    reqs = Requests(mix, standing, SEED)
    n = len(standing) * len(targets)
    for client in range(int(mix["clients"])):
        seen = set()
        for i in range(n):
            r = reqs.request(client, i)
            combo = (client + i) % n
            motif, delta = standing[combo % len(standing)]
            assert r == dict(id=f"c{client}.{i}", motif=motif, delta=delta,
                             k=mix["k"], seed=_mix(SEED, 0, client, i),
                             target_rse=targets[combo // len(standing)],
                             k_max=mix["k_max"])
            seen.add((r["motif"], r["target_rse"]))
        assert len(seen) == n       # every client asks every combination


@pytest.mark.parametrize("reply,target,met", [
    ({"ok": True, "k": 65536, "rse": 0.04}, 0.05, True),
    ({"ok": True, "k": 65536, "rse": 0.05}, 0.05, True),
    ({"ok": True, "k": 4194304, "rse": 0.06}, 0.05, False),  # k_max first
    ({"ok": True, "k": 65536, "rse": None}, 0.05, False),
    ({"ok": True, "k": 65536, "rse": 0.01, "degraded": True}, 0.05, False),
    ({"ok": False}, 0.05, False),
    ({"ok": True, "k": 131072}, None, True),                 # no target
])
def test_a_screen_counts_only_when_it_meets_its_target(reply, target, met):
    req = {"id": "c0.0"} if target is None else {"id": "c0.0",
                                                  "target_rse": target}
    assert Record(req=req, sent=0.0, answered=1.0, reply=reply).met is met


def test_screen_cell_finds_its_files():
    from repro.core.motif import get_motif
    cell = Cell.load("wiki.screen", False)
    assert cell.chips == 1 and cell.config_name == "wiki-talk-slice"
    assert {"target_rse", "k_max"} <= set(cell.mix)
    assert cell.limits["reference_samples"] >= 1 << 20
    for motif, delta in cell.config["standing"]:
        assert delta == 3600
        assert [tuple(e) for e in cell.config["motifs"][motif]] == list(
            get_motif(motif).edges)
    assert set(cell.metrics) == {"setup_s", "samples_per_s.screen"}


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_reduced_keys_are_the_files(config):
    entry = next(c for c in BENCH["configs"] if c["name"] == config)
    cfg = _json(ROOT, entry["file"])
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    assert set(cfg["reduced"]) <= set(cfg["graph"])


@pytest.mark.parametrize("cache", ["bench/cache/jax", "elsewhere"])
def test_child_env_takes_the_compile_cache_it_is_given(cache, tmp_path):
    want = str(tmp_path / cache)
    env = child_env(ROOT, "cpu", "metrics", want)
    assert env["JAX_COMPILATION_CACHE_DIR"] == want
