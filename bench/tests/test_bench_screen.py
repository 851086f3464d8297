"""Screen traffic (requests that state an error) on the CPU through the
real gateway, at a tiny size: a sound run comes out correct with no
compile in its window, and each fault planted under the timed path turns
``correct`` false -- targets four times looser than stated, an estimate
doubled, every second reply left out, a reported ``rse`` above its
target, a quarter of the error reported.  The control answers the same
traffic, adaptive budgets and all, and fails; the reference in the
program's place passes."""
import json
import os

import pytest

from bench import control
from bench.run import Cell
from bench.server import Server
from bench.tests.test_bench_rehearsal import _drop_every_second, _run, \
    _scale_estimate

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
SEED = 818181
SECONDS = 6.0


def _json(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return json.load(f)


def _cell(trace=False):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    pick = bench["per_layer" if trace else "end_to_end"]
    return Cell(name="tiny.screen", chips=1, config_name="tiny",
                config=_json("tiny.json"), mix=_json("tiny_screen_mix.json"),
                limits=_json("tiny_screen_limits.json"),
                metrics={m["name"]: m for m in pick
                         if "wiki.screen" in m.get("workloads",
                                                   ["wiki.screen"])})


def _screen(out_dir, cache, alter=None, trace=False, seed=SEED):
    return _run(out_dir, cache, alter=alter, trace=trace, seed=seed,
                cell=_cell(trace), seconds=SECONDS)


@pytest.fixture(scope="module")
def sound(tmp_path_factory, jax_cache):
    return _screen(tmp_path_factory.mktemp("screen"), jax_cache)


def test_sound_screen_run_is_correct(sound):
    assert sound["correct"], sound["check"]
    assert sound["failed"] == 0 and sound["attempted"] > 0
    assert sound["check"]["rse_miss"]["value"] == 0
    assert 0 < sound["check"]["rse_under"]["value"] <= \
        sound["check"]["rse_under"]["limit"]
    d = sound["diagnostics"]
    assert d["backend_compiles_in_window"] == 0, d
    assert d["lru_misses_in_window"] == 0
    assert all(fused for *_, fused in d["warm"])
    assert {m for m, *_ in d["warm"]} == {"M4-2", "M5-3"}
    for name in ("setup_s", "samples_per_s.screen"):
        assert sound["metrics"][name]["value"] > 0


def test_traced_screen_run_reports_its_per_layer_metrics(tmp_path,
                                                         jax_cache):
    r = _screen(tmp_path, jax_cache, trace=True, seed=828282)
    assert r["correct"], r["check"]
    for name in ("queue_wait_ms", "samples_per_screen", "screens_per_s",
                 "preprocess_s"):
        assert r["metrics"][name]["value"] > 0, name
    # served k: at least the initial budget, a whole number of windows
    assert r["metrics"]["samples_per_screen"]["value"] >= 4096
    assert "samples_per_s.screen" not in r["metrics"]


def _loosen_targets(monkeypatch):
    """Every request reaches the server with a target four times looser
    than the mix states; the harness keeps the stated one."""
    send = Server.send

    def looser(self, *objs):
        return send(self, *(dict(o, target_rse=4 * o["target_rse"])
                            if "target_rse" in o else o for o in objs))
    monkeypatch.setattr(Server, "send", looser)


def _rse_above_target(r):
    return dict(r, rse=1.0) if "rse" in r else r


def _rse_quartered(r):
    return dict(r, rse=r["rse"] / 4) if r.get("rse") else r


@pytest.mark.parametrize("fault,number", [
    ("looser", "rse_miss"),                   # stops at a looser error
    (_scale_estimate, "z_max"),               # an answer altered where made
    (_drop_every_second(), "failed"),         # half the answers left out
    (_rse_above_target, "rse_miss"),          # the stated error not met
    (_rse_quartered, "rse_under"),            # a quarter of the error told
])
def test_planted_screen_fault_is_not_correct(fault, number, tmp_path,
                                             jax_cache, monkeypatch):
    if fault == "looser":
        _loosen_targets(monkeypatch)
        fault = None
    r = _screen(tmp_path, jax_cache, alter=fault)
    assert not r["correct"]
    c = r["check"][number]
    assert c["value"] > c["limit"], r["check"]


@pytest.mark.parametrize("sound_reading", [True, False])
def test_control_answers_screen_traffic(sound_reading):
    v = control.control(_cell(), 3, 60, sound=sound_reading)
    assert v["correct"] is sound_reading, v["numbers"]
    assert max(v["info"]["k_served"]) > 4096     # a growth round ran
    assert v["numbers"]["rse_miss"][0] == 0
