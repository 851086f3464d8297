"""A whole run on the CPU through the real gateway, at a tiny size: the
run comes out correct, its last line has the result's documented shape,
and each fault planted under the timed path turns ``correct`` false.

The seams: ``platform="cpu"`` skips the harness's look for a chip, and
``alter`` rewrites each reply as the clients receive it.
"""
import json
import os

import pytest

from bench.run import Cell, run_cell

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
SECONDS = 4.0


def _json(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return json.load(f)


def _cell(trace=False):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    pick = bench["per_layer" if trace else "end_to_end"]
    return Cell(name="tiny", chips=1, config_name="tiny",
                config=_json("tiny.json"), mix=_json("tiny_mix.json"),
                limits=_json("tiny_limits.json"),
                metrics={m["name"]: m for m in pick})


def _run(alter=None, trace=False, seed=424242):
    return run_cell(_cell(trace), seed, SECONDS, trace, platform="cpu",
                    alter=alter, grace=3.0)


@pytest.fixture(scope="module")
def sound():
    return _run()


def test_sound_run_is_correct(sound):
    assert sound["correct"], sound["check"]
    assert sound["failed"] == 0 and sound["attempted"] > 0
    assert sound["diagnostics"]["lru_misses_in_window"] == 0


def test_last_line_shape(sound):
    line = json.loads(json.dumps(sound))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "check"
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["device"]["platform"] == "cpu"
    for name in _cell().metrics:
        m = line["metrics"][name]
        assert m["value"] > 0 and m["unit"]
    for c in line["check"].values():
        assert set(c) == {"value", "limit"}


def test_traced_run_reports_per_layer_metrics():
    r = _run(trace=True, seed=515151)
    assert r["correct"], r["check"]
    assert r["metrics"]["preprocess_s"]["value"] > 0
    assert "setup_s" not in r["metrics"]


def _scale_estimate(r):
    if "estimate" in r:
        r = dict(r, estimate=2.0 * r["estimate"])
    return r


def _bump_W(r):
    return dict(r, W=r["W"] + 1) if "W" in r else r


def _drop_every_second():
    seen = []

    def alter(r):
        if str(r.get("id")).startswith("warm"):
            return r
        seen.append(1)
        if len(seen) % 2 == 0:
            raise TimeoutError("reply left out")
        return r
    return alter


@pytest.mark.parametrize("fault,number", [
    (_scale_estimate, "z_max"),          # an answer altered where made
    (_bump_W, "w_gap"),                  # the DP's total altered
    (_drop_every_second(), "failed"),    # half the batch left out
])
def test_planted_fault_is_not_correct(fault, number):
    r = _run(alter=fault)
    assert not r["correct"]
    c = r["check"][number]
    assert c["value"] > c["limit"], r["check"]
