"""A whole run on the CPU through the real gateway, at a tiny size: the
run comes out correct, its last line has the result's documented shape,
and each fault planted under the timed path turns ``correct`` false.

The seams: ``platform="cpu"`` skips the harness's look for a chip, and
``alter`` rewrites each reply as the clients receive it.  Every run
writes to a directory of its own, so that runs in parallel test workers
never share one.
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


def _run(out_dir, alter=None, trace=False, seed=424242, cell=None):
    return run_cell(cell or _cell(trace), seed, SECONDS, trace,
                    out_dir=str(out_dir), platform="cpu", alter=alter,
                    grace=3.0)


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    return _run(tmp_path_factory.mktemp("sound"))


def test_sound_run_is_correct(sound):
    assert sound["correct"], sound["check"]
    assert sound["failed"] == 0 and sound["attempted"] > 0
    assert sound["diagnostics"]["lru_misses_in_window"] == 0


def test_last_line_shape(sound):
    line = json.loads(json.dumps(sound))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "check"
    assert set(line["device"]) >= {"platform", "kind", "count", "mesh",
                                   "memory_peak_bytes"}
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["mesh"] is None
    for name in _cell().metrics:
        m = line["metrics"][name]
        assert m["value"] > 0 and m["unit"]
    for c in line["check"].values():
        assert set(c) == {"value", "limit"}


def test_traced_run_reports_per_layer_metrics(tmp_path):
    r = _run(tmp_path, trace=True, seed=515151)
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
def test_planted_fault_is_not_correct(fault, number, tmp_path):
    r = _run(tmp_path, alter=fault)
    assert not r["correct"]
    c = r["check"][number]
    assert c["value"] > c["limit"], r["check"]
