"""Every cell, configuration, mix, limit and metric is found by name."""
import json
import os

import pytest

from bench.run import Cell, reader

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [False, True])
def test_cell_loads_its_files(cell, trace):
    c = Cell.load(cell, trace)
    assert c.config["standing"] and c.mix["server"]["chunk"] > 0
    assert set(c.limits) >= {"reference_samples", "z_max", "z_pool"}
    for motif, _ in c.config["standing"]:
        assert c.config["motifs"][motif]
    kinds = {m["name"] for m in BENCH["per_layer" if trace else "end_to_end"]}
    assert set(c.metrics) <= kinds and c.metrics
    if not trace:
        assert "setup_s" in c.metrics


@pytest.mark.parametrize("metric", METRICS)
def test_every_metric_has_a_reader(metric):
    assert callable(reader(metric))


def test_per_layer_metrics_move_a_metric_their_cells_report():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m["workloads"]:
            assert cell in moved.get("workloads", [cell]), (m["name"], cell)


def test_unknown_workload_is_refused():
    from bench.run import BenchError
    with pytest.raises(BenchError):
        Cell.load("no.such.cell", False)


def test_unknown_device_kind_has_no_peaks():
    from bench.peaks import peaks
    assert peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")
