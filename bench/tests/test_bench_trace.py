"""The trace reduction on a recorded chip trace: two executions of the
window program of ``aml.batch`` and the gap between them (TPU v5 lite,
trimmed to the gap's surroundings and stored as a text proto)."""
import os
from types import SimpleNamespace

import pytest

from bench import trace as T
from bench.run import reader

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "tpu_window_trace.pbtxt")


@pytest.fixture(scope="module")
def reduced():
    return T.reduce_trace(T.load_planes(FIXTURE))


def test_busy_is_the_union_of_program_executions(reduced):
    assert reduced["devices"] == 1
    assert reduced["window_s"] == pytest.approx(2.516065081)
    assert reduced["busy_s"] == pytest.approx(2.510145187)
    runs, secs = next(v for k, v in reduced["modules"].items()
                      if k.startswith("jit_window"))
    assert runs == 2 and secs == pytest.approx(2.510144645)


def test_idle_gaps_are_named_by_the_host(reduced):
    name, secs = reduced["idle_gaps"][0]
    assert name == "tpu::System::TransferFromDevice"
    assert secs == pytest.approx(0.004672652)
    assert sum(s for _, s in reduced["idle_gaps"]) <= \
        reduced["window_s"] - reduced["busy_s"] + 1e-9


def test_device_ops_are_innermost_and_named_short(reduced):
    names = [n for n, _ in reduced["device_ops"]]
    assert all(" = " not in n and n.startswith("%") for n in names)
    assert len(names) <= 10


def test_innermost_drops_enclosing_events():
    evs = [("loop", 0.0, 10.0), ("a", 1.0, 2.0), ("b", 3.0, 4.0),
           ("c", 10.0, 11.0)]
    assert [e[0] for e in T.innermost(evs)] == ["a", "b", "c"]
    assert T.merge([(0, 2), (1, 3), (5, 6)]) == [[0, 3], [5, 6]]


def test_trace_readers(reduced):
    from bench.peaks import peaks
    ctx = SimpleNamespace(trace=reduced, peaks=peaks("TPU v5 lite"),
                          cell=SimpleNamespace(mix={"server": {
                              "chunk": 8192, "checkpoint_every": 4}}),
                          check={"info": {"bytes_per_sample":
                                          {"M5-3": 200.0}}})
    idle = reader("device_idle_pct.batch")(ctx)
    assert idle == pytest.approx(100 * (1 - 2.510145187 / 2.516065081))
    roof = reader("window_roofline_pct")(ctx)
    assert roof == pytest.approx(
        100 * 2 * 32768 * 200.0 / 819e9 / 2.510144645)
    assert 0 < roof < 100


def test_no_device_events_reads_nothing():
    planes = [("/host:CPU", {"python": [("f", 0.0, 1.0)]})]
    assert T.reduce_trace(planes) is None
    ctx = SimpleNamespace(trace=None, peaks=None)
    assert reader("device_idle_pct.batch")(ctx) is None
    assert reader("window_roofline_pct")(ctx) is None
