"""The capture reader (``bench/xplane.py``) and the five readers that
attribute device time by named scope and annotation, and set-up by
compile stage."""
import os
from types import SimpleNamespace

import pytest

from bench import trace as T
from bench import xplane as X
from bench.run import reader

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SCOPELESS = os.path.join(DATA, "tpu_window_trace.pbtxt")
# a traced aml.batch run on a TPU v5 lite, trimmed to the first three
# windows of its first drain: the ops that start in each window's first
# millisecond, its long scoped ops and its score ops (each with its
# ``tf_op`` stat alone, names cut to the instruction), the enclosing
# scan loop, and the engine.dispatch / engine.device / session.drain
# annotations with their stats
SCOPED = os.path.join(DATA, "tpu_scoped_window_trace.pbtxt")
TRACE_READERS = ("sampler_ns_per_sample", "validator_ns_per_sample",
                 "window_gap_ms")
COMPILE_READERS = ("preprocess_compile_s", "window_compile_s")


def test_reads_what_profile_data_reads():
    """Same planes, lines and event times as ``jax.profiler``'s reader."""
    ours, theirs = X.load_planes(SCOPELESS), T.load_planes(SCOPELESS)
    assert [n for n, _ in ours] == [n for n, _ in theirs]
    for (_, a), (_, b) in zip(ours, theirs):
        assert set(a) == set(b)
        for line in b:
            assert [ev[:3] for ev in a[line]] == b[line]


def test_scoped_capture_on_the_chip():
    planes = X.load_planes(SCOPED)
    ns = X.phase_ns(planes)
    assert ns == pytest.approx({"sample": 1232492.032, "validate": 273.672,
                                "score": 807145.156, "other": 1749715.782})
    assert X.dispatched_samples(planes) == 3 * 32768
    # both gaps lie in the drain, though the first window starts before
    # the drain's annotation on the profiler clock
    assert X.window_gaps_ns(planes) == pytest.approx(
        [6697497.5, 6421583.516])
    ctx = SimpleNamespace(xplane=SCOPED)
    assert reader("sampler_ns_per_sample")(ctx) == pytest.approx(
        1232492.032 / 98304)
    assert reader("validator_ns_per_sample")(ctx) == pytest.approx(
        (273.672 + 807145.156) / 98304)
    assert reader("window_gap_ms")(ctx) == pytest.approx(
        (6.6974975 + 6.421583516) / 2)


def test_scopeless_capture_reads_nothing():
    planes = X.load_planes(SCOPELESS)
    assert X.phase_ns(planes) is None
    assert X.dispatched_samples(planes) is None
    assert X.window_gaps_ns(planes) is None
    ctx = SimpleNamespace(xplane=SCOPELESS)
    for name in TRACE_READERS:
        assert reader(name)(ctx) is None, name


@pytest.mark.parametrize("path,phase", [
    ("jit(window)/while/body/closed_call/vmap(jit(fn))/sample/child/gather",
     "sample"),
    ("jit(window)/while/body/vmap(jit(fn))/validate/sort", "validate"),
    ("jit(window)/while/body/score/add", "score"),
    ("jit(window)/while/body/sampler/add", "other"),
    ("", "other"),
])
def test_phase_of(path, phase):
    assert X.phase_of(path) == phase


def _ev(name, s, e, **stats):
    return (name, float(s), float(e), stats)


def _planes(drain=(0, 100)):
    """One device running three windows (and a small program between the
    first two), a host with dispatch annotations and one drain."""
    dev = {
        T.MODULES_LINE: [_ev("jit_window(1)", 0, 30),
                         _ev("jit_stack(2)", 32, 33),
                         _ev("jit_window(1)", 36, 60),
                         _ev("jit_window(1)", 64, 90)],
        T.OPS_LINE: [_ev("%while.1", 0, 30, tf_op="jit(window)/while"),
                     _ev("%f.1", 1, 11, tf_op="jit(window)/while/body/"
                         "vmap(jit(fn))/sample/child/gather"),
                     _ev("%f.2", 12, 15, tf_op="jit(window)/while/body/"
                         "vmap(jit(fn))/validate/gt"),
                     _ev("%f.3", 15, 16, tf_op="jit(window)/while/body/"
                         "score/add"),
                     _ev("%c.1", 17, 18),
                     _ev("%f.9", 32, 33, tf_op="jit(stack)/concatenate")],
    }
    host = {"python": [_ev("session.drain", *drain, requests=1)]
            + [_ev("engine.dispatch", s, s + 1, samples=1000, j0=j, n=4)
               for s, j in ((0, 0), (35, 4), (63, 8))]}
    return [("/device:TPU:0", dev), ("/host:CPU", host)]


def test_phases_gaps_and_samples_on_synthetic_planes():
    planes = _planes()
    ns = X.phase_ns(planes)
    # the loop encloses the others; the program between windows is out
    assert ns == {"sample": 10.0, "validate": 3.0, "score": 1.0,
                  "other": 1.0}
    assert X.dispatched_samples(planes) == 3000
    # 30 -> 36 less the 1 ns program between, then 60 -> 64
    assert X.window_gaps_ns(planes) == [5.0, 4.0]
    # a gap counts when it lies inside a drain: one that ends within the
    # second gap keeps only the first; one around no gap keeps none
    assert X.window_gaps_ns(_planes(drain=(20, 62))) == [5.0]
    assert X.window_gaps_ns(_planes(drain=(40, 62))) is None


def test_compile_readers():
    scrape = {"stage": {"preprocess": [30.0, 1.0],
                        "compile.preprocess": [12.5, 4.0],
                        "compile.device": [6.0, 9.0],
                        "compile.dispatch": [0.5, 1.0]}}
    ctx = SimpleNamespace(setup_scrape=scrape)
    assert reader("preprocess_compile_s")(ctx) == 12.5
    assert reader("window_compile_s")(ctx) == 6.5
    bare = SimpleNamespace(setup_scrape={"stage": {"preprocess": [30.0, 1.0]}})
    for name in COMPILE_READERS:
        assert reader(name)(bare) is None
