"""A traced run on the CPU through the real gateway, at a tiny size: the
server's compile time by stage reaches the result line, and a CPU
capture, which has no device plane, gives no reading to the readers of
named scopes and annotations."""
from bench.tests.test_bench_rehearsal import _run

TRACE_READERS = ("sampler_ns_per_sample", "validator_ns_per_sample",
                 "window_gap_ms")


def test_traced_run_reports_compile_stages(tmp_path, jax_cache):
    r = _run(tmp_path, jax_cache, trace=True, seed=616161)
    assert r["correct"], r["check"]
    pre = r["metrics"]["preprocess_compile_s"]["value"]
    assert 0 < pre <= r["metrics"]["preprocess_s"]["value"]
    assert r["metrics"]["window_compile_s"]["value"] > 0
    for name in TRACE_READERS:
        assert name not in r["metrics"]
