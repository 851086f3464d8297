"""Observability layer (repro.obs): registry, spans, flight recorder.

The load-bearing assertions:

* **Bit-identity across levels**: estimates at ``REPRO_OBS=off``,
  ``metrics`` and ``trace`` are bit-identical — solo and cohort-fused,
  both sampler backends.  Telemetry observes; it never participates.
* **Monotonic counters**: ``engine.clear_window_cache()`` and session
  teardown no longer zero any counter; the only reset is the explicit
  test seam.
* **Trace-id propagation**: one gateway wire line yields a connected
  span chain (intake -> queue_wait -> drain -> dispatch -> emit) under
  ONE trace id, across all three gateway threads.
* **Structural soundness**: histogram bucket math, Prometheus text
  round-trip, ring wraparound, span nesting, the no-retrace warm path
  with tracing enabled.
"""
from __future__ import annotations

import io
import json

import pytest

from repro import obs
from repro.api import EstimateConfig, Request, Session
from repro.core import engine
from repro.core.batch import estimate_many
from repro.core.estimator import estimate
from repro.core.motif import get_motif
from repro.gateway import gateway_serve_loop
from repro.obs.registry import (BUCKET_BOUNDS, N_BUCKETS, CounterBlock,
                                Histogram, Registry)

CHUNK = 64
DELTA = 2_500
SPEC = "powerlaw:n=120,m=2400,time_span=60000,seed=5"


def _graph():
    from repro.launch.estimate import parse_graph
    return parse_graph(SPEC)


def _cfg(**kw):
    base = dict(chunk=CHUNK, coalesce_window_s=60.0)
    base.update(kw)
    return EstimateConfig(**base)


@pytest.fixture(autouse=True)
def _obs_restore():
    """Every test leaves the level knob-resolved and the ring empty."""
    yield
    obs.set_level(None)
    obs.RECORDER.clear()


# ---------------------------------------------------------------------------
# registry: buckets, exposition, monotonicity, facades
# ---------------------------------------------------------------------------
def test_histogram_bucket_math():
    assert N_BUCKETS == len(BUCKET_BOUNDS) + 1
    assert BUCKET_BOUNDS[0] == 1e-6
    # boundary values land in the bucket whose bound they equal
    assert Histogram.bucket_index(0.0) == 0
    assert Histogram.bucket_index(1e-6) == 0
    assert Histogram.bucket_index(1.0000001e-6) == 1
    assert Histogram.bucket_index(2e-6) == 1
    # beyond the last bound -> the +Inf bucket
    assert Histogram.bucket_index(BUCKET_BOUNDS[-1]) == len(BUCKET_BOUNDS) - 1
    assert Histogram.bucket_index(1e9) == len(BUCKET_BOUNDS)

    h = Histogram("t_seconds")
    for dt in (0.0, 1e-6, 3e-6, 0.5, 1e9):
        h.observe(dt)
    snap = h.snapshot()
    assert sum(snap["counts"]) == h.count == 5
    assert snap["sum"] == pytest.approx(1e9 + 0.5 + 4e-6)
    assert snap["counts"][-1] == 1          # the 1e9 outlier


def test_prometheus_text_round_trip():
    reg = Registry()
    c = reg.counter("t_total", "a counter")
    c.inc(3)
    g = reg.gauge("t_rate", "a gauge")
    g.set(2.5)
    fam = reg.histogram("t_seconds", "a histogram", labels=("tenant",))
    child = fam.labels(tenant='we"ird\\name')
    child.observe(1e-6)
    child.observe(0.5)
    text = reg.prometheus_text()
    assert text.endswith("\n")
    lines = text.splitlines()
    assert "# HELP t_total a counter" in lines
    assert "# TYPE t_total counter" in lines
    assert "t_total 3" in lines
    assert "# TYPE t_rate gauge" in lines
    assert "t_rate 2.5" in lines
    assert "# TYPE t_seconds histogram" in lines
    # label escaping: the quote and backslash survive, escaped
    esc = 'tenant="we\\"ird\\\\name"'
    buckets = [ln for ln in lines if ln.startswith("t_seconds_bucket")]
    assert len(buckets) == N_BUCKETS and all(esc in ln for ln in buckets)
    # cumulative buckets are nondecreasing and +Inf equals _count
    counts = [int(ln.rsplit(" ", 1)[1]) for ln in buckets]
    assert counts == sorted(counts)
    assert counts[-1] == 2
    assert f"t_seconds_count{{{esc}}} 2" in lines
    # idempotent re-declare returns the same object; mismatch raises
    assert reg.counter("t_total") is c
    with pytest.raises(ValueError):
        reg.gauge("t_total")
    with pytest.raises(ValueError):
        reg.histogram("t_seconds", labels=("other",))


def test_counters_are_monotonic():
    reg = Registry()
    c = reg.counter("m_total")
    c.inc(2)
    with pytest.raises(ValueError):
        c.inc(-1)
    assert c.value == 2


def test_counterblock_facade_semantics():
    class Block(CounterBlock):
        _PREFIX = "t_block"
        _FIELDS = ("hits", "misses")

    reg = Registry()
    b = Block(reg)
    b.hits += 1
    b.hits += 2
    b.misses = 5                       # upward assignment = increment
    assert b.hits == 3 and b.misses == 5
    assert b.as_dict() == {"hits": 3, "misses": 5}
    # two blocks over one registry are views of the SAME counters
    assert Block(reg).hits == 3
    b.hits = 1                         # downward assignment = test reset
    assert b.hits == 1
    b.reset()
    assert b.as_dict() == {"hits": 0, "misses": 0}
    with pytest.raises(AttributeError):
        b.nope = 1


def test_engine_stats_survive_cache_clear():
    """Satellite (b): cache clears must not zero serving counters."""
    g = _graph()
    estimate(g, get_motif("M4-2"), DELTA, 256, seed=0, chunk=CHUNK)
    before = engine.STATS.as_dict()
    assert before["dispatches"] > 0
    engine.clear_window_cache()
    assert engine.STATS.as_dict() == before
    estimate(g, get_motif("M4-2"), DELTA, 256, seed=0, chunk=CHUNK)
    assert engine.STATS.dispatches > before["dispatches"]


def test_window_lru_counters_track_hits_and_misses():
    g = _graph()
    fam = obs.REGISTRY.get("repro_engine_window_lru_total")
    hit = fam.labels(cache="window", event="hit")
    miss = fam.labels(cache="window", event="miss")
    engine.clear_window_cache()
    m0, h0 = miss.value, hit.value
    estimate(g, get_motif("M4-2"), DELTA, 256, seed=0, chunk=CHUNK)
    assert miss.value > m0                 # cold: compiled at least once
    m1, h1 = miss.value, hit.value
    estimate(g, get_motif("M4-2"), DELTA, 256, seed=1, chunk=CHUNK)
    assert hit.value > h1 and miss.value == m1     # warm: pure re-hits


# ---------------------------------------------------------------------------
# bit-identity across obs levels
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_bit_identity_across_levels(backend):
    g = _graph()
    solo, fused = {}, {}
    for lvl in ("off", "metrics", "trace"):
        obs.set_level(lvl)
        r = estimate(g, get_motif("M5-3"), DELTA, 512, seed=0, chunk=CHUNK,
                     sampler_backend=backend)
        solo[lvl] = (r.estimate, r.W, r.valid)
        many = estimate_many(g, [("M4-2", DELTA, 256), ("M4-4", DELTA, 256),
                                 ("0-1,1-2", 1_500, 256)],
                             seed=0, chunk=CHUNK, sampler_backend=backend)
        fused[lvl] = [(m.estimate, m.W, m.valid) for m in many]
    assert solo["off"] == solo["metrics"] == solo["trace"]
    assert fused["off"] == fused["metrics"] == fused["trace"]


def test_off_level_records_nothing():
    obs.set_level("off")
    obs.RECORDER.clear()
    stage = obs.REGISTRY.get("repro_stage_seconds")
    n0 = sum(c.count for c in stage.children())
    d0 = engine.STATS.dispatches
    estimate(_graph(), get_motif("M4-2"), DELTA, 256, seed=0, chunk=CHUNK)
    assert len(obs.RECORDER) == 0                       # no spans recorded
    assert sum(c.count for c in stage.children()) == n0  # no histograms
    assert engine.STATS.dispatches > d0                  # counters always-on


def test_metrics_level_feeds_stages_but_not_ring():
    obs.set_level("metrics")
    obs.RECORDER.clear()
    stage = obs.REGISTRY.get("repro_stage_seconds")
    n0 = sum(c.count for c in stage.children())
    with Session(_graph(), _cfg()) as s:
        h = s.submit(Request(motif="M4-2", delta=DELTA, k=256))
        s.flush()
        h.result()
    assert sum(c.count for c in stage.children()) > n0
    assert len(obs.RECORDER) == 0


# ---------------------------------------------------------------------------
# spans, nesting, flight recorder
# ---------------------------------------------------------------------------
def test_span_nesting_and_trace_inheritance():
    obs.set_level("trace")
    obs.RECORDER.clear()
    tid = obs.new_trace()
    assert len(tid) == 16 and tid != obs.new_trace()
    with obs.trace_context(tid):
        with obs.span("outer") as a:
            with obs.span("inner") as b:
                assert b.parent_id == a.span_id
                assert a.trace == b.trace == tid
            obs.event("point", k=1)
    recs = obs.RECORDER.records()
    by_name = {r["name"]: r for r in recs}
    assert by_name["inner"]["parent"] == by_name["outer"]["span"]
    assert by_name["outer"]["parent"] == 0
    assert {r["trace"] for r in recs} == {tid}
    assert by_name["point"]["dur_s"] == 0.0
    assert by_name["point"]["attrs"] == {"k": 1}
    # inner exits (and records) before outer
    assert recs.index(by_name["inner"]) < recs.index(by_name["outer"])


def test_flight_recorder_ring_wraparound():
    r = obs.FlightRecorder(4)
    for i in range(10):
        r.append({"name": f"s{i}"})
    assert len(r) == 4 and r.recorded == 10
    assert [x["name"] for x in r.records()] == ["s6", "s7", "s8", "s9"]
    nd = r.export_ndjson()
    assert nd.endswith("\n")
    assert [json.loads(ln)["name"] for ln in nd.splitlines()] \
        == ["s6", "s7", "s8", "s9"]
    r.clear()
    assert len(r) == 0 and r.recorded == 0 and r.export_ndjson() == ""


def test_no_retrace_warm_path_with_tracing(no_retrace):
    obs.set_level("trace")
    with Session(_graph(), _cfg()) as s:
        h = s.submit(Request(motif="M4-2", delta=DELTA, k=256))
        s.flush()
        cold = h.result()
        with no_retrace():
            h2 = s.submit(Request(motif="M4-2", delta=DELTA, k=256))
            s.flush()
            warm = h2.result()
    assert warm.estimate == cold.estimate


# ---------------------------------------------------------------------------
# wire surfaces: metrics / trace verbs + the gateway span chain
# ---------------------------------------------------------------------------
def test_serve_metrics_and_trace_verbs():
    obs.set_level("trace")
    obs.RECORDER.clear()
    # the telemetry verbs answer inline, so the request's drain must have
    # finished before they are read: serve it in a first loop, whose quit
    # waits on the dispatcher, and scrape in a second loop
    scrape = ['{"cmd": "metrics"}', '{"cmd": "trace"}',
              '{"cmd": "profile", "windows": 1}', '{"cmd": "health"}',
              '{"cmd": "quit"}']
    out = io.StringIO()
    with Session(_graph(), _cfg()) as s:
        for lines in ([json.dumps({"id": 1, "motif": "M4-2",
                                   "delta": DELTA, "k": 256}),
                       '{"cmd": "quit"}'], scrape):
            gateway_serve_loop(adopt=s, outfile=out,
                               infile=io.StringIO("\n".join(lines) + "\n"))
    resp = [json.loads(ln) for ln in out.getvalue().splitlines()]
    assert next(r for r in resp if r.get("id") == 1)["ok"]
    met = next(r for r in resp if r.get("cmd") == "metrics")
    assert met["ok"] and met["content_type"].startswith("text/plain")
    assert "# TYPE repro_engine_dispatches_total counter" in met["text"]
    assert "repro_stage_seconds_bucket" in met["text"]
    tr = next(r for r in resp if r.get("cmd") == "trace")
    assert tr["ok"] and tr["level"] == "trace" and tr["count"] == len(
        tr["spans"]) > 0
    assert {"gateway.intake", "session.drain", "engine.dispatch"} \
        <= {s["name"] for s in tr["spans"]}
    prof = next(r for r in resp if r.get("cmd") == "profile")
    assert prof["ok"] is False          # no --profile-dir configured
    health = next(r for r in resp if r.get("cmd") == "health")
    assert health["obs"]["level"] == "trace"
    assert health["obs"]["recorded"] > 0


def test_gateway_trace_chain_across_threads():
    """One wire request -> one connected intake->emit chain, one id."""
    obs.set_level("trace")
    obs.RECORDER.clear()
    lines = [json.dumps({"cmd": "open_tenant", "tenant": "fin",
                         "graph": SPEC}),
             json.dumps({"tenant": "fin", "id": 7, "motif": "M4-2",
                         "delta": DELTA, "k": 256}),
             '{"cmd": "quit"}']
    out = io.StringIO()
    served = gateway_serve_loop(
        _cfg(), infile=io.StringIO("\n".join(lines) + "\n"), outfile=out)
    assert served == 1
    recs = obs.RECORDER.records()
    intake = next(r for r in recs if r["name"] == "gateway.intake"
                  and r.get("attrs", {}).get("id") == 7)
    tid = intake["trace"]
    assert tid is not None
    chain = [r for r in recs if r["trace"] == tid]
    names = {r["name"] for r in chain}
    assert {"gateway.intake", "stage.queue_wait", "session.preprocess",
            "session.drain", "engine.dispatch", "engine.device",
            "gateway.emit"} <= names
    # the chain genuinely crosses the three gateway threads
    threads = {r["thread"] for r in chain}
    assert "gateway-dispatch" in threads and "gateway-emit" in threads
    assert len(threads) >= 3
    # device span nests under its dispatch span
    disp = next(r for r in chain if r["name"] == "engine.dispatch")
    dev = next(r for r in chain if r["name"] == "engine.device")
    assert dev["parent"] == disp["span"]
    # per-tenant latency histogram saw the request
    fam = obs.REGISTRY.get("repro_tenant_request_seconds")
    assert fam.labels(tenant="fin").count >= 1


def test_gateway_rse_trajectory_events():
    """Per-request RSE-vs-samples trajectory lands in the recorder."""
    obs.set_level("trace")
    obs.RECORDER.clear()
    with Session(_graph(), _cfg(checkpoint_every=2)) as s:
        h = s.submit(Request(motif="M4-2", delta=DELTA, k=4 * CHUNK))
        s.flush()
        h.result()
    points = [r for r in obs.RECORDER.records()
              if r["name"] == "request.window"]
    assert len(points) >= 2
    ks = [p["attrs"]["k_done"] for p in points]
    assert ks == sorted(ks) and ks[-1] == 4 * CHUNK
    assert all("rse" in p["attrs"] for p in points)


# ---------------------------------------------------------------------------
# profiler annotations, named scopes, compile time by stage
# ---------------------------------------------------------------------------
def _annotations(logdir) -> dict:
    """``{name: [stats dict, ...]}`` of the host events in a capture."""
    import glob

    from jax.profiler import ProfileData
    path = glob.glob(str(logdir / "**" / "*.xplane.pb"), recursive=True)[0]
    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                out.setdefault(ev.name, []).append(dict(ev.stats))
    return out


def test_capture_carries_span_annotations(tmp_path):
    """Spans open when the capture starts (the drain) and spans opened
    during it (every dispatch) land in the trace with their attrs."""
    obs.set_level("metrics")
    with Session(_graph(), _cfg(checkpoint_every=2)) as s:
        h = s.submit(Request(motif="M4-2", delta=DELTA, k=6 * CHUNK))
        s.flush()
        h.result()                          # compiled outside the capture
        obs.arm_profile(5, str(tmp_path))
        for seed in (1, 2):
            h = s.submit(Request(motif="M4-2", delta=DELTA, k=6 * CHUNK,
                                 seed=seed))
            s.flush()
            h.result()
    assert obs.profile_status()["captured"] == 5
    ann = _annotations(tmp_path)
    disp = ann["engine.dispatch"]
    assert len(disp) == 5
    assert [d["j0"] for d in disp] == [0, 2, 4, 0, 2]
    assert all(d["samples"] == 2 * CHUNK and d["n"] == 2 for d in disp)
    # the first drain opened before the capture, the second ends after
    # it: both are annotated over the part the capture saw
    assert len(ann["session.drain"]) == 2
    assert len(ann["engine.device"]) == 5


def test_no_annotation_outside_a_capture(monkeypatch):
    import jax.profiler

    built = []

    class Spy:
        def __init__(self, name, **kw):
            built.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Spy)
    obs.set_level("trace")
    with obs.span("outer", stage="drain"):
        with obs.span("inner", x=1):
            obs.observe_stage("queue_wait", 0.001)
    assert built == []
    monkeypatch.setitem(obs.trace._PROFILE, "active", True)
    with obs.span("outer", stage="drain"):
        obs.observe_stage("queue_wait", 0.001)
    monkeypatch.setitem(obs.trace._PROFILE, "active", False)
    assert built == ["outer", "stage.queue_wait"]


@pytest.mark.parametrize("lvl", ["off", "metrics"])
def test_spans_import_no_jax(lvl):
    import os
    import subprocess
    import sys
    code = ("import sys\n"
            "from repro import obs\n"
            "with obs.span('a', stage='drain'):\n"
            "    with obs.span('b', stage='dispatch') as sp:\n"
            "        sp.set(n=1)\n"
            "        obs.observe_stage('queue_wait', 0.5)\n"
            "        assert obs.current_stage() == "
            f"({'None' if lvl == 'off' else repr('dispatch')})\n"
            "assert obs.current_stage() is None\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n")
    env = dict(os.environ, REPRO_OBS=lvl,
               PYTHONPATH=os.pathsep.join(p for p in sys.path if p))
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_window_program_names_its_phases(backend):
    """The lowered window program's op metadata carries the scopes the
    benchmark's trace readers attribute device time by."""
    import re

    import jax
    import jax.numpy as jnp

    from repro.core.batch import BatchPlanner
    g = _graph()
    planner = BatchPlanner(g)
    tree, wts = planner.plan(get_motif("M4-2"), DELTA)
    fn = engine.make_engine_window_fn(tree, CHUNK, backend=backend)
    keys = jnp.stack([jax.random.PRNGKey(0)])
    hlo = fn.lower(planner.dev, wts, keys, 0, n=2).as_text(debug_info=True)
    paths = set(re.findall(r'loc\("([^"]+)"', hlo))
    want = ["sample", "validate", "score"]
    if backend == "xla":
        want = ["sample/window", "sample/center", "sample/child",
                "sample/vertex_map", "validate", "score"]
    for scope in want:
        assert any(p == scope or p.startswith(scope + "/")
                   or f"/{scope}/" in p for p in paths), scope


@pytest.mark.parametrize("lvl", ["off", "metrics", "trace"])
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_goldens_at_every_level(lvl, backend):
    """Solo and cohort-fused, the pinned golden estimates hold at every
    telemetry level (scopes and annotations change no bit)."""
    from pathlib import Path

    from repro.graphs import powerlaw_temporal_graph
    gold = json.loads((Path(__file__).parent
                       / "golden_estimates.json").read_text())
    g = powerlaw_temporal_graph(n=150, m=2_000, time_span=40_000, seed=11)
    kw = dict(chunk=gold["chunk"], checkpoint_every=gold["checkpoint_every"],
              sampler_backend=backend)
    obs.set_level(lvl)
    reqs = gold["requests"]
    fused = estimate_many(
        g, [(r["motif"], r["delta"], r["k"], r["seed"]) for r in reqs]
        + [(reqs[1]["motif"], reqs[1]["delta"], reqs[1]["k"],
            reqs[1]["seed"] + 1)], **kw)
    for r, f in zip(reqs, fused):
        solo = estimate(g, get_motif(r["motif"]), r["delta"], r["k"],
                        seed=r["seed"], **kw)
        for got in (solo, f):
            assert (got.estimate, got.cnt2_sum, got.valid, got.W) == (
                r["estimate"], r["cnt2"], r["valid"], r["W"])
    assert fused[1].fused_jobs == 2         # two streams, one cohort


def test_bind_carries_stage_and_trace_into_pool_workers():
    from concurrent.futures import ThreadPoolExecutor
    obs.set_level("metrics")
    seen = []

    def work():
        seen.append((obs.current_stage(), obs.current_trace()))

    tid = obs.new_trace()
    with ThreadPoolExecutor(2) as pool:
        with obs.span("plan", stage="preprocess", trace=tid):
            pool.submit(obs.bind(work)).result()
            pool.submit(work).result()
        pool.submit(work).result()
    assert seen == [("preprocess", tid), (None, None), (None, None)]
    obs.set_level("off")
    assert obs.bind(work) is work


def test_compile_time_lands_on_the_callers_stage():
    """A fresh jit compiled in a pool worker under a ``preprocess`` span
    counts as ``compile.preprocess``; a jit nested in it counts once."""
    from concurrent.futures import ThreadPoolExecutor

    import jax
    import jax.numpy as jnp

    obs.set_level("metrics")
    assert obs.install_compile_listener()
    stage = obs.REGISTRY.get("repro_stage_seconds")
    hist = stage.labels(stage="compile.preprocess")
    compiles = obs.REGISTRY.get("repro_engine_compiles_total")
    s0, c0 = hist.sum, sum(c.value for c in compiles.children()
                           if c.label_values[0] == "preprocess")

    inner = jax.jit(lambda x: jnp.sort(x) * 3)

    def outer(x):
        return inner(x + 1).sum()

    outer_j = jax.jit(outer)
    with ThreadPoolExecutor(2) as pool:
        with obs.span("plan", stage="preprocess") as sp:
            futs = [pool.submit(obs.bind(lambda n=n: outer_j(
                jnp.arange(n, dtype=jnp.float32)).block_until_ready()))
                for n in (7, 9)]
            for f in futs:
                f.result()
    added = hist.sum - s0
    assert 0 < added <= sp.elapsed_s
    assert sum(c.value for c in compiles.children()
               if c.label_values[0] == "preprocess") >= c0 + 2
    assert obs.current_stage() is None


def test_samples_drawn_counter_replaces_the_rate_gauge():
    assert obs.REGISTRY.get("repro_sampler_samples_per_s") is None
    d0 = engine.STATS.samples_drawn
    r = estimate(_graph(), get_motif("M4-2"), DELTA, 5 * CHUNK, seed=0,
                 chunk=CHUNK)
    assert engine.STATS.samples_drawn - d0 == r.k == 5 * CHUNK
    assert "repro_engine_samples_drawn_total" in \
        obs.REGISTRY.prometheus_text()
