"""Chaos suite for the resilient serving core (repro.resilience).

Every fault here is INJECTED through the deterministic harness
(``resilience.faultinject``) — named sites, explicit hit schedules,
replayable runs — and every degraded path is held to the bit-identity
contract: retried dispatches, laddered backends, halved dispatch
windows, deadline partials and WAL-recovered stores must all produce
numbers identical to the fault-free run (for the samples they drew).
"""
from __future__ import annotations

import io
import json
import os

import numpy as np
import pytest

from repro.api import EstimateConfig, Request, Session
from repro.core.estimator import estimate
from repro.core.motif import get_motif
from repro.gateway import gateway_serve_loop
from repro.graphs import powerlaw_temporal_graph
from repro.resilience import (BadRequestError, FatalError, FaultInjector,
                              FaultSpec, TransientError, atomic_write_json,
                              classify, error_payload, is_retryable,
                              seeded_hits)
from repro.resilience.retry import (DISPATCH_POLICY, RetryPolicy,
                                    backoff_delay, backoff_delays)
from repro.resilience.retry import STATS as RSTATS
from repro.stream import StreamingSession, StreamStore
from repro.stream.wal import _HEADER, _REC, read_records

DELTA = 3_000
CHUNK = 256
CKPT = 2


@pytest.fixture(scope="module")
def graph():
    return powerlaw_temporal_graph(n=150, m=2_000, time_span=40_000, seed=11)


def _cfg(**kw):
    base = dict(chunk=CHUNK, checkpoint_every=CKPT, coalesce_window_s=60.0)
    base.update(kw)
    return EstimateConfig(**base)


def _est(graph, k=1024, seed=0, **kw):
    return estimate(graph, get_motif("M5-3"), DELTA, k, seed=seed,
                    chunk=CHUNK, checkpoint_every=CKPT, **kw)


# ---------------------------------------------------------------------------
# taxonomy
# ---------------------------------------------------------------------------
def test_classify_battery():
    assert classify(TransientError("x")) == "retryable"
    assert classify(TimeoutError("x")) == "retryable"
    assert classify(ConnectionError("x")) == "retryable"
    assert classify(MemoryError("x")) == "retryable"
    assert classify(FatalError("x")) == "fatal"
    assert classify(RuntimeError("x")) == "fatal"
    assert classify(AssertionError("x")) == "fatal"
    assert classify(ValueError("x")) == "bad_request"
    assert classify(TypeError("x")) == "bad_request"
    assert classify(KeyError("x")) == "bad_request"
    assert classify(BadRequestError("x")) == "bad_request"
    # marker classes win over their base classification
    assert classify(BadRequestError("x")) == "bad_request"
    assert is_retryable(TransientError("x"))
    assert not is_retryable(RuntimeError("x"))


def test_device_errors_classified_by_status_text():
    """XLA device errors are matched by type NAME (no jax import in the
    taxonomy) and their gRPC status decides retryability."""
    class XlaRuntimeError(Exception):
        pass

    assert classify(XlaRuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory allocating tensor")) == "retryable"
    assert classify(XlaRuntimeError("UNAVAILABLE: device lost")) == "retryable"
    assert classify(XlaRuntimeError(
        "INVALID_ARGUMENT: shape mismatch")) == "fatal"


def test_error_payload_wire_shape():
    p = error_payload(ValueError("no such motif"))
    assert p == {"error": "ValueError: no such motif",
                 "error_kind": "bad_request"}


# ---------------------------------------------------------------------------
# deterministic backoff
# ---------------------------------------------------------------------------
def test_backoff_deterministic_capped_and_jittered():
    pol = RetryPolicy(max_attempts=6, base_s=0.01, cap_s=0.05,
                      multiplier=2.0, jitter=0.5)
    a = backoff_delays(pol, seed=7)
    b = backoff_delays(pol, seed=7)
    assert a == b and len(a) == 5           # replayable, one per retry
    for i, d in enumerate(a):
        raw = min(pol.cap_s, pol.base_s * pol.multiplier ** i)
        assert raw * (1 - pol.jitter) <= d <= raw   # jitter window
    assert a[-1] <= pol.cap_s                        # capped
    assert backoff_delays(pol, seed=8) != a          # seeds de-synchronize
    assert backoff_delay(DISPATCH_POLICY, 0, seed=3) \
        == backoff_delay(DISPATCH_POLICY, 0, seed=3)


# ---------------------------------------------------------------------------
# fault-injection harness
# ---------------------------------------------------------------------------
def test_injector_schedule_log_and_exclusivity():
    from repro.resilience import fire
    with FaultInjector([FaultSpec("site.a", hits=(1,)),
                        FaultSpec("site.b", hits=None, tag="x")]) as inj:
        fire("site.a")                       # hit 0: pass
        with pytest.raises(TransientError):
            fire("site.a")                   # hit 1: fires
        fire("site.a")                       # hit 2: pass
        fire("site.b", tag="other")          # tag mismatch: not matched
        with pytest.raises(TransientError, match="site.b"):
            fire("site.b", tag="prefix-x-suffix")
        with pytest.raises(RuntimeError, match="already installed"):
            FaultInjector([]).__enter__()
    assert inj.log == [("site.a", "", 0, False), ("site.a", "", 1, True),
                       ("site.a", "", 2, False),
                       ("site.b", "prefix-x-suffix", 0, True)]
    fire("site.a")                           # uninstalled: no-op


def test_seeded_hits_deterministic():
    a = seeded_hits(11, 1000, 0.1)
    assert a == seeded_hits(11, 1000, 0.1)
    assert a != seeded_hits(12, 1000, 0.1)
    assert 40 < len(a) < 200                 # ~10% of 1000
    with pytest.raises(ValueError):
        seeded_hits(0, 10, 1.5)


# ---------------------------------------------------------------------------
# engine: transient retry + degradation ladder
# ---------------------------------------------------------------------------
def test_transient_dispatch_retried_bit_identical(graph):
    base = _est(graph)
    r0 = RSTATS.retries
    with FaultInjector([FaultSpec("engine.dispatch", hits=(0,))]):
        r = _est(graph)
    assert r.estimate == base.estimate and r.cnt2_sum == base.cnt2_sum
    assert r.fallback_reason == base.fallback_reason    # no ladder step
    assert not r.degraded
    assert RSTATS.retries == r0 + 1


def test_fatal_dispatch_not_retried(graph):
    r0 = RSTATS.retries
    with FaultInjector([FaultSpec("engine.dispatch", hits=(0,),
                                  exc=FatalError)]) as inj:
        with pytest.raises(FatalError):
            _est(graph)
    assert RSTATS.retries == r0              # no retry burned on a bug
    assert sum(1 for (_, _, _, fired) in inj.log if fired) == 1


def test_pallas_oom_ladders_to_xla_bit_identical(graph):
    base = _est(graph, sampler_backend="pallas")
    assert base.sampler_backend == "pallas"
    steps0 = RSTATS.ladder_steps
    # every pallas dispatch fails (exhausting the retry budget); xla
    # dispatches are untouched — the ladder swaps exactly once
    with FaultInjector([FaultSpec("engine.dispatch", tag="pallas",
                                  hits=None)]):
        r = _est(graph, sampler_backend="pallas")
    assert r.estimate == base.estimate and r.cnt2_sum == base.cnt2_sum
    assert r.valid == base.valid
    assert r.sampler_backend == "xla"
    assert "ladder: pallas -> xla" in r.fallback_reason
    assert RSTATS.ladder_steps == steps0 + 1


def test_compile_refusal_is_fatal_not_laddered(graph):
    """A compiler's refusal is deterministic: it surfaces as ``fatal``
    with no retry and no pallas -> xla swap, even though its status is
    RESOURCE_EXHAUSTED.  A device OOM while a program runs stays
    retryable.  (The messages are the TPU compiler's and runtime's own
    texts; tests/test_tpu_compile.py classifies real refusals.)"""
    class JaxRuntimeError(RuntimeError):
        pass

    hbm = ("RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out "
           "of memory in memory space hbm. Used 20.00G of 15.75G hbm.")
    vmem = ("RESOURCE_EXHAUSTED: Allocation (size=268435456) would exceed "
            "memory (size=134217728) :: #allocation2 [space=vmem]")
    mosaic = "INTERNAL: Mosaic failed to compile TPU kernel: bad layout"
    for msg in (hbm, vmem, mosaic):
        assert classify(JaxRuntimeError(msg)) == "fatal"
    assert classify(JaxRuntimeError(
        "RESOURCE_EXHAUSTED: Error allocating device buffer: Attempting to "
        "allocate 8.00G. That was not possible. There are 6.23G free."
    )) == "retryable"

    r0, steps0 = RSTATS.retries, RSTATS.ladder_steps
    with FaultInjector([FaultSpec("engine.dispatch", tag="pallas", hits=None,
                                  exc=JaxRuntimeError, message=hbm)]) as inj:
        with pytest.raises(JaxRuntimeError, match="compile permanent error"):
            _est(graph, sampler_backend="pallas")
    assert RSTATS.retries == r0 and RSTATS.ladder_steps == steps0
    assert sum(1 for (_, _, _, fired) in inj.log if fired) == 1


def test_ladder_isolates_fused_siblings(graph):
    """Only the failing cohort degrades: a second request in the SAME
    submit window but a different plan group keeps its pallas backend
    and its bit-identical numbers."""
    with Session(graph, _cfg(sampler_backend="pallas")) as s:
        b1 = s.submit(Request("M5-3", DELTA, 1024, seed=0))
        b2 = s.submit(Request("M4-2", DELTA, 512, seed=3))
        base1, base2 = b1.result(), b2.result()
    assert base1.sampler_backend == base2.sampler_backend == "pallas"

    # fail ONLY the first pallas group's first-window dispatch attempts
    # (hits 0..2 = the full retry budget); later pallas dispatches
    # (the sibling group + the laddered group never re-enter pallas)
    # proceed normally
    with Session(graph, _cfg(sampler_backend="pallas")) as s:
        h1 = s.submit(Request("M5-3", DELTA, 1024, seed=0))
        h2 = s.submit(Request("M4-2", DELTA, 512, seed=3))
        with FaultInjector([FaultSpec("engine.dispatch", tag="pallas",
                                      hits=(0, 1, 2))]):
            r1, r2 = h1.result(), h2.result()
    assert r1.sampler_backend == "xla"            # laddered
    assert "ladder: pallas -> xla" in r1.fallback_reason
    assert r2.sampler_backend == "pallas"         # sibling untouched
    assert r2.fallback_reason == base2.fallback_reason
    assert r1.estimate == base1.estimate and r1.cnt2_sum == base1.cnt2_sum
    assert r2.estimate == base2.estimate and r2.cnt2_sum == base2.cnt2_sum


def test_window_halving_ladder_bit_identical(graph):
    base = _est(graph, sampler_backend="xla")
    from repro.core import engine
    d0 = engine.STATS.dispatches
    # xla has no backend to fall to: after the first window's retry
    # budget (hits 0..2) the ladder halves the dispatch window to 1
    # chunk; subsequent sub-dispatches succeed
    with FaultInjector([FaultSpec("engine.dispatch", tag="xla",
                                  hits=(0, 1, 2))]):
        r = _est(graph, sampler_backend="xla")
    assert r.estimate == base.estimate and r.cnt2_sum == base.cnt2_sum
    assert r.valid == base.valid
    assert "dispatch window halved to 1" in r.fallback_reason
    # 4 chunks in 1-chunk sub-windows = 4 dispatches (vs 2 fault-free)
    assert engine.STATS.dispatches - d0 == 4


def test_ladder_exhausted_raises(graph):
    # even 1-chunk dispatches fail -> the ladder has no rung left
    with FaultInjector([FaultSpec("engine.dispatch", tag="xla",
                                  hits=None)]):
        with pytest.raises(TransientError):
            _est(graph, sampler_backend="xla")


# ---------------------------------------------------------------------------
# deadlines: graceful degradation, never an error
# ---------------------------------------------------------------------------
def test_deadline_expired_before_start_returns_empty_partial(graph):
    with Session(graph, _cfg()) as s:
        r = s.submit(Request("M5-3", DELTA, 1024, seed=0,
                             deadline_s=1e-9)).result()
    assert r.degraded and "deadline" in r.degrade_reason
    assert r.k == 0 and r.estimate == 0.0


def test_deadline_mid_run_partial_bit_identical(graph):
    _est(graph, k=2048)                      # warm the compile caches
    with Session(graph, _cfg()) as s:
        r = s.submit(Request("M5-3", DELTA, 1 << 17, seed=0,
                             deadline_s=0.25)).result()
    assert r.degraded and "deadline" in r.degrade_reason
    assert r.k < (1 << 17)                   # it really was cut short
    assert r.k % CHUNK == 0                  # a whole checkpoint window
    if r.k:
        clean = _est(graph, k=r.k)           # same budget, no deadline
        assert r.estimate == clean.estimate
        assert r.cnt2_sum == clean.cnt2_sum


def test_deadline_mid_adaptive_growth_returns_partial(graph):
    d0 = RSTATS.deadline_degraded
    with Session(graph, _cfg()) as s:
        r = s.submit(Request("M5-3", DELTA, 512, seed=0,
                             target_rse=1e-9, k_max=1 << 30,
                             deadline_s=0.3)).result()
    assert r.degraded and "deadline" in r.degrade_reason
    assert r.k >= 512                        # at least the initial round
    assert r.rse is not None and r.rse > 1e-9    # achieved, not target
    assert RSTATS.deadline_degraded > d0 or "growth stopped" \
        in r.degrade_reason


def test_request_deadline_validation():
    with pytest.raises(ValueError):
        Request("M5-3", DELTA, 512, deadline_s=0.0)


# ---------------------------------------------------------------------------
# WAL: crash-safe streaming store
# ---------------------------------------------------------------------------
_B1 = ([0, 1, 2], [1, 2, 0], [100, 200, 300])
_B2 = ([3, 4], [4, 5], [400, 500])
_B3 = ([5, 6, 0], [6, 0, 5], [600, 700, 800])


def _apply(store, ops):
    for op in ops:
        if op[0] == "ingest":
            store.ingest(*op[1])
        else:
            try:
                store.advance()
            except ValueError:
                pass                         # empty stream: same both sides


def _record_boundaries(path):
    with open(path, "rb") as f:
        data = f.read()
    offs = [len(_HEADER)]
    pos = len(_HEADER)
    while pos + _REC.size <= len(data):
        _, length, _ = _REC.unpack_from(data, pos)
        pos += _REC.size + length
        offs.append(pos)
    return offs, data


def _store_fingerprint(store):
    """Observable state a recovered store must reproduce exactly."""
    return (store.epoch, store.buffered, store.retained,
            store.stats.ingested)


def test_wal_recovery_at_every_truncation_point(tmp_path):
    ops = [("ingest", _B1), ("advance",), ("ingest", _B2),
           ("advance",), ("ingest", _B3)]
    full = str(tmp_path / "full.wal")
    w = StreamStore.recover(full, horizon=10_000)
    _apply(w, ops)
    offs, data = _record_boundaries(full)
    assert len(offs) == len(ops) + 1         # one record per op

    for i, off in enumerate(offs):
        # crash exactly at a record boundary: records 0..i-1 survive
        for extra, label in ((0, "boundary"), (3, "midrecord")):
            if off + extra > len(data):
                continue
            p = str(tmp_path / f"crash_{i}_{label}.wal")
            with open(p, "wb") as f:
                f.write(data[:off + extra])
            rec = StreamStore.recover(p, horizon=10_000)
            ref = StreamStore(horizon=10_000)
            _apply(ref, ops[:i])             # only the acked prefix
            assert _store_fingerprint(rec) == _store_fingerprint(ref), \
                (i, label)
            # the torn tail is physically gone: the file now ends at the
            # last intact record and appends continue from there
            assert os.path.getsize(p) == off
            # drive both one step further: bit-identical next epoch
            rec.ingest([7], [8], [900])
            ref.ingest([7], [8], [900])
            e_rec, e_ref = rec.advance(), ref.advance()
            assert e_rec.index == e_ref.index
            assert e_rec.m_real == e_ref.m_real
            np.testing.assert_array_equal(e_rec.graph.t, e_ref.graph.t)
            np.testing.assert_array_equal(e_rec.graph.src, e_ref.graph.src)
            np.testing.assert_array_equal(e_rec.graph.dst, e_ref.graph.dst)


def test_wal_refuses_foreign_file(tmp_path):
    p = str(tmp_path / "not_a.wal")
    with open(p, "wb") as f:
        f.write(b"something else entirely")
    with pytest.raises(ValueError, match="not a WAL"):
        read_records(p)


def test_wal_fsync_fault_leaves_tail_unmutated(tmp_path):
    s = StreamStore.recover(str(tmp_path / "f.wal"), horizon=None)
    with FaultInjector([FaultSpec("wal.fsync", hits=(0,))]):
        with pytest.raises(TransientError):
            s.ingest(*_B1)
    assert s.buffered == 0                   # write-ahead: store untouched
    assert s.ingest(*_B1) == 3               # next attempt succeeds


def test_wal_recovered_estimates_bit_identical(tmp_path):
    """The full contract through the session layer, both backends: a
    recovered stream serves standing-query numbers identical to the
    uncrashed replica's next epoch."""
    from repro.stream import StandingQuery
    edges = ([i % 11 for i in range(60)],
             [(i + 1) % 11 for i in range(60)],
             [120 * i for i in range(60)])
    for backend in ("xla", "pallas"):
        cfg = EstimateConfig(chunk=CHUNK, seed=0, sampler_backend=backend)
        p = str(tmp_path / f"s_{backend}.wal")
        live = StreamingSession(store=StreamStore.recover(p, horizon=None),
                                config=cfg)
        qid = live.subscribe(StandingQuery("0-1,1-2,2-0", delta=400, k=512))
        live.ingest(*edges)
        er_live = live.advance()

        # "crash": throw the live session away, recover from the WAL
        rec = StreamingSession(store=StreamStore.recover(p, horizon=None),
                               config=cfg)
        rec.subscribe(StandingQuery("0-1,1-2,2-0", delta=400, k=512))
        more = ([5, 6], [7, 8], [7300, 7400])
        live.ingest(*more)
        rec.ingest(*more)
        er2_live, er2_rec = live.advance(), rec.advance()
        assert er2_rec.epoch.index == er2_live.epoch.index
        assert er2_rec.results[qid].estimate == er2_live.results[qid].estimate
        assert er2_rec.results[qid].cnt2_sum == er2_live.results[qid].cnt2_sum
        assert er_live.results[qid].k == 512


# ---------------------------------------------------------------------------
# atomic checkpoint writes
# ---------------------------------------------------------------------------
def test_atomic_write_survives_midwrite_kill(tmp_path):
    p = str(tmp_path / "state.json")
    atomic_write_json(p, {"gen": 1, "acc": [1, 2, 3]})
    with FaultInjector([FaultSpec("checkpoint.write", exc=FatalError)]):
        with pytest.raises(FatalError):
            atomic_write_json(p, {"gen": 2, "acc": [9, 9, 9]})
    # the real path still holds the previous COMPLETE content; the torn
    # half-write stayed confined to the .tmp side of the rename
    assert json.load(open(p)) == {"gen": 1, "acc": [1, 2, 3]}
    assert os.path.exists(p + ".tmp")
    with pytest.raises(json.JSONDecodeError):
        json.load(open(p + ".tmp"))


def test_checkpoint_midwrite_kill_then_resume_bit_identical(graph, tmp_path):
    base = _est(graph)
    p = str(tmp_path / "job.ckpt")
    # die mid-write of the SECOND checkpoint (the first survives intact)
    with FaultInjector([FaultSpec("checkpoint.write", tag=p, hits=(1,),
                                  exc=FatalError)]):
        with pytest.raises(FatalError):
            _est(graph, checkpoint_path=p)
    r = _est(graph, checkpoint_path=p)       # resumes from checkpoint 1
    assert r.estimate == base.estimate and r.cnt2_sum == base.cnt2_sum


def test_torn_checkpoint_file_treated_as_absent(graph, tmp_path):
    base = _est(graph)
    p = str(tmp_path / "torn.ckpt")
    with open(p, "w") as f:
        f.write('{"motif": "M5-3", "delta": 3000, "se')   # torn JSON
    r = _est(graph, checkpoint_path=p)
    assert r.estimate == base.estimate and r.cnt2_sum == base.cnt2_sum


# ---------------------------------------------------------------------------
# serve loop: wire-level degradation + health
# ---------------------------------------------------------------------------
def _serve(resident, lines) -> tuple[int, list[dict]]:
    """Serve ``lines`` through the gateway loop with ``resident`` (a
    Session or StreamingSession) adopted as the default tenant."""
    out = io.StringIO()
    served = gateway_serve_loop(
        adopt=resident, infile=io.StringIO("\n".join(lines) + "\n"),
        outfile=out)
    return served, [json.loads(ln) for ln in out.getvalue().splitlines()]


def test_serve_deadline_ms_degraded_partial(graph):
    lines = [json.dumps(dict(id=1, motif="M5-3", delta=DELTA, k=1 << 17,
                             deadline_ms=150)),
             json.dumps(dict(cmd="quit"))]
    with Session(graph, _cfg()) as s:
        _, resp = _serve(s, lines)
    r = next(x for x in resp if x.get("id") == 1)
    assert r["ok"] and r["degraded"]         # degraded partial, NOT error
    assert r["k_done"] == r["k"] and r["k"] % CHUNK == 0
    assert "deadline" in r["degrade_reason"]


def test_serve_stream_health_reports_wal_and_epoch(tmp_path):
    store = StreamStore.recover(str(tmp_path / "h.wal"), horizon=None)
    cfg = EstimateConfig(chunk=CHUNK, seed=0)
    # health answers inline: the second probe runs in a second loop, so
    # the first loop's quit has waited for the advance to finish
    with StreamingSession(store=store, config=cfg) as ss:
        _, resp = _serve(ss, [
            json.dumps(dict(cmd="health")),
            json.dumps(dict(cmd="subscribe", motif="0-1,1-2,2-0",
                            delta=400, k=512)),
            json.dumps(dict(cmd="ingest",
                            edges=[[i % 7, (i + 1) % 7, 100 * i]
                                   for i in range(40)])),
            json.dumps(dict(cmd="advance")),
            json.dumps(dict(cmd="quit"))])
        _, resp2 = _serve(ss, [json.dumps(dict(cmd="health")),
                               json.dumps(dict(cmd="quit"))])
    health = [r for r in resp + resp2 if r.get("cmd") == "health"]
    assert len(health) == 2
    tenant = [h["tenants"]["default"] for h in health]
    assert tenant[0]["mode"] == "stream" and tenant[0]["epoch"] == 0
    assert tenant[0]["wal"]["records"] == 0
    assert tenant[1]["epoch"] == 1
    assert tenant[1]["wal"]["records"] == 2      # ingest + advance logged
    assert tenant[1]["wal"]["offset"] > tenant[0]["wal"]["offset"]
    assert health[1]["resilience"]["wal_records"] >= 2
    adv = next(r for r in resp if r.get("cmd") == "advance")
    assert adv["ok"] and adv["queries"] == 1


def test_serve_write_fault_counted_server_survives(graph):
    # stats answers inline at intake, before the request is even queued,
    # so it is the first write and the one the fault takes
    lines = [json.dumps(dict(cmd="stats")),
             json.dumps(dict(id=1, motif="M5-3", delta=DELTA, k=512)),
             json.dumps(dict(cmd="quit"))]
    e0 = RSTATS.emit_failures
    with Session(graph, _cfg()) as s:
        with FaultInjector([FaultSpec("serve.write", hits=(0,))]):
            served, resp = _serve(s, lines)
    assert served == 1
    assert RSTATS.emit_failures == e0 + 1
    # the first response was lost to the injected write fault, but the
    # server kept serving: the request + quit still answered
    assert not any(r.get("cmd") == "stats" for r in resp)
    assert next(r for r in resp if r.get("id") == 1)["ok"]
    assert any(r.get("cmd") == "quit" for r in resp)
