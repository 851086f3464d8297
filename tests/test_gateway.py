"""Gateway subsystem (repro.gateway): tenancy, overlap, witnesses.

The load-bearing assertions:

* **Interleaving bit-identity**: per-request counts from a two-tenant
  interleaved gateway run are bit-identical to solo synchronous
  ``estimate()`` runs at the same seed/budget, for both sampler
  backends — the gateway decides WHEN work runs, never what it draws.
* **Backpressure**: a tenant past its pending quota is shed at enqueue
  with the structured ``overloaded`` taxonomy kind, never stalled.
* **Tenancy**: idle-LRU eviction at pool capacity (busy tenants are
  never victims), reopen after eviction, per-tenant WAL recovery.
* **Witness reservoir determinism**: same seed -> same witnesses,
  across repeated runs, submission interleavings and mesh shapes; the
  count is bit-identical with witnesses on or off; ``witnesses=0``
  dispatches no witness programs at all.
* **Warm path**: tenant N+1 on same-bucket snapshots re-hits tenant N's
  compiled window programs (``no_retrace``).
"""
from __future__ import annotations

import io
import json
import os
import subprocess
import sys
import textwrap
import threading

import numpy as np
import pytest

from repro.api import EstimateConfig, Request, Session
from repro.core import engine
from repro.core.estimator import estimate
from repro.core.motif import get_motif
from repro.gateway import FairScheduler, GatewayState, Work, \
    gateway_serve_loop
from repro.gateway.io import LineSource
from repro.resilience import OVERLOADED, OverloadedError, classify, \
    error_payload
from repro.stream import StandingQuery

CHUNK = 64
DELTA = 2_500

FIN_SPEC = "fintxn:n_accounts=80,m=1600,time_span=50000,seed=3"
SOC_SPEC = "powerlaw:n=120,m=2400,time_span=60000,seed=5"


def _cfg(**kw):
    base = dict(chunk=CHUNK, checkpoint_every=2, coalesce_window_s=60.0)
    base.update(kw)
    return EstimateConfig(**base)


def _graph(spec):
    from repro.launch.estimate import parse_graph
    return parse_graph(spec)


def run_gateway(lines, config=None, **kw):
    out = io.StringIO()
    served = gateway_serve_loop(
        config or _cfg(), infile=io.StringIO("\n".join(lines) + "\n"),
        outfile=out, **kw)
    return served, [json.loads(ln) for ln in out.getvalue().splitlines()]


def by_id(responses, rid):
    found = [o for o in responses
             if o.get("id") == rid and not o.get("progress")]
    assert len(found) == 1, (rid, responses)
    return found[0]


# ---------------------------------------------------------------------------
# interleaving bit-identity
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_two_tenant_interleaving_bit_identity(backend):
    """Interleaved two-tenant wire counts == solo synchronous estimates."""
    jobs = [  # (rid, tenant, motif, delta, k, seed) — tenants alternate
        (1, "fin", "M4-2", DELTA, 512, 0),
        (2, "soc", "M4-2", DELTA, 512, 0),
        (3, "fin", "0-1,1-2", 1_500, 256, 7),
        (4, "soc", "M5-3", 4_000, 512, 1),
        (5, "fin", "M4-2", DELTA, 512, 3),
        (6, "soc", "0-1,1-2", 1_500, 256, 7),
    ]
    lines = [
        json.dumps({"cmd": "open_tenant", "tenant": "fin",
                    "graph": FIN_SPEC}),
        json.dumps({"cmd": "open_tenant", "tenant": "soc",
                    "graph": SOC_SPEC}),
    ] + [json.dumps({"tenant": t, "id": rid, "motif": m, "delta": d,
                     "k": k, "seed": s}) for rid, t, m, d, k, s in jobs] \
      + ['{"cmd": "quit"}']
    served, resp = run_gateway(lines,
                               _cfg(sampler_backend=backend))
    assert served == len(jobs)
    graphs = {"fin": _graph(FIN_SPEC), "soc": _graph(SOC_SPEC)}
    for rid, t, m, d, k, s in jobs:
        r = by_id(resp, rid)
        assert r["ok"] is True and r["tenant"] == t
        solo = estimate(graphs[t], get_motif(m), d, k, seed=s, chunk=CHUNK,
                        checkpoint_every=2, sampler_backend=backend)
        assert r["estimate"] == solo.estimate, (rid, m)
        assert r["valid"] == solo.valid and r["W"] == solo.W


# ---------------------------------------------------------------------------
# backpressure
# ---------------------------------------------------------------------------
def test_quota_sheds_with_overloaded():
    """Submits past the per-tenant quota shed at ENQUEUE with the
    overloaded kind; other tenants keep enqueueing."""
    started, release = threading.Event(), threading.Event()

    def execute(unit):
        started.set()
        release.wait(30)

    sched = FairScheduler(execute, quota=2)
    try:
        # pin the dispatcher on another tenant so the quota fills
        sched.submit("other", Work("request", {}, "other"))
        assert started.wait(30)
        sched.submit("t", Work("request", {"id": 1}, "t"))
        sched.submit("t", Work("request", {"id": 2}, "t"))
        with pytest.raises(OverloadedError) as ei:
            sched.submit("t", Work("request", {"id": 3}, "t"))
        assert classify(ei.value) == OVERLOADED
        assert error_payload(ei.value)["error_kind"] == "overloaded"
        assert sched.stats.shed == 1
        assert sched.pending("t") == 2
        # a different tenant still has quota headroom
        sched.submit("u", Work("request", {"id": 4}, "u"))
    finally:
        release.set()
        sched.stop()
    assert sched.pending("t") == 0          # drained at stop


def test_wire_overloaded_payload():
    """The wire encoding a shed request answers with (PR-7 taxonomy)."""
    p = error_payload(OverloadedError("tenant 'x' has 16 pending"))
    assert p["error_kind"] == OVERLOADED
    assert "pending" in p["error"]


# ---------------------------------------------------------------------------
# tenancy: LRU eviction + reopen
# ---------------------------------------------------------------------------
def test_idle_lru_eviction_and_reopen():
    state = GatewayState(_cfg(), max_tenants=2)
    state.open_tenant("a", graph="er:n=40,m=400,time_span=9000,seed=1")
    state.open_tenant("b", graph="er:n=40,m=400,time_span=9000,seed=2")
    state.tenants["a"].last_active = 0.0    # oldest idle tenant
    state.open_tenant("c", graph="er:n=40,m=400,time_span=9000,seed=3")
    assert set(state.tenants) == {"b", "c"} and state.evictions == 1

    # busy tenants are never victims: with b busy, c (idle) is evicted
    state.pending_of = lambda name: 1 if name == "b" else 0
    state.tenants["b"].last_active = 0.0
    state.open_tenant("a", graph="er:n=40,m=400,time_span=9000,seed=1")
    assert set(state.tenants) == {"b", "a"} and state.evictions == 2

    # everything busy -> the open itself sheds (overloaded)
    state.pending_of = lambda name: 1
    with pytest.raises(OverloadedError):
        state.open_tenant("d", graph="er:n=40,m=400,time_span=9000,seed=4")
    state.pending_of = lambda name: 0
    state.close_all()
    assert not state.tenants


def test_tenant_name_and_spec_validation(tmp_path):
    state = GatewayState(_cfg(), max_tenants=2)
    for bad in ("", "../etc", "a/b", ".hidden", "x" * 65, 7, None):
        with pytest.raises(ValueError):
            state.open_tenant(bad, stream=True)
    # graph tenants accept synthetic specs only — no server file reads
    with pytest.raises(ValueError, match="synthetic"):
        state.open_tenant("f", graph=str(tmp_path / "edges.txt"))
    # wal needs a server-side wal_dir
    with pytest.raises(ValueError, match="wal-dir"):
        state.open_tenant("s", stream=True, wal=True)
    state.close_all()


def test_per_tenant_wal_recovery_over_wire(tmp_path):
    """A WAL stream tenant closed (or evicted) and reopened resumes its
    stream bit-identically — per-tenant WAL paths derive server-side."""
    rng = np.random.default_rng(0)
    edges = [[int(a), int(b), int(t)] for a, b, t in zip(
        rng.integers(0, 50, 600), rng.integers(0, 50, 600),
        np.sort(rng.integers(0, 20_000, 600)))]
    open_line = json.dumps({"cmd": "open_tenant", "tenant": "s",
                            "stream": True, "wal": True})
    sub = json.dumps({"cmd": "subscribe", "tenant": "s", "motif": "0-1,1-2",
                      "delta": 1_500, "k": 256})
    served, resp = run_gateway(
        [open_line, sub,
         json.dumps({"cmd": "ingest", "tenant": "s", "edges": edges}),
         '{"cmd": "advance", "tenant": "s"}',
         '{"cmd": "close_tenant", "tenant": "s"}', '{"cmd": "quit"}'],
        wal_dir=str(tmp_path))
    first = [o for o in resp if o.get("sub") == 0 and "estimate" in o]
    assert len(first) == 1 and first[0]["ok"]
    assert os.path.exists(tmp_path / "s.wal")

    # second process: same tenant name recovers epoch + history from WAL
    served2, resp2 = run_gateway(
        [open_line, sub,
         json.dumps({"cmd": "ingest", "tenant": "s", "edges": edges}),
         '{"cmd": "advance", "tenant": "s"}', '{"cmd": "quit"}'],
        wal_dir=str(tmp_path))
    opened = [o for o in resp2 if o.get("cmd") == "open_tenant"][0]
    assert opened["ok"] and opened["recovered"] and opened["epoch"] == 1
    second = [o for o in resp2 if o.get("sub") == 0 and "estimate" in o]
    assert len(second) == 1 and second[0]["ok"]
    assert second[0]["epoch"] == 1


# ---------------------------------------------------------------------------
# health / stats per-tenant blocks
# ---------------------------------------------------------------------------
def test_health_and_stats_grow_per_tenant_blocks():
    lines = [
        json.dumps({"cmd": "open_tenant", "tenant": "fin",
                    "graph": FIN_SPEC}),
        json.dumps({"cmd": "open_tenant", "tenant": "s", "stream": True}),
        json.dumps({"tenant": "fin", "id": 1, "motif": "M4-2",
                    "delta": DELTA, "k": 256}),
        '{"cmd": "quit"}',
    ]
    out = io.StringIO()
    # drive by hand so health lands after the drain deterministically
    from repro.gateway.serve import _Gateway
    gw = _Gateway(_cfg(), out, max_tenants=4, quota=16, wal_dir=None,
                  mesh=None)
    try:
        for ln in lines[:-1]:
            obj = json.loads(ln)
            if obj.get("cmd") == "open_tenant":
                gw.sched.submit_control(Work("open_tenant", obj))
            else:
                gw.sched.submit(obj["tenant"],
                                Work("request", obj, obj["tenant"]))
        gw.sched.barrier()
        health, stats = gw.health(), gw.stats()
    finally:
        gw.sched.stop()
        gw.state.close_all()
        gw.emitter.close()
    for block in (health, stats):
        assert set(block["tenants"]) == {"fin", "s"}
        fin = block["tenants"]["fin"]
        assert fin["mode"] == "graph" and fin["served"] == 1
        assert fin["pending"] == 0 and fin["errors"] == 0
        assert fin["engine"]["dispatches"] >= 1     # per-tenant deltas
        s = block["tenants"]["s"]
        assert s["mode"] == "stream" and s["served"] == 0
        assert s["epoch"] == 0 and s["subscriptions"] == 0
    assert stats["max_tenants"] == 4
    assert health["scheduler"]["quota"] == 16
    # the device the process computes on, as jax reports it
    import jax
    assert health["device"] == dict(
        platform=jax.devices()[0].platform,
        kind=jax.devices()[0].device_kind, count=len(jax.devices()),
        peak_bytes=health["device"]["peak_bytes"])
    opened = [json.loads(ln) for ln in out.getvalue().splitlines()
              if '"open_tenant"' in ln]
    fin_open = next(r for r in opened if r["tenant"] == "fin")
    assert fin_open["n"] > 0 and fin_open["m"] > 0


# ---------------------------------------------------------------------------
# witness reservoir
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def soc_graph():
    return _graph(SOC_SPEC)


def _witnessed(g, n_wit, *, seed=0, k=512, backend=None, mesh=None,
               interleave=False):
    with Session(g, _cfg(sampler_backend=backend), mesh=mesh) as s:
        reqs = [Request("M4-2", delta=DELTA, k=k, seed=seed,
                        witnesses=n_wit)]
        if interleave:   # cohort-mates must not perturb the reservoir
            reqs.append(Request("M4-2", delta=DELTA, k=k, seed=seed + 9))
            reqs.append(Request("0-1,1-2", delta=1_500, k=k, seed=seed))
        handles = s.submit_many(reqs)
        return handles[0].result()


def test_witness_determinism_and_count_identity(soc_graph):
    base = _witnessed(soc_graph, 0)
    assert base.witnesses is None
    r5 = _witnessed(soc_graph, 5)
    assert r5.estimate == base.estimate          # capture never moves bits
    assert r5.valid == base.valid
    assert 1 <= len(r5.witnesses) <= 5           # up to n accepted matches
    again = _witnessed(soc_graph, 5)
    assert again.witnesses == r5.witnesses       # same seed -> same tuples
    fused = _witnessed(soc_graph, 5, interleave=True)
    assert fused.witnesses == r5.witnesses       # cohort-invariant
    assert fused.estimate == base.estimate
    motif = get_motif("M4-2")
    for w in r5.witnesses:                       # real full matches
        ts = [e[2] for e in w["edges"]]
        assert max(ts) - min(ts) <= DELTA
        assert len(w["edges"]) == motif.num_edges and w["cnt"] >= 1


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_witness_backends_agree(soc_graph, backend):
    r = _witnessed(soc_graph, 4, backend=backend)
    r_xla = _witnessed(soc_graph, 4, backend="xla")
    assert r.witnesses == r_xla.witnesses
    assert r.estimate == r_xla.estimate


def test_witnesses_zero_dispatches_nothing(soc_graph):
    engine.STATS.reset()
    _witnessed(soc_graph, 0)
    assert engine.STATS.witness_dispatches == 0
    _witnessed(soc_graph, 3)
    assert engine.STATS.witness_dispatches > 0


def test_witnesses_mesh_shape_invariant(soc_graph):
    """Same witnesses on a 1-device run and an 8-device mesh run."""
    want = _witnessed(soc_graph, 5)
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys, json
        sys.path.insert(0, "src")
        from repro.api import EstimateConfig, Request, Session
        from repro.launch.mesh import make_estimator_mesh
        from repro.launch.estimate import parse_graph
        g = parse_graph({SOC_SPEC!r})
        mesh = make_estimator_mesh()
        assert mesh.shape["data"] == 8
        cfg = EstimateConfig(chunk={CHUNK}, checkpoint_every=2,
                             coalesce_window_s=60.0)
        with Session(g, cfg, mesh=mesh) as s:
            h, = s.submit_many([Request("M4-2", delta={DELTA}, k=512,
                                        seed=0, witnesses=5)])
            res = h.result()
        print(json.dumps(dict(estimate=res.estimate,
                              witnesses=[[list(e) for e in w["edges"]]
                                         for w in res.witnesses])))
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=420,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    got = json.loads(r.stdout.strip().splitlines()[-1])
    assert got["estimate"] == want.estimate
    assert got["witnesses"] == [[list(e) for e in w["edges"]]
                                for w in want.witnesses]


def test_witness_progress_streams_over_wire():
    lines = [
        json.dumps({"cmd": "open_tenant", "tenant": "soc",
                    "graph": SOC_SPEC}),
        json.dumps({"tenant": "soc", "id": 1, "motif": "M4-2",
                    "delta": DELTA, "k": 512, "witnesses": 4}),
        '{"cmd": "quit"}',
    ]
    served, resp = run_gateway(lines)
    prog = [o for o in resp if o.get("progress")]
    final = by_id(resp, 1)
    assert final["ok"] and 1 <= len(final["witnesses"]) <= 4
    # one line per checkpoint window, monotone k_done, reservoir grows
    # toward the final one
    assert len(prog) == final["windows"] >= 2
    assert [p["window"] for p in prog] == list(range(len(prog)))
    assert all(p["k_done"] <= q["k_done"] for p, q in zip(prog, prog[1:]))
    assert prog[-1]["witnesses"] == final["witnesses"]


# ---------------------------------------------------------------------------
# cross-tenant warm path
# ---------------------------------------------------------------------------
def test_cross_tenant_shared_bucket_warm_path(no_retrace):
    """Tenant N+1 whose snapshot pads to the SAME buckets re-hits tenant
    N's compiled window programs: zero retraces on its advance."""

    def batch(seed):
        r = np.random.default_rng(seed)
        return (r.integers(0, 100, 900).astype(np.int64),
                r.integers(0, 100, 900).astype(np.int64),
                np.sort(r.integers(0, 30_000, 900)).astype(np.int64))

    state = GatewayState(_cfg(), max_tenants=4)
    try:
        a = state.open_tenant("a", stream=True)
        a.stream.subscribe(StandingQuery("M4-2", DELTA, 256))
        a.stream.ingest(*batch(1))
        ep_a = a.stream.advance()                  # cold: compiles
        b = state.open_tenant("b", stream=True)
        b.stream.subscribe(StandingQuery("M4-2", DELTA, 256))
        b.stream.ingest(*batch(2))
        with no_retrace() as probe:
            ep_b = b.stream.advance()              # warm: re-hits a's
        assert probe.dispatches > 0
        assert list(ep_a.epoch.buckets) == list(ep_b.epoch.buckets)
        assert ep_b.results[0].estimate > 0
    finally:
        state.close_all()


# ---------------------------------------------------------------------------
# gateway/io: deadline reader + malformed-line isolation
# ---------------------------------------------------------------------------
def test_linesource_expired_deadline_drains_buffered_lines():
    """readline(0) must return a complete line already in the OS buffer
    instead of timing out on it (the extracted-deadline fix)."""
    r, w = os.pipe()
    try:
        os.write(w, b'{"already": "buffered"}\nrest')
        with os.fdopen(r, "rb", buffering=0) as f:
            src = LineSource(f)
            assert src.readline(0) == '{"already": "buffered"}\n'
            assert src.readline(0) is None      # partial line: true timeout
            os.write(w, b'-of-line\n')
            assert src.readline(5) == 'rest-of-line\n'
            os.close(w)
            assert src.readline(1) == ""        # EOF
    finally:
        for fd in (w,):
            try:
                os.close(fd)
            except OSError:
                pass


def test_malformed_line_isolated_from_other_tenants():
    lines = [
        json.dumps({"cmd": "open_tenant", "tenant": "fin",
                    "graph": FIN_SPEC}),
        'this is not json',
        json.dumps({"tenant": "nope", "id": 9, "motif": "M4-2",
                    "delta": DELTA, "k": 256}),
        '[1, 2, 3]',
        json.dumps({"tenant": "fin", "id": 1, "motif": "M4-2",
                    "delta": DELTA, "k": 256}),
        '{"cmd": "quit"}',
    ]
    served, resp = run_gateway(lines)
    bad = [o for o in resp if not o.get("ok")]
    assert len(bad) == 3
    assert sum("bad json" in str(o.get("error")) for o in bad) == 2
    assert sum("must be a JSON object" in str(o.get("error"))
               for o in bad) == 1
    unknown = by_id(resp, 9)
    assert unknown["error_kind"] == "bad_request"
    good = by_id(resp, 1)          # the healthy tenant is untouched
    assert good["ok"] is True and served == 1
