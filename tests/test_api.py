"""Session-based public API (repro.api): coalescing windows, adaptive
budgets, streaming, serving a session through the gateway loop, the
motif DSL — and the shim contract.

The load-bearing assertions:

* ``estimate()``/``estimate_many()`` are thin shims over a one-shot
  ``Session`` and must be **bit-identical to the pinned golden values**
  (``golden_estimates.json``) on a fixed graph, for BOTH sampler
  backends — and those values must agree with the exact count.
* N concurrent ``submit()``s coalesce into the fused engine plan (one
  dispatch per job-cohort per window, pinned via ``engine.STATS``) and
  return bit-identical results to sequential ``estimate()``.
* ``target_rse`` requests grow ``k`` geometrically, RESUME instead of
  resampling (final result bit-identical to a one-shot run at the final
  budget), stop growing once the target is met, and cap at ``k_max``.
"""
from __future__ import annotations

import io
import json
import math
import time
from pathlib import Path

import pytest

from repro.api import EstimateConfig, Request, Session
from repro.core import engine
from repro.core.estimator import estimate
from repro.core.motif import (TemporalMotif, get_motif, is_motif_spec,
                              motif_spec, parse_motif_spec)
from repro.gateway import gateway_serve_loop
from repro.graphs import powerlaw_temporal_graph

DELTA = 3_000
CHUNK = 256
CKPT_EVERY = 2

# Golden outputs of estimate() on powerlaw(n=150, m=2000, span=40000,
# seed=11) with chunk=256, checkpoint_every=2 — identical for both sampler
# backends.  Captured under jax 0.9, whose default
# ``jax_threefry_partitionable=True`` changed the random bit stream the
# sampler draws from: the values first pinned under jax 0.4.37 (same W,
# other draws) no longer hold there, and no estimator code changed.
# chip_smoke.py holds the served path on the chip to the same file, and
# test_goldens_agree_with_exact keeps a re-pin from hiding a real bug.
_GOLDEN_FILE = json.loads(
    (Path(__file__).parent / "golden_estimates.json").read_text())
GOLDEN = {(r["motif"], r["delta"], r["k"], r["seed"]):
          dict(estimate=r["estimate"], cnt2=r["cnt2"], valid=r["valid"],
               W=r["W"])
          for r in _GOLDEN_FILE["requests"]}


@pytest.fixture(scope="module")
def graph():
    return powerlaw_temporal_graph(n=150, m=2_000, time_span=40_000, seed=11)


def _cfg(**kw):
    base = dict(chunk=CHUNK, checkpoint_every=CKPT_EVERY,
                coalesce_window_s=60.0)
    base.update(kw)
    return EstimateConfig(**base)


# ---------------------------------------------------------------------------
# shim contract
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_estimate_shim_bit_identical_to_pre_redesign(graph, backend):
    for (mn, d, k, seed), want in GOLDEN.items():
        r = estimate(graph, get_motif(mn), d, k, seed=seed, chunk=CHUNK,
                     checkpoint_every=CKPT_EVERY, sampler_backend=backend)
        assert r.estimate == want["estimate"]
        assert r.cnt2_sum == want["cnt2"]
        assert r.valid == want["valid"]
        assert r.W == want["W"]
        assert r.sampler_backend == backend


def test_goldens_agree_with_exact(graph):
    """Each golden estimate, and a 64x larger budget of the same request
    on the same path, lies within 3 standard errors of
    ``core.exact.count_exact``.  The standard error treats the ``cnt2 /
    2`` matched samples as a Poisson count around the exact count:
    ``exact / sqrt(cnt2 / 2)``.  At the golden budget that bound is
    loose (M5-3 has 10 matches: +-95%); the large budget holds the code
    that made the goldens to about +-11% (M5-3) and +-2% (M4-2), so a
    re-pin that a biased estimator produced cannot pass."""
    from repro.core.exact import count_exact
    assert _GOLDEN_FILE["graph"] == \
        "powerlaw:n=150,m=2000,time_span=40000,seed=11"
    assert (_GOLDEN_FILE["chunk"], _GOLDEN_FILE["checkpoint_every"]) == \
        (CHUNK, CKPT_EVERY)
    for (mn, d, k, seed), want in GOLDEN.items():
        exact = count_exact(graph, get_motif(mn), d)
        big = estimate(graph, get_motif(mn), d, 64 * k, seed=seed,
                       chunk=CHUNK, checkpoint_every=CKPT_EVERY)
        for est, cnt2 in ((want["estimate"], want["cnt2"]),
                          (big.estimate, big.cnt2_sum)):
            se = exact / math.sqrt(cnt2 / 2)
            assert abs(est - exact) <= 3 * se, (mn, exact, est, cnt2)


def test_session_submit_matches_estimate_shim(graph):
    """The session path IS the estimate path: same numbers end to end."""
    with Session(graph, _cfg()) as s:
        h = s.submit(Request("M5-3", DELTA, 1024, seed=0))
        r = h.result()
    want = GOLDEN[("M5-3", DELTA, 1024, 0)]
    assert r.estimate == want["estimate"] and r.cnt2_sum == want["cnt2"]


# ---------------------------------------------------------------------------
# request coalescing
# ---------------------------------------------------------------------------
def test_coalesced_submits_bit_identical_and_dispatches_pinned(graph):
    """6 concurrent submits == 6 sequential estimate() calls, with the
    FUSED plan's dispatch count (engine.STATS), not the per-job loop's."""
    reqs = [(mn, k) for mn in ("M5-3", "M4-2") for k in (512, 1024, 2048)]
    engine.STATS.reset()
    with Session(graph, _cfg()) as s:
        handles = [s.submit(Request(mn, DELTA, k, seed=0))
                   for mn, k in reqs]
        results = [h.result() for h in handles]
    # per (tree, delta) group: budgets span 2/4/8 chunks -> windows
    # [0,2) x3 jobs, [2,4) x2, [4,6) x1, [6,8) x1 = 4 dispatches (2 fused)
    assert engine.STATS.dispatches == 2 * 4
    assert engine.STATS.fused_dispatches == 2 * 2
    assert engine.STATS.job_windows == 2 * 7
    assert s.stats.drains == 1 and s.stats.dispatches == 8

    engine.STATS.reset()
    for (mn, k), rb in zip(reqs, results):
        rs = estimate(graph, get_motif(mn), DELTA, k, seed=0, chunk=CHUNK,
                      checkpoint_every=CKPT_EVERY)
        assert rb.estimate == rs.estimate
        assert rb.cnt2_sum == rs.cnt2_sum
        assert rb.valid == rs.valid
        assert rb.tree_edges == rs.tree_edges
        assert rb.fused_jobs == 3 and rs.fused_jobs == 1
    assert engine.STATS.dispatches == engine.STATS.job_windows == 14


def test_count_closed_window_drains_on_submit(graph):
    with Session(graph, _cfg(coalesce_max_requests=2)) as s:
        h1 = s.submit(Request("M5-3", DELTA, 512, seed=0))
        assert not h1.done                       # window still open
        h2 = s.submit(Request("M5-3", DELTA, 512, seed=1))
        assert h1.done and h2.done               # count-closed: drained
        assert s.stats.drains == 1


def test_time_closed_window_drains_next_submit(graph):
    with Session(graph, _cfg(coalesce_window_s=0.0)) as s:
        h1 = s.submit(Request("M5-3", DELTA, 512, seed=0))
        assert not h1.done
        h2 = s.submit(Request("M5-3", DELTA, 512, seed=1))   # expires window
        assert h1.done and not h2.done
        assert h2.result().cnt2_sum >= 0 and h2.done


def test_window_clock_resets_after_time_closed_flush(graph):
    """A window opened right after a time-closed drain must start with a
    FRESH clock (not the pre-flush timestamp), so back-to-back submits
    after a drain still coalesce."""
    with Session(graph, _cfg(coalesce_window_s=0.2)) as s:
        s.submit(Request("M5-3", DELTA, 512, seed=0))
        time.sleep(0.25)
        h2 = s.submit(Request("M5-3", DELTA, 512, seed=1))  # time-closes r1
        assert not h2.done                 # ...but h2 itself stays queued
        age = s.window_age()
        assert age is not None and age < 0.2   # not backdated by the drain
        h3 = s.submit(Request("M5-3", DELTA, 512, seed=2))
        assert not h3.done
        assert h2.result().fused_jobs == 2     # h2+h3 fused in one plan


def test_preprocess_cache_survives_across_windows(graph, no_retrace):
    """A warm session re-serves (tree, delta) plans without re-preprocess
    — and without recompiling the window program."""
    with Session(graph, _cfg()) as s:
        s.submit(Request("M5-3", DELTA, 512, seed=0)).result()
        calls = s.planner.preprocess_calls
        assert calls > 0
        s.submit(Request("M5-3", DELTA, 2048, seed=5)).result()
        assert s.planner.preprocess_calls == calls   # plan-cache hit
        # same plan shape again: warm end to end, zero retraces
        with no_retrace() as probe:
            s.submit(Request("M5-3", DELTA, 512, seed=7)).result()
        assert probe.dispatches > 0


# ---------------------------------------------------------------------------
# adaptive budgets
# ---------------------------------------------------------------------------
def test_adaptive_budget_grows_then_stops_at_target(graph):
    """k grows geometrically until the empirical RSE crosses the target,
    then STOPS — and the result is bit-identical to a one-shot run with
    the final budget (growth resumes, never resamples)."""
    with Session(graph, _cfg()) as s:
        h = s.submit(Request("M4-2", DELTA, 512, seed=3, target_rse=0.2,
                             k_max=1 << 20))
        r = h.result()
    assert r.k > 512                      # grew at least once
    assert r.k < 1 << 20                  # stopped well before the cap
    assert h.rse <= 0.2 and r.rse == h.rse
    ref = estimate(graph, get_motif("M4-2"), DELTA, r.k, seed=3,
                   chunk=CHUNK, checkpoint_every=CKPT_EVERY)
    assert r.cnt2_sum == ref.cnt2_sum and r.estimate == ref.estimate


def test_adaptive_budget_capped_at_k_max(graph):
    with Session(graph, _cfg()) as s:
        h = s.submit(Request("M4-2", DELTA, 512, seed=3, target_rse=1e-7,
                             k_max=2048))
        r = h.result()
    assert r.k == 2048                    # ran to the cap...
    assert h.rse > 1e-7                   # ...without meeting the target
    ref = estimate(graph, get_motif("M4-2"), DELTA, 2048, seed=3,
                   chunk=CHUNK, checkpoint_every=CKPT_EVERY)
    assert r.cnt2_sum == ref.cnt2_sum and r.estimate == ref.estimate


def test_adaptive_already_met_target_no_growth(graph):
    """A run whose first round already meets the target never grows."""
    with Session(graph, _cfg()) as s:
        h = s.submit(Request("M4-2", DELTA, 1024, seed=3, target_rse=0.9))
        r = h.result()
    assert r.k == 1024 and s.stats.adaptive_rounds == 0


# ---------------------------------------------------------------------------
# streaming
# ---------------------------------------------------------------------------
def test_stream_yields_per_window_progressive_estimates(graph):
    with Session(graph, _cfg()) as s:
        h = s.submit(Request("M5-3", DELTA, 2048, seed=0))
        snaps = list(h.stream())
    res = h.result()
    assert len(snaps) == 4                # 8 chunks / checkpoint_every=2
    assert [p.k_done for p in snaps] == [512, 1024, 1536, 2048]
    assert snaps[-1].estimate == res.estimate
    assert snaps[-1].cnt2_sum == res.cnt2_sum
    assert all(b.k_done > a.k_done for a, b in zip(snaps, snaps[1:]))
    assert math.isinf(snaps[0].rse)       # < 2 windows: no batch means yet
    assert snaps[-1].rse == h.rse


# ---------------------------------------------------------------------------
# motif edge-list DSL
# ---------------------------------------------------------------------------
def test_motif_dsl_roundtrip():
    m = get_motif("0-1,1-2,2-0")
    assert isinstance(m, TemporalMotif)
    assert m.edges == ((0, 1), (1, 2), (2, 0))
    assert m.num_vertices == 3
    # round trip: serialize -> parse -> identical structure + name
    spec = motif_spec(m)
    assert spec == "0-1,1-2,2-0"
    m2 = parse_motif_spec(spec)
    assert m2.edges == m.edges and m2.num_vertices == m.num_vertices
    assert m2.name == spec
    # every catalog motif round-trips through the DSL too
    for name in ("M5-3", "diamond", "edge2"):
        cat = get_motif(name)
        via = parse_motif_spec(motif_spec(cat))
        assert via.edges == cat.edges
        assert via.num_vertices == cat.num_vertices


def test_motif_dsl_catalog_precedence_and_validation():
    assert get_motif("M5-3").name == "M5-3"     # catalog names never parse
    assert not is_motif_spec("M5-3") and not is_motif_spec("scatter-gather")
    assert is_motif_spec("0-1 , 1-2")           # whitespace tolerated
    with pytest.raises(KeyError):
        get_motif("not-a-motif")
    with pytest.raises(ValueError):
        parse_motif_spec("M5-3")
    with pytest.raises(ValueError):             # self-loop
        get_motif("0-0,0-1")
    with pytest.raises(ValueError):             # vertex 2 skipped: isolated 1?
        get_motif("0-1,3-0")                    # ids must be dense 0..n-1


def test_motif_dsl_estimates_match_catalog(graph):
    """An inline spec structurally equal to a catalog motif estimates
    bit-identically (same trees, same weights, same draws)."""
    spec = motif_spec(get_motif("triangle"))
    r_cat = estimate(graph, get_motif("triangle"), DELTA, 512, seed=0,
                     chunk=CHUNK, checkpoint_every=CKPT_EVERY)
    r_dsl = estimate(graph, get_motif(spec), DELTA, 512, seed=0,
                     chunk=CHUNK, checkpoint_every=CKPT_EVERY)
    assert r_dsl.cnt2_sum == r_cat.cnt2_sum
    assert r_dsl.estimate == r_cat.estimate
    assert r_dsl.motif == spec and r_cat.motif == "triangle"


# ---------------------------------------------------------------------------
# a session served through the gateway loop as its default tenant
# (in-process; scripts/ci.sh smoke-tests the real subprocess)
# ---------------------------------------------------------------------------
def _serve(session, lines) -> tuple[int, list[dict]]:
    """Serve ``lines`` through the gateway loop with ``session`` adopted
    as the default tenant; returns (served, parsed response lines)."""
    out = io.StringIO()
    served = gateway_serve_loop(
        adopt=session, infile=io.StringIO("\n".join(lines) + "\n"),
        outfile=out)
    return served, [json.loads(ln) for ln in out.getvalue().splitlines()]


def test_serve_loop_roundtrip(graph):
    lines = [
        json.dumps(dict(id=1, motif="M5-3", delta=DELTA, k=1024)),
        json.dumps(dict(id=2, motif="0-1,1-2,2-0", delta=DELTA, k=512)),
        json.dumps(dict(id=3, motif="no-such", delta=DELTA, k=256)),
        json.dumps(dict(id=4, motif="M4-2", delta=DELTA, k=512, seed=3,
                        target_rse=0.2, k_max=4096)),
        json.dumps(dict(cmd="stats")),
        json.dumps(dict(cmd="quit")),
    ]
    with Session(graph, _cfg()) as s:
        served, resp = _serve(s, lines)
        # the loop leaves an adopted session open: its counters after
        # the quit barrier are the whole run's
        assert s.stats.completed == 3 and s.stats.submitted == 3
    by_id = {r["id"]: r for r in resp if "id" in r}
    assert served == 3
    want = GOLDEN[("M5-3", DELTA, 1024, 0)]
    assert by_id[1]["ok"] and by_id[1]["estimate"] == want["estimate"]
    assert by_id[1]["valid"] == want["valid"]
    assert by_id[1]["tenant"] == "default"
    assert by_id[2]["ok"] and by_id[2]["motif"] == "0-1,1-2,2-0"
    assert not by_id[3]["ok"] and "no-such" in by_id[3]["error"]
    assert by_id[4]["ok"] and by_id[4]["k"] > 512   # adaptive growth ran
    assert by_id[4]["rse"] <= 0.2
    # stats answers inline, without waiting on the dispatcher
    stats = next(r for r in resp if r.get("cmd") == "stats")
    assert stats["ok"] and stats["tenants"]["default"]["mode"] == "graph"
    quit_r = next(r for r in resp if r.get("cmd") == "quit")
    assert quit_r["served"] == 3


def test_serve_loop_malformed_json_keeps_serving(graph):
    # blank lines and bad JSON must not kill the server (a blank line is
    # NOT EOF), and invalid request fields answer ok:false per line
    lines = ["{nope", "", json.dumps(dict(id=7, motif="M5-3", delta=DELTA,
                                          k=0)),
             json.dumps(dict(motif="M5-3", delta=DELTA, k=512))]
    with Session(graph, _cfg()) as s:
        served, resp = _serve(s, lines)
    assert served == 1
    assert not resp[0]["ok"] and "bad json" in resp[0]["error"]
    assert not resp[1]["ok"] and resp[1]["id"] == 7      # k=0 rejected
    assert resp[2]["ok"] and resp[2]["k"] == 512


def test_serve_loop_rejects_unknown_fields(graph):
    """The wire protocol must not accept fields it does not understand —
    in particular ``checkpoint`` (server-side file paths) stays
    CLI/library-only."""
    lines = [json.dumps(dict(id=1, motif="M5-3", delta=DELTA, k=512,
                             checkpoint="/tmp/evil.ckpt")),
             json.dumps(dict(id=2, motif="M5-3", delta=DELTA, k=512))]
    with Session(graph, _cfg()) as s:
        served, resp = _serve(s, lines)
    assert served == 1
    assert not resp[0]["ok"] and "checkpoint" in resp[0]["error"]
    assert resp[1]["ok"]


def test_drain_failure_marks_window_mates_and_session_survives(graph,
                                                               tmp_path):
    """An execution failure mid-drain fails every handle of the window
    with the cause (no bare assert), and the session keeps serving.
    Through the gateway loop the same failure answers each request with
    a structured ``error_kind`` and the SERVER also stays up."""
    s = Session(graph, _cfg())
    good = s.submit(Request("M5-3", DELTA, 512, seed=0))
    bad = s.submit(Request("M5-3", DELTA, 512, seed=1,
                           checkpoint_path=str(tmp_path / "no" / "dir.ckpt")))
    with pytest.raises(FileNotFoundError):
        s.flush()
    for h in (good, bad):
        assert h.done
        with pytest.raises(RuntimeError, match="failed during session"):
            h.result()
    # the session itself is still healthy
    r = s.submit(Request("M5-3", DELTA, 1024, seed=0)).result()
    assert r.cnt2_sum == GOLDEN[("M5-3", DELTA, 1024, 0)]["cnt2"]

    # serve level: a fatally failing drain answers ok:false with the
    # taxonomy kind, then the SAME adopted session answers the next
    # request (and a health probe) normally.  The second request runs
    # through a second loop so the gateway cannot fuse it into the
    # failing window (a failed drain marks every window-mate).
    from repro.resilience import FatalError, FaultInjector, FaultSpec
    from repro.resilience.retry import STATS as RSTATS
    drain_failures0 = RSTATS.drain_failures
    with FaultInjector([FaultSpec("engine.dispatch", hits=(0,),
                                  exc=FatalError)]):
        served1, resp = _serve(s, [
            json.dumps(dict(id=1, motif="M5-3", delta=DELTA, k=512)),
            json.dumps(dict(cmd="health")),   # answered WITHOUT draining
            json.dumps(dict(cmd="quit"))])
        served2, resp2 = _serve(s, [
            json.dumps(dict(id=2, motif="M5-3", delta=DELTA, k=1024)),
            json.dumps(dict(cmd="quit"))])
    by_id = {r["id"]: r for r in resp + resp2 if "id" in r}
    assert served1 == 0 and served2 == 1     # a failed handle is no serve
    assert not by_id[1]["ok"] and by_id[1]["error_kind"] == "fatal"
    assert by_id[2]["ok"]
    assert by_id[2]["valid"] == GOLDEN[("M5-3", DELTA, 1024, 0)]["valid"]
    health = next(r for r in resp if r.get("cmd") == "health")
    assert health["ok"] and health["mode"] == "gateway"
    assert health["tenants"]["default"]["mode"] == "graph"
    assert "resilience" in health
    assert RSTATS.drain_failures == drain_failures0 + 1
    s.close()


def test_request_validation():
    with pytest.raises(ValueError):
        Request("M5-3", DELTA, 0)                        # k < 1
    with pytest.raises(ValueError):
        Request("M5-3", -1, 512)                         # negative delta
    with pytest.raises(ValueError):
        Request("M5-3", DELTA, 512, target_rse=0.0)      # non-positive rse
    with pytest.raises(ValueError):
        Request("M5-3", DELTA, 512, k_max=256)           # k_max < k


# ---------------------------------------------------------------------------
# config / env resolution
# ---------------------------------------------------------------------------
def test_config_resolves_env_once(monkeypatch):
    monkeypatch.setenv("REPRO_SAMPLER_BACKEND", "pallas")
    monkeypatch.setenv("REPRO_DEPSUM_BACKEND", "pallas")
    cfg = EstimateConfig().resolve()
    assert cfg.sampler_backend == "pallas"
    assert cfg.depsum_backend == "pallas"
    # explicit values beat the environment
    cfg2 = EstimateConfig(sampler_backend="xla",
                          depsum_backend="xla").resolve()
    assert cfg2.sampler_backend == "xla" and cfg2.depsum_backend == "xla"
    # resolve() validates
    monkeypatch.setenv("REPRO_SAMPLER_BACKEND", "cuda")
    with pytest.raises(ValueError):
        EstimateConfig().resolve()
    # frozen: configs are immutable values
    with pytest.raises(Exception):
        cfg.chunk = 1


def test_session_closed_rejects_submits(graph):
    s = Session(graph, _cfg())
    s.close()
    with pytest.raises(RuntimeError):
        s.submit(Request("M5-3", DELTA, 256))
