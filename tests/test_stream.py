"""Streaming graph subsystem (repro.stream) + the padding seams.

The load-bearing assertions:

* **Padding is invisible**: estimates on a ``pad_snapshot``-padded graph
  are bit-identical to the unpadded graph's, for both sampler backends
  (pad edges are zero-weight suffixes the samplers can never select).
* **Epoch determinism contract**: every standing query's per-epoch count
  is bit-identical to a cold one-shot ``estimate()`` on that epoch's
  materialized snapshot graph — across compaction and eviction
  boundaries, for both sampler backends.
* **Program reuse**: epochs sharing snapshot buckets re-hit the engine's
  compiled window programs (no retrace on the second epoch).
* Store tier mechanics (tail -> segments -> snapshot, horizon eviction,
  batch-split invariance), the streaming loader, and the serve-loop
  ingest/advance/subscribe round trip.
"""
from __future__ import annotations

import gzip
import io
import json

import numpy as np
import pytest

from repro.api import EstimateConfig, Request
from repro.core import engine
from repro.core.estimator import estimate
from repro.core.graph import pad_bucket, pad_snapshot
from repro.core.motif import get_motif
from repro.gateway import gateway_serve_loop
from repro.graphs import powerlaw_temporal_graph
from repro.graphs.loader import iter_edge_batches, load_edge_list
from repro.stream import (StandingQuery, StreamingSession, StreamStore,
                          replay_edge_list)

CHUNK = 64
DELTA = 2_500
MOTIF = "M4-2"


@pytest.fixture(scope="module")
def graph():
    return powerlaw_temporal_graph(n=120, m=2_400, time_span=60_000, seed=5)


@pytest.fixture(scope="module")
def edges(graph):
    """The module graph replayed as a time-ordered edge stream."""
    order = np.argsort(graph.t, kind="stable")
    return (graph.src[order].astype(np.int64),
            graph.dst[order].astype(np.int64),
            graph.t[order].astype(np.int64))


def _cfg(**kw):
    base = dict(chunk=CHUNK, checkpoint_every=2, coalesce_window_s=60.0)
    base.update(kw)
    return EstimateConfig(**base)


# ---------------------------------------------------------------------------
# padding seam
# ---------------------------------------------------------------------------
def test_pad_bucket():
    assert [pad_bucket(x) for x in (0, 1, 2, 3, 4, 5, 9)] == \
        [1, 1, 2, 4, 4, 8, 16]
    assert pad_bucket(3, floor=16) == 16


def test_pad_snapshot_suffix_invariants(graph):
    g, p = graph, pad_snapshot(graph)
    assert (p.m, p.n, p.num_pairs) == (
        pad_bucket(g.m), pad_bucket(g.n + 2), pad_bucket(g.num_pairs + 1))
    assert (p.m_real, p.n_real, p.p_real) == (g.m, g.n, g.num_pairs)
    assert p.live_m == g.m and g.live_m == g.m
    # real entries keep their exact unpadded positions in every order
    for name in ("src", "dst", "t", "out_edge", "out_t", "in_edge", "in_t",
                 "pair_edge", "pair_t", "pair_id", "rev_pair_id",
                 "pair_pos_out", "pair_pos_in"):
        np.testing.assert_array_equal(getattr(p, name)[:g.m],
                                      getattr(g, name))
    np.testing.assert_array_equal(p.out_ptr[:g.n + 1], g.out_ptr)
    np.testing.assert_array_equal(p.in_ptr[:g.n + 1], g.in_ptr)
    np.testing.assert_array_equal(p.pair_ptr[:g.num_pairs + 1], g.pair_ptr)
    # pad edges: dedicated pad vertices, at the last real timestamp
    assert np.all(p.src[g.m:] == p.n - 2) and np.all(p.dst[g.m:] == p.n - 1)
    assert np.all(p.t[g.m:] == g.t[-1]) and p.time_span == g.time_span
    # rebased real pair keys still answer u*n+v lookups; sentinels don't
    assert np.all(np.diff(p.pair_key[:g.num_pairs]) > 0)
    assert np.all(p.pair_key[g.num_pairs + 1:] >= p.n * p.n)
    k0 = int(g.src[0]) * p.n + int(g.dst[0])
    assert p.pair_key[np.searchsorted(p.pair_key, k0)] == k0
    # device arrays carry the traced mask scalar
    assert int(p.device_arrays()["m_real"]) == g.m
    assert int(g.device_arrays()["m_real"]) == g.m
    with pytest.raises(ValueError):
        pad_snapshot(p)          # no double padding
    with pytest.raises(ValueError):
        pad_snapshot(g, n_bucket=g.n + 1)   # needs 2 pad vertices


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_padded_estimate_bit_identical_to_unpadded(graph, backend):
    p = pad_snapshot(graph)
    for motif, k, seed in ((MOTIF, 512, 0), ("0-1,1-2,2-0", 256, 3)):
        a = estimate(graph, get_motif(motif), DELTA, k, seed=seed,
                     chunk=CHUNK, sampler_backend=backend)
        b = estimate(p, get_motif(motif), DELTA, k, seed=seed,
                     chunk=CHUNK, sampler_backend=backend)
        assert a.estimate == b.estimate
        assert (a.W, a.cnt2_sum, a.valid) == (b.W, b.cnt2_sum, b.valid)
        assert b.sampler_backend == backend


# ---------------------------------------------------------------------------
# store tiers
# ---------------------------------------------------------------------------
def test_store_tiers_and_eviction():
    st = StreamStore(horizon=100, pad=False, max_segments=2)
    assert st.ingest([0, 1], [1, 2], [5, 50]) == 2
    assert st.ingest(2, 3, 120) == 1            # scalars work
    assert st.buffered == 3
    st.compact()                                # tail sealed; t<20 evicted
    assert st.buffered == 0 and st.retained == 2
    assert st.stats.evicted == 1                # the t=5 edge aged out
    # self-loops dropped at ingest
    assert st.ingest([4, 4], [4, 5], [130, 140]) == 1
    assert st.stats.dropped == 1
    # max_segments=2 triggers a merge on the third compaction
    st.compact()
    st.ingest(5, 6, 150)
    st.compact()
    assert st.stats.merges == 1 and len(st._segments) == 1
    ep = st.advance()
    assert ep.index == 0 and st.epoch == 1
    assert ep.m_real == 4 and (ep.t_lo, ep.t_hi) == (50, 150)
    with pytest.raises(ValueError):
        StreamStore(horizon=-1)
    with pytest.raises(ValueError):
        st.ingest([1, 2], [3], [4, 5])


def test_snapshot_independent_of_batch_split(edges):
    """An epoch is a pure function of the retained edge multiset."""
    src, dst, t = edges
    a = StreamStore(horizon=30_000, pad=False)
    a.ingest(src, dst, t)
    b = StreamStore(horizon=30_000, pad=False)
    for lo in range(0, len(src), 537):
        b.ingest(src[lo:lo + 537], dst[lo:lo + 537], t[lo:lo + 537])
        b.compact()
    ga, gb = a.advance().graph, b.advance().graph
    assert (ga.m, ga.n) == (gb.m, gb.n)
    np.testing.assert_array_equal(ga.src, gb.src)
    np.testing.assert_array_equal(ga.dst, gb.dst)
    np.testing.assert_array_equal(ga.t, gb.t)


def test_empty_advance_raises():
    with pytest.raises(ValueError, match="empty stream"):
        StreamStore().advance()


# ---------------------------------------------------------------------------
# the epoch determinism contract (the acceptance criterion)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_epoch_determinism_contract(edges, backend):
    """Per-epoch standing counts == cold estimate() on the epoch snapshot,
    across compaction AND eviction boundaries, both sampler backends."""
    src, dst, t = edges
    B = len(src) // 3
    with StreamingSession(config=_cfg(sampler_backend=backend),
                          horizon=25_000, min_m_bucket=2048,
                          min_n_bucket=256, min_p_bucket=2048) as ss:
        qid = ss.subscribe(StandingQuery(MOTIF, DELTA, 256, seed=0))
        qid2 = ss.subscribe(StandingQuery("0-1,1-2,2-0", DELTA, 128, seed=7))
        saw_eviction = False
        for e in range(3):
            lo, hi = e * B, (len(src) if e == 2 else (e + 1) * B)
            ss.ingest(src[lo:hi], dst[lo:hi], t[lo:hi])
            er = ss.advance()
            saw_eviction |= er.epoch.evicted > 0
            g = er.epoch.graph
            assert g.m_real is not None          # snapshots are padded
            for q, res in ((ss.queries[qid], er.results[qid]),
                           (ss.queries[qid2], er.results[qid2])):
                cold = estimate(g, get_motif(q.motif), q.delta, q.k,
                                seed=q.seed, chunk=CHUNK,
                                sampler_backend=backend)
                assert res.estimate == cold.estimate
                assert res.cnt2_sum == cold.cnt2_sum
                assert res.valid == cold.valid
                assert res.W == cold.W
                assert res.sampler_backend == cold.sampler_backend
        assert saw_eviction, "horizon never evicted — contract untested " \
                             "across an eviction boundary"
        assert ss.stats.epochs == 3 and ss.stats.queries_run == 6


def test_warm_epochs_reuse_compiled_programs(edges, no_retrace):
    """Steady-state epochs sharing buckets must NOT retrace the window
    program.  Epoch 0 is warm-up (its retained span — and thus its
    window-count bucket — differs from the horizon-limited steady
    state); epochs 1 and 2 share every bucket and must re-hit."""
    src, dst, t = edges
    B = len(src) // 3
    with StreamingSession(config=_cfg(), horizon=25_000, min_m_bucket=2048,
                          min_n_bucket=256, min_p_bucket=2048) as ss:
        ss.subscribe(StandingQuery(MOTIF, DELTA, 256, seed=0))
        ss.ingest(src[:B], dst[:B], t[:B])
        ss.advance()
        ss.ingest(src[B:2 * B], dst[B:2 * B], t[B:2 * B])
        er1 = ss.advance()
        assert engine._WINDOW_FN_LRU, "no compiled window programs to observe"
        ss.ingest(src[2 * B:], dst[2 * B:], t[2 * B:])
        with no_retrace() as probe:
            er2 = ss.advance()
        assert probe.dispatches > 0               # the epoch really ran
        assert er2.epoch.buckets == er1.epoch.buckets
        assert er2.epoch.evicted > 0              # horizon is active


# ---------------------------------------------------------------------------
# streaming loader + replay
# ---------------------------------------------------------------------------
def test_iter_edge_batches_text_gz_npz(tmp_path, graph):
    txt = tmp_path / "edges.txt"
    rows = np.stack([graph.src, graph.dst, graph.t], axis=1)
    with open(txt, "w") as f:
        f.write("# comment line\n\n")
        np.savetxt(f, rows, fmt="%d")
    gz = tmp_path / "edges.txt.gz"
    with gzip.open(gz, "wt") as f:
        np.savetxt(f, rows, fmt="%d")
    npz = tmp_path / "edges.npz"
    np.savez(npz, src=graph.src, dst=graph.dst, t=graph.t)
    for path in (txt, gz, npz):
        batches = list(iter_edge_batches(str(path), batch_size=701))
        assert all(len(b[0]) <= 701 for b in batches)
        got = np.stack([np.concatenate([b[i] for b in batches])
                        for i in range(3)], axis=1)
        np.testing.assert_array_equal(got, rows)
    with pytest.raises(ValueError):
        list(iter_edge_batches(str(txt), batch_size=0))


def test_load_edge_list_gz_and_replay_roundtrip(tmp_path, graph):
    gz = tmp_path / "edges.txt.gz"
    rows = np.stack([graph.src, graph.dst, graph.t], axis=1)
    with gzip.open(gz, "wt") as f:
        np.savetxt(f, rows, fmt="%d")
    g2 = load_edge_list(str(gz), cache=False)
    assert (g2.m, g2.n) == (graph.m, graph.n)
    # replaying the file into a store materializes the same graph
    st = StreamStore(pad=False)
    assert replay_edge_list(st, str(gz), batch_size=997) == graph.m
    g3 = st.advance().graph
    np.testing.assert_array_equal(g3.src, g2.src)
    np.testing.assert_array_equal(g3.dst, g2.dst)
    np.testing.assert_array_equal(g3.t, g2.t)


# ---------------------------------------------------------------------------
# serve loop: ingest / advance / subscribe round trip
# ---------------------------------------------------------------------------
def _run_stream_serve(*phases, **ss_kw):
    """Serve each phase (a list of wire objects) through its own gateway
    loop over ONE adopted stream; a phase's quit/EOF waits on the
    dispatcher, so an inline probe opening the next phase sees it done.
    Returns (served summed, every phase's response lines in order)."""
    out = io.StringIO()
    kw = dict(config=_cfg(), horizon=10_000, min_m_bucket=64)
    kw.update(ss_kw)
    served = 0
    with StreamingSession(**kw) as ss:
        for lines in phases:
            served += gateway_serve_loop(
                adopt=ss, outfile=out, infile=io.StringIO(
                    "\n".join(json.dumps(o) for o in lines) + "\n"))
    return served, [json.loads(ln) for ln in out.getvalue().splitlines()]


def test_serve_stream_roundtrip():
    edges = [[i % 9, (i + 1) % 9, 150 * i] for i in range(80)]
    edges2 = [[(i + 2) % 9, i % 9, 12_000 + 150 * i] for i in range(80)]
    served, rs = _run_stream_serve([
        {"cmd": "subscribe", "motif": "0-1,1-2", "delta": 400, "k": 128},
        {"cmd": "advance"},                       # empty stream -> error
        {"cmd": "ingest", "edges": edges},
        {"cmd": "advance"},
        {"id": 5, "motif": "0-1,1-2", "delta": 400, "k": 128},
        {"cmd": "ingest", "edges": edges2},
        {"cmd": "advance"},
        {"cmd": "quit"},
    ], [
        {"cmd": "stats"},                 # inline: after phase 1's quit
        {"cmd": "unsubscribe", "sub": 0},
    ])
    # the tenant's queue is FIFO: the ad-hoc request drains as its own
    # window before the second ingest executes
    sub, bad_adv, ing1, ep0_q, ep0, adhoc, ing2, ep1_q, ep1, quit_r, \
        stats, unsub = rs
    assert sub == {"ok": True, "cmd": "subscribe", "tenant": "default",
                   "sub": 0, "name": "0-1,1-2"}
    assert not bad_adv["ok"] and "empty stream" in bad_adv["error"]
    assert ing1["ok"] and ing1["ingested"] == 80 and ing1["buffered"] == 80
    assert ep0_q["ok"] and ep0_q["sub"] == 0 and ep0_q["epoch"] == 0
    # horizon=10000 vs t_max=11850: the 13 edges below t=1850 age out at
    # the first advance already
    assert ep0["ok"] and ep0["cmd"] == "advance" and ep0["m"] == 67
    assert ep0["evicted"] == 13
    # the ad-hoc request against epoch 0 matches the standing estimate
    assert adhoc["id"] == 5 and adhoc["ok"]
    assert adhoc["estimate"] == ep0_q["estimate"]
    assert ep1_q["epoch"] == 1 and ep1["epoch"] == 1
    assert ep1["evicted"] > 0                     # horizon aged epoch-0 edges
    tenant = stats["tenants"]["default"]
    assert tenant["epoch"] == 2 and tenant["subscriptions"] == 1
    assert unsub["ok"] and unsub["sub"] == 0
    assert quit_r["ok"]
    # 1 ad-hoc + 2 standing-epoch responses
    assert served == 3 and quit_r["served"] == 3


def test_serve_stream_guards():
    served, rs = _run_stream_serve([
        {"id": 1, "motif": "M4-2", "delta": 100, "k": 64},  # no epoch yet
        {"cmd": "ingest", "edges": "nope"},
        {"cmd": "ingest", "edges": [[1, 2], [3, 4]]},
        {"cmd": "subscribe", "motif": "M4-2", "delta": 100, "k": 64,
         "checkpoint_path": "/tmp/x"},             # unknown field rejected
        {"cmd": "subscribe", "motif": "no-such-motif", "delta": 1, "k": 1},
        {"cmd": "unsubscribe", "sub": 99},
    ])
    assert served == 0
    assert [r["ok"] for r in rs] == [False] * 6
    assert "no epoch" in rs[0]["error"]
    assert "edges" in rs[1]["error"]
    assert "checkpoint_path" in rs[3]["error"]


def test_serve_plain_session_rejects_stream_cmds(graph):
    from repro.api import Session
    out = io.StringIO()
    with Session(graph, _cfg()) as s:
        gateway_serve_loop(adopt=s, infile=io.StringIO('{"cmd": "advance"}\n'),
                           outfile=out)
    r = json.loads(out.getvalue().splitlines()[0])
    assert not r["ok"] and "needs a stream tenant" in r["error"]
    # with no adopted session, a tenant-less line has nowhere to go
    out = io.StringIO()
    gateway_serve_loop(infile=io.StringIO('{"cmd": "advance"}\n'),
                       outfile=out)
    r = json.loads(out.getvalue().splitlines()[0])
    assert not r["ok"] and 'need "tenant"' in r["error"]


# ---------------------------------------------------------------------------
# session guards + ad-hoc queries
# ---------------------------------------------------------------------------
def test_streaming_session_guards(edges):
    src, dst, t = edges
    ss = StreamingSession(horizon=10_000, config=_cfg(), min_m_bucket=64)
    with pytest.raises(RuntimeError, match="no epoch"):
        ss.query(Request(MOTIF, DELTA, 64))
    with pytest.raises(ValueError):
        StreamingSession(store=StreamStore(), horizon=5)  # both given
    with pytest.raises((KeyError, ValueError)):
        StandingQuery("no-such-motif", 10, 16)
    with pytest.raises(ValueError):
        StandingQuery(MOTIF, 10, 0)
    ss.ingest(src[:400], dst[:400], t[:400])
    er = ss.advance()
    assert er.results == {}                       # no subscriptions yet
    r = ss.query(Request(MOTIF, DELTA, 64, seed=0))
    cold = estimate(er.epoch.graph, get_motif(MOTIF), DELTA, 64, seed=0,
                    chunk=CHUNK)
    assert r.estimate == cold.estimate
    ss.close()
    with pytest.raises(RuntimeError):
        ss.ingest(1, 2, 3)
    with pytest.raises(RuntimeError):
        ss.advance()
    with pytest.raises(RuntimeError):
        ss.subscribe(StandingQuery(MOTIF, DELTA, 16))
