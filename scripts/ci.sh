#!/usr/bin/env bash
# Tier-1 CI gate: the full test suite must be green.
#
#   scripts/ci.sh            # tier-1 tests
#   CI_BENCH=1 scripts/ci.sh # + the fast serving benchmarks
#
# Mirrors ROADMAP.md "Tier-1 verify".  Dev-only deps (hypothesis) are
# best-effort: tests guard their imports, so an offline container still
# runs the full tier-1 set minus property tests.
set -euo pipefail
cd "$(dirname "$0")/.."

# contract linter FIRST: a seconds-fast, jax-free gate over the whole
# source tree (env-seam / retrace / determinism / exactness invariants —
# see src/repro/analysis).  Fails the build before anything heavy runs.
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m repro.analysis.lint src/

timeout 120 python -m pip install -q --disable-pip-version-check \
    -r requirements-dev.txt 2>/dev/null \
  || echo "ci: offline — running with preinstalled deps only"

PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -x -q

# sampler-backend seam: the interpret-mode kernel parity tests must hold
# with REPRO_SAMPLER_BACKEND resolved both ways (the suite above already
# ran them under the default "xla")
for backend in xla pallas; do
  REPRO_SAMPLER_BACKEND=$backend \
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m pytest -x -q tests/test_sampler_kernel.py
done

# execution engine: fusion + sharding parity must hold when the parent
# process ITSELF runs an 8-device host mesh (the suite above ran the
# in-process mesh tests on 1 device; the subprocess legs always force 8)
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
  python -m pytest -x -q tests/test_engine.py

# serving front-end: pipe 3 NDJSON requests (catalog motif, inline DSL
# motif, adaptive target_rse) through a real --serve process and assert
# three well-formed ok responses come back
printf '%s\n' \
    '{"id":1,"motif":"M5-3","delta":3000,"k":1024}' \
    '{"id":2,"motif":"0-1,1-2,2-0","delta":3000,"k":1024}' \
    '{"id":3,"motif":"M4-2","delta":3000,"k":512,"target_rse":0.5,"k_max":4096}' \
  | PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m repro.launch.estimate --graph powerlaw:n=150,m=2000 \
        --serve --chunk 256 \
  | PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -c '
import json, sys
lines = [ln for ln in sys.stdin if ln.strip()]
assert len(lines) == 3, f"want 3 responses, got {len(lines)}: {lines}"
ids = set()
for ln in lines:
    r = json.loads(ln)
    assert r["ok"], r
    assert "estimate" in r and r["W"] > 0 and r["k"] > 0, r
    ids.add(r["id"])
assert ids == {1, 2, 3}, ids
print("serve smoke OK")
'

# streaming front-end: drive a real --serve --stream process through the
# live verbs (subscribe -> ingest -> advance x2 with eviction) and assert
# the standing-query epoch responses + summaries come back well-formed
python - <<'PYEOF' > /tmp/ci_stream_input.ndjson
import json
lines = [
    {"cmd": "subscribe", "motif": "0-1,1-2,2-0", "delta": 400, "k": 512},
    {"cmd": "ingest",
     "edges": [[i % 11, (i + 1) % 11, 120 * i] for i in range(150)]},
    {"cmd": "advance"},
    {"cmd": "ingest",
     "edges": [[(i + 3) % 11, i % 11, 18000 + 120 * i] for i in range(150)]},
    {"cmd": "advance"},
    {"cmd": "quit"},
]
print("\n".join(json.dumps(o) for o in lines))
PYEOF
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
  python -m repro.launch.estimate --serve --stream --horizon 12000 \
      --chunk 256 < /tmp/ci_stream_input.ndjson \
  | PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -c '
import json, sys
rs = [json.loads(ln) for ln in sys.stdin if ln.strip()]
by_cmd = {}
for r in rs:
    by_cmd.setdefault(r.get("cmd", "sub" if "sub" in r else "?"), []).append(r)
assert by_cmd["subscribe"][0]["ok"] and by_cmd["subscribe"][0]["sub"] == 0
assert all(r["ok"] and r["ingested"] == 150 for r in by_cmd["ingest"])
advances = by_cmd["advance"]
assert len(advances) == 2 and [a["epoch"] for a in advances] == [0, 1]
assert advances[1]["evicted"] > 0, "horizon never evicted"
subs = by_cmd["sub"]
assert len(subs) == 2 and all(r["ok"] and "estimate" in r for r in subs)
assert [r["epoch"] for r in subs] == [0, 1]
assert by_cmd["quit"][0]["served"] == 2
print("stream serve smoke OK")
'

# tree-cohort sharing: 3 standing queries whose motifs all plan onto the
# wedge 0-1,1-2 spanning tree must fuse into ONE cohort dispatch per
# advance window (shared sample stream, one count lane per motif) —
# pinned through the stats/health "engine" block (engine.STATS)
python - <<'PYEOF' > /tmp/ci_cohort_input.ndjson
import json
lines = [
    {"cmd": "subscribe", "motif": "0-1,1-2", "delta": 2000, "k": 512},
    {"cmd": "subscribe", "motif": "0-1,1-2,1-2", "delta": 2000, "k": 512},
    {"cmd": "subscribe", "motif": "0-1,1-2,1-2,1-2", "delta": 2000,
     "k": 512},
    {"cmd": "ingest",
     "edges": [[i % 11, (i + 1) % 11, 120 * i] for i in range(150)]},
    {"cmd": "advance"},
    {"cmd": "ingest",
     "edges": [[(i + 3) % 11, i % 11, 18000 + 120 * i] for i in range(150)]},
    {"cmd": "advance"},
    {"cmd": "stats"},
    {"cmd": "quit"},
]
print("\n".join(json.dumps(o) for o in lines))
PYEOF
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
  python -m repro.launch.estimate --serve --stream --horizon 12000 \
      --chunk 256 < /tmp/ci_cohort_input.ndjson \
  | PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -c '
import json, sys
rs = [json.loads(ln) for ln in sys.stdin if ln.strip()]
subs = [r for r in rs if "sub" in r and "estimate" in r]
assert len(subs) == 6 and all(r["ok"] for r in subs), subs
assert subs[0]["estimate"] > 0, subs[0]   # the shared stream counts
eng = next(r for r in rs if r.get("cmd") == "stats")["engine"]
# one cohort dispatch per advance window: 2 advances x (3 queries, 1
# shared tree) -> 2 dispatches covering 6 job-windows, 512 samples
# drawn per window and consumed twice more without redrawing
assert eng["dispatches"] == 2, eng
assert eng["tree_cohorts"] == 2, eng
assert eng["fused_dispatches"] == 2, eng
assert eng["job_windows"] == 6, eng
assert eng["motifs_per_cohort"] == 3.0, eng
assert eng["samples_shared"] == 2 * 2 * 512, eng
print("tree-cohort serve smoke OK")
'

# stream replay: the CLI replays a recorded (gzipped) edge list through
# the store, advancing epochs with standing queries
python - <<'PYEOF'
import gzip, numpy as np
rng = np.random.default_rng(0)
m, n = 1200, 40
src = rng.integers(0, n, m); dst = (src + rng.integers(1, n, m)) % n
t = np.sort(rng.integers(0, 30_000, m))
with gzip.open("/tmp/ci_stream_replay.txt.gz", "wt") as f:
    np.savetxt(f, np.stack([src, dst, t], 1), fmt="%d")
PYEOF
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
  python -m repro.launch.estimate --stream-replay /tmp/ci_stream_replay.txt.gz \
      --horizon 15000 --replay-batch 400 --motif 0-1,1-2 --delta 500 \
      --k 1024 --chunk 256 \
  | tee /tmp/ci_stream_replay.out
grep -q "epoch 2:" /tmp/ci_stream_replay.out || {
  echo "stream replay smoke FAILED"; exit 1; }
echo "stream replay smoke OK"

# crash-safe WAL: SIGKILL a real --serve --stream --wal process right after
# an ingest is acknowledged, restart on the same WAL, and assert the
# recovered epoch's standing-query estimate is bit-identical to an
# uncrashed reference run (both sampler backends)
rm -f /tmp/ci_wal_*.wal
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} timeout 580 python - <<'PYEOF'
import json, os, signal, subprocess, sys

CMD = [sys.executable, "-m", "repro.launch.estimate", "--serve", "--stream",
       "--horizon", "12000", "--chunk", "256"]
EDGES1 = [[i % 11, (i + 1) % 11, 120 * i] for i in range(150)]
EDGES2 = [[(i + 3) % 11, i % 11, 18000 + 120 * i] for i in range(150)]
SUB = {"cmd": "subscribe", "motif": "0-1,1-2", "delta": 2000, "k": 512}


def start(wal, backend):
    env = dict(os.environ, REPRO_SAMPLER_BACKEND=backend)
    return subprocess.Popen(CMD + ["--wal", wal], stdin=subprocess.PIPE,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.DEVNULL, text=True, env=env)


def call(p, obj, n_replies=1):
    p.stdin.write(json.dumps(obj) + "\n")
    p.stdin.flush()
    return [json.loads(p.stdout.readline()) for _ in range(n_replies)]


for backend in ("xla", "pallas"):
    ref_wal = f"/tmp/ci_wal_ref_{backend}.wal"
    crash_wal = f"/tmp/ci_wal_crash_{backend}.wal"

    # reference: the uncrashed run (subscribe -> ingest/advance x2)
    p = start(ref_wal, backend)
    assert call(p, SUB)[0]["ok"]
    assert call(p, {"cmd": "ingest", "edges": EDGES1})[0]["ingested"] == 150
    call(p, {"cmd": "advance"}, n_replies=2)
    assert call(p, {"cmd": "ingest", "edges": EDGES2})[0]["ok"]
    ref = call(p, {"cmd": "advance"}, n_replies=2)[0]
    call(p, {"cmd": "quit"})
    p.wait(timeout=60)
    assert ref["ok"] and ref["epoch"] == 1, ref

    # crash: SIGKILL right after the second ingest is ACKED -- the WAL
    # fsyncs write-ahead, so the acknowledged batch must survive
    p = start(crash_wal, backend)
    assert call(p, SUB)[0]["ok"]
    assert call(p, {"cmd": "ingest", "edges": EDGES1})[0]["ok"]
    call(p, {"cmd": "advance"}, n_replies=2)
    assert call(p, {"cmd": "ingest", "edges": EDGES2})[0]["ok"]
    os.kill(p.pid, signal.SIGKILL)
    p.wait(timeout=60)

    # recovery: a fresh process on the same WAL replays to epoch 1 with
    # the acked batch buffered; its next advance matches ref bit-for-bit
    p = start(crash_wal, backend)
    h = call(p, {"cmd": "health"})[0]
    assert h["epoch"] == 1 and h["buffered"] == 150, h
    assert h["resilience"]["wal_replayed"] == 3, h
    assert call(p, SUB)[0]["ok"]
    rec = call(p, {"cmd": "advance"}, n_replies=2)[0]
    call(p, {"cmd": "quit"})
    p.wait(timeout=60)
    assert rec == ref, (rec, ref)        # the WHOLE response, bit for bit
    assert rec["epoch"] == 1 and rec["estimate"] > 0, rec
    print(f"wal SIGKILL smoke OK ({backend}): epoch={rec['epoch']} "
          f"estimate={rec['estimate']}")
PYEOF

# gateway: drive a real --serve --gateway process with two INTERLEAVED
# tenant command streams (a graph tenant with witnesses + a stream
# tenant with a standing query).  The whole burst is written before any
# reply is read — intake enqueues while drains run — then the stats
# probe (answered inline, never draining) lands after the drained
# responses prove the pool is live.  Asserts per-tenant routing,
# witness payloads, and the per-tenant stats blocks.
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} timeout 580 python - <<'PYEOF'
import json, subprocess, sys

p = subprocess.Popen(
    [sys.executable, "-m", "repro.launch.estimate", "--serve", "--gateway",
     "--chunk", "256", "--max-tenants", "4"],
    stdin=subprocess.PIPE, stdout=subprocess.PIPE,
    stderr=subprocess.DEVNULL, text=True)
burst = [
    {"cmd": "open_tenant", "tenant": "fin",
     "graph": "fintxn:n_accounts=60,m=1200,time_span=40000,seed=3"},
    {"cmd": "open_tenant", "tenant": "soc", "stream": True,
     "horizon": 12000},
    # interleaved: fin request / soc stream verbs / fin request ...
    {"tenant": "fin", "id": 1, "motif": "M4-2", "delta": 2000, "k": 512,
     "witnesses": 3},
    {"cmd": "subscribe", "tenant": "soc", "motif": "0-1,1-2",
     "delta": 2000, "k": 512},
    {"tenant": "fin", "id": 2, "motif": "0-1,1-2", "delta": 1500,
     "k": 512},
    {"cmd": "ingest", "tenant": "soc",
     "edges": [[i % 11, (i + 1) % 11, 120 * i] for i in range(150)]},
    {"cmd": "advance", "tenant": "soc"},
]
p.stdin.write("".join(json.dumps(o) + "\n" for o in burst))
p.stdin.flush()

rs = []
def have(pred):
    return any(pred(r) for r in rs)
# the terminal response of each queue: both fin finals, soc's epoch
# sub-response and advance summary (cross-tenant emit order is free)
while not (have(lambda r: r.get("id") == 2 and not r.get("progress"))
           and have(lambda r: "sub" in r and "estimate" in r)
           and have(lambda r: r.get("cmd") == "advance")):
    rs.append(json.loads(p.stdout.readline()))

def call(obj, n=1):
    p.stdin.write(json.dumps(obj) + "\n")
    p.stdin.flush()
    return [json.loads(p.stdout.readline()) for _ in range(n)]

finals = {r["id"]: r for r in rs
          if r.get("id") is not None and not r.get("progress")}
assert finals[1]["ok"] and finals[1]["tenant"] == "fin", finals
assert finals[2]["ok"] and finals[2]["tenant"] == "fin", finals
assert 1 <= len(finals[1]["witnesses"]) <= 3, finals[1]
prog = [r for r in rs if r.get("progress")]
assert prog and all(r["tenant"] == "fin" for r in prog), prog
subs = [r for r in rs if "sub" in r and "estimate" in r]
assert len(subs) == 1 and subs[0]["ok"] and subs[0]["tenant"] == "soc"
stats = call({"cmd": "stats"})[0]
assert set(stats["tenants"]) == {"fin", "soc"}, stats
assert stats["tenants"]["fin"]["mode"] == "graph"
assert stats["tenants"]["fin"]["served"] == 2, stats
assert stats["tenants"]["soc"]["mode"] == "stream"
assert stats["tenants"]["soc"]["epoch"] == 1, stats
assert stats["scheduler"]["turns"] > 0, stats
closed = call({"cmd": "close_tenant", "tenant": "soc"})[0]
assert closed["ok"] and closed["pool_size"] == 1, closed
quit_r = call({"cmd": "quit"})[0]
assert quit_r["served"] == 3, quit_r      # 2 fin requests + 1 epoch sub
p.wait(timeout=60)
print("gateway serve smoke OK")
PYEOF

# observability: the same gateway binary run at REPRO_OBS=trace must be
# scrapable over the wire — the metrics verb answers inline mid-burst
# (while drains run behind intake), the per-tenant latency histograms
# appear once the burst completes, and the flight recorder holds one
# connected intake -> drain -> dispatch -> emit span chain per request
# under a single stable trace id, crossing the gateway's three threads
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} REPRO_OBS=trace \
  timeout 580 python - <<'PYEOF'
import json, subprocess, sys

p = subprocess.Popen(
    [sys.executable, "-m", "repro.launch.estimate", "--serve", "--gateway",
     "--chunk", "256", "--max-tenants", "2"],
    stdin=subprocess.PIPE, stdout=subprocess.PIPE,
    stderr=subprocess.DEVNULL, text=True)

def send(obj):
    p.stdin.write(json.dumps(obj) + "\n")
    p.stdin.flush()

send({"cmd": "open_tenant", "tenant": "fin",
      "graph": "fintxn:n_accounts=60,m=1200,time_span=40000,seed=3"})
for i in (1, 2):
    send({"tenant": "fin", "id": i, "motif": "M4-2", "delta": 2000,
          "k": 512})
send({"cmd": "metrics"})          # mid-burst: answered inline, no drain

rs = []
def have(pred):
    return any(pred(r) for r in rs)
while not (have(lambda r: r.get("id") == 2 and not r.get("progress"))
           and have(lambda r: r.get("cmd") == "metrics")):
    rs.append(json.loads(p.stdout.readline()))
mid = next(r for r in rs if r.get("cmd") == "metrics")
assert mid["ok"] and mid["content_type"].startswith("text/plain"), mid
# engine counters may not be declared yet mid-burst (the engine imports
# on the dispatcher's first drain) — the always-on series must be
assert "# TYPE repro_resilience_retries_total counter" in mid["text"]
assert "# TYPE repro_stage_seconds histogram" in mid["text"]

def call(obj):
    send(obj)
    return json.loads(p.stdout.readline())

# the stats response is emitted AFTER both finals' emit spans closed, so
# once it is read the recorder holds the complete chains
st = call({"cmd": "stats"})
assert st["ok"] and st["obs"]["level"] == "trace", st

post = call({"cmd": "metrics"})
assert "# TYPE repro_engine_dispatches_total counter" in post["text"]
assert "repro_tenant_request_seconds_bucket" in post["text"]
assert 'tenant="fin"' in post["text"]
assert "repro_stage_seconds_bucket" in post["text"]

tr = call({"cmd": "trace"})
assert tr["ok"] and tr["level"] == "trace" and tr["count"] > 0, tr
intakes = [r for r in tr["spans"] if r["name"] == "gateway.intake"
           and r.get("attrs", {}).get("id") == 1]
assert intakes, [r["name"] for r in tr["spans"]]
tid = intakes[0]["trace"]
chain = [r for r in tr["spans"] if r["trace"] == tid]
names = {r["name"] for r in chain}
assert {"gateway.intake", "session.drain", "engine.dispatch",
        "gateway.emit"} <= names, names
assert len({r["thread"] for r in chain}) >= 3, chain   # 3 threads, 1 id

quit_r = call({"cmd": "quit"})
assert quit_r["served"] == 2, quit_r
p.wait(timeout=60)
print("obs gateway smoke OK")
PYEOF

if [[ "${CI_BENCH:-0}" == "1" ]]; then
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m benchmarks.run --suite batch --fast
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m benchmarks.run --suite sampler --fast
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m benchmarks.run --suite engine --fast
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m benchmarks.run --suite serve --fast
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m benchmarks.run --suite stream --fast
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m benchmarks.run --suite multimotif --fast
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m benchmarks.run --suite resilience --fast
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m benchmarks.run --suite gateway --fast
fi
