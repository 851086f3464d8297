"""Benchmark harness — one benchmark per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--only t3,t6]

Paper tables reproduced (on calibrated synthetic graphs — WT/SO/BI/RE are
not redistributable offline; see DESIGN.md §7):

  t3_speed     Table 3: TIMEST runtime vs the exact counter, 5/6-vertex
               motifs, + estimation error vs exact ground truth
  t4_accuracy  Table 4: TIMEST vs PRESTO-A/E error at matched budgets
  t5_small     Table 5: 4-vertex motifs vs PRESTO/ES/IS
  t6_ablation  Table 6: constraint ablation C1 / C1+2 / C1+2+3
               (valid-sample rate + error)
  t7_trees     Table 7: spanning-tree choice (W, error, runtime)
  f6_sweep     Figure 6: error spread across all rooted trees (M4-scale)
  perf_micro   sampling throughput (samples/s) + us/sample

Output: CSV lines ``bench,case,metric,value`` to stdout.
"""
from __future__ import annotations

import argparse
import time

import numpy as np


def _graph(fast: bool):
    """Benchmark graph: sized so the EXACT oracle (the pure-python BT
    counter every error column needs) stays in tens of seconds per motif
    on this 1-core container; the estimator itself handles much larger
    graphs (see examples/ and the launch.estimate CLI)."""
    from repro.graphs import powerlaw_temporal_graph
    if fast:
        return powerlaw_temporal_graph(n=300, m=4_000, time_span=60_000,
                                       seed=7), 3_000
    return powerlaw_temporal_graph(n=500, m=8_000, time_span=120_000,
                                   seed=7), 4_000


def emit(bench, case, metric, value):
    print(f"{bench},{case},{metric},{value}", flush=True)


_EXACT_CACHE: dict = {}


def clear_engine_caches():
    """Cold-start helper for the serving benchmarks: drop every compiled
    program the engine/preprocess layers cache, so a 'sequential' leg
    models one process per request.  Keep in sync with any new cache."""
    from repro.core import engine as engine_mod
    from repro.core import weights as weights_mod
    engine_mod.clear_window_cache()
    weights_mod._PREPROCESS_FN_CACHE.clear()
    weights_mod._window_totals_fn.cache_clear()


def exact_cached(g, motif, delta):
    """The pure-python exact oracle is the slow part — cache per motif."""
    from repro.core.exact import count_exact
    key = (id(g), motif.name, delta)
    if key not in _EXACT_CACHE:
        t0 = time.perf_counter()
        _EXACT_CACHE[key] = (count_exact(g, motif, delta),
                             time.perf_counter() - t0)
    return _EXACT_CACHE[key]


# ---------------------------------------------------------------------------
def t3_speed(fast: bool):
    from repro.core.estimator import estimate
    from repro.core.exact import count_exact
    from repro.core.motif import get_motif

    g, delta = _graph(fast)
    # M5-1/M6-1 hub stars explode the EXACT oracle on power-law graphs
    # (73M matches / 176 s at this size) — the full list keeps one star
    # and the cycle/path/dense motifs the paper features.
    motifs = ["M5-1", "M5-3"] if fast else ["M5-1", "M5-2", "M5-3", "M6-3"]
    k = 1 << (14 if fast else 17)
    for name in motifs:
        m = get_motif(name)
        exact, t_exact = exact_cached(g, m, delta)
        t0 = time.perf_counter()
        res = estimate(g, m, delta, k, seed=0)
        t_est = time.perf_counter() - t0
        err = abs(res.estimate - exact) / max(exact, 1)
        emit("t3", name, "exact_count", exact)
        emit("t3", name, "exact_s", f"{t_exact:.3f}")
        emit("t3", name, "timest_s", f"{t_est:.3f}")
        emit("t3", name, "speedup", f"{t_exact / max(t_est, 1e-9):.2f}")
        emit("t3", name, "error_pct", f"{100 * err:.2f}")


def t4_accuracy(fast: bool):
    from repro.core.baselines import presto_estimate
    from repro.core.estimator import estimate
    from repro.core.exact import count_exact
    from repro.core.motif import get_motif

    g, delta = _graph(fast)
    motifs = ["M5-1"] if fast else ["M5-1", "M5-3"]
    for name in motifs:
        m = get_motif(name)
        exact, _ = exact_cached(g, m, delta)
        res = estimate(g, m, delta, 1 << (14 if fast else 17), seed=1)
        emit("t4", name, "timest_err_pct",
             f"{100 * abs(res.estimate - exact) / max(exact, 1):.2f}")
        for variant in ("A", "E"):
            r = presto_estimate(g, m, delta, variant=variant,
                                r=6 if fast else 20, seed=1)
            emit("t4", name, f"presto_{variant}_err_pct",
                 f"{100 * abs(r.estimate - exact) / max(exact, 1):.2f}")
            emit("t4", name, f"presto_{variant}_s", f"{r.runtime_s:.3f}")


def t5_small(fast: bool):
    from repro.core.baselines import es_estimate, is_estimate
    from repro.core.estimator import estimate
    from repro.core.exact import count_exact
    from repro.core.motif import get_motif

    g, delta = _graph(fast)
    motifs = ["M4-1", "M4-2"] if fast else ["M4-1", "M4-2", "M4-3", "M4-4"]
    for name in motifs:
        m = get_motif(name)
        exact, _ = exact_cached(g, m, delta)
        res = estimate(g, m, delta, 1 << (13 if fast else 16), seed=2)
        emit("t5", name, "timest_err_pct",
             f"{100 * abs(res.estimate - exact) / max(exact, 1):.2f}")
        es = es_estimate(g, m, delta, p=0.05, seed=2)
        emit("t5", name, "es_err_pct",
             f"{100 * abs(es.estimate - exact) / max(exact, 1):.2f}")
        isr = is_estimate(g, m, delta, c=10.0, p=0.3, seed=2)
        emit("t5", name, "is_err_pct",
             f"{100 * abs(isr.estimate - exact) / max(exact, 1):.2f}")


def t6_ablation(fast: bool):
    from repro.core.estimator import estimate
    from repro.core.exact import count_exact
    from repro.core.motif import get_motif

    g, delta = _graph(fast)
    # the paper ablates on M5-5 (5-clique); cliques are vanishingly rare
    # on these synthetic graphs (exact ~ 0 makes error % meaningless), so
    # the ablation runs on the money-cycle M5-3 at both sizes.
    m = get_motif("M5-3")
    exact, _ = exact_cached(g, m, delta)
    k = 1 << (14 if fast else 16)
    for label, c2, c3 in (("C1", False, False), ("C1+2", True, False),
                          ("C1+2+3", True, True)):
        t0 = time.perf_counter()
        res = estimate(g, m, delta, k, seed=3, use_c2=c2, use_c3=c3)
        dt = time.perf_counter() - t0
        emit("t6", label, "valid_rate_pct", f"{100 * res.valid_rate:.2f}")
        emit("t6", label, "fail_vmap_pct",
             f"{100 * res.fail_vmap / max(res.k, 1):.2f}")
        emit("t6", label, "fail_delta_pct",
             f"{100 * res.fail_delta / max(res.k, 1):.2f}")
        emit("t6", label, "fail_order_pct",
             f"{100 * res.fail_order / max(res.k, 1):.2f}")
        emit("t6", label, "error_pct",
             f"{100 * abs(res.estimate - exact) / max(exact, 1):.2f}")
        emit("t6", label, "runtime_s", f"{dt:.3f}")


def t7_trees(fast: bool):
    from repro.core.estimator import estimate
    from repro.core.exact import count_exact
    from repro.core.motif import get_motif
    from repro.core.spanning_tree import candidate_trees

    g, delta = _graph(fast)
    m = get_motif("M5-3")
    exact, _ = exact_cached(g, m, delta)
    trees = candidate_trees(m, n_candidates=3, roots_per_tree=1)
    k = 1 << (14 if fast else 16)
    for i, tree in enumerate(trees):
        t0 = time.perf_counter()
        res = estimate(g, m, delta, k, seed=4, tree=tree)
        dt = time.perf_counter() - t0
        emit("t7", f"S{i + 1}", "W", res.W)
        emit("t7", f"S{i + 1}", "error_pct",
             f"{100 * abs(res.estimate - exact) / max(exact, 1):.2f}")
        emit("t7", f"S{i + 1}", "runtime_s", f"{dt:.3f}")


def f6_sweep(fast: bool):
    from repro.core.estimator import estimate
    from repro.core.exact import count_exact
    from repro.core.motif import get_motif
    from repro.core.spanning_tree import all_rooted_trees

    g, delta = _graph(True)  # always the small graph: many trees
    m = get_motif("M4-4")
    exact = count_exact(g, m, delta)
    errs = []
    trees = all_rooted_trees(m)
    if fast:
        trees = trees[:6]
    for tree in trees:
        res = estimate(g, m, delta, 1 << 13, seed=5, tree=tree)
        errs.append(100 * abs(res.estimate - exact) / max(exact, 1))
    emit("f6", "M4-4", "n_trees", len(errs))
    emit("f6", "M4-4", "err_min_pct", f"{min(errs):.2f}")
    emit("f6", "M4-4", "err_median_pct", f"{float(np.median(errs)):.2f}")
    emit("f6", "M4-4", "err_max_pct", f"{max(errs):.2f}")


def perf_micro(fast: bool):
    import jax

    from repro.core.estimator import choose_tree, make_chunk_fn
    from repro.core.motif import get_motif

    g, delta = _graph(fast)
    m = get_motif("M5-3")
    dev = g.device_arrays()
    tree, wts = choose_tree(g, m, delta, dev=dev)
    K = 1 << 13
    chunk_fn = make_chunk_fn(tree, K)  # the fused production path (C2)
    key = jax.random.PRNGKey(0)
    jax.block_until_ready(chunk_fn(dev, wts, key)["cnt2"])  # compile
    reps = 3 if fast else 10
    t0 = time.perf_counter()
    for i in range(reps):
        jax.block_until_ready(
            chunk_fn(dev, wts, jax.random.fold_in(key, i))["cnt2"])
    dt = time.perf_counter() - t0
    emit("perf", "M5-3", "samples_per_s", f"{reps * K / dt:.0f}")
    emit("perf", "M5-3", "us_per_sample", f"{1e6 * dt / (reps * K):.3f}")


def batch_bench(fast: bool):
    """Batched multi-motif serving (core/batch.py) vs the per-request
    sequential loop on a >= 8-job workload over one graph.

    The sequential baseline models one-motif-at-a-time serving: every
    request pays its own preprocessing and compiled-sampler caches (the
    engine caches are cleared per job, as separate requests/processes
    would).  ``estimate_many`` runs the same jobs through one shared
    upload + deduplicated preprocess + shared compiled samplers, with
    bit-identical results.  Writes BENCH_batch.json.
    """
    import json
    import os

    from repro.core.batch import estimate_many
    from repro.core.estimator import estimate
    from repro.core.motif import get_motif
    from repro.graphs import powerlaw_temporal_graph

    g = powerlaw_temporal_graph(n=300, m=4_000, time_span=60_000, seed=7)
    motifs = ("M4-2", "M5-3")
    deltas = (2_000, 4_000)
    ks = (1 << 11, 1 << 12) if fast else (1 << 11, 1 << 12, 1 << 13)
    jobs = [(mn, d, k) for mn in motifs for d in deltas for k in ks]
    # chunk/checkpoint_every chosen so every budget is whole scan windows
    # of the same static length — all jobs of a tree share one compiled
    # sampler program
    chunk, ck_every = 1 << 10, 2

    t0 = time.perf_counter()
    seq = []
    for (mn, d, k) in jobs:
        clear_engine_caches()  # each request starts cold, like a serving process
        seq.append(estimate(g, get_motif(mn), d, k, seed=0, chunk=chunk,
                            checkpoint_every=ck_every))
    t_seq = time.perf_counter() - t0

    clear_engine_caches()
    t0 = time.perf_counter()
    bat = estimate_many(g, jobs, seed=0, chunk=chunk,
                        checkpoint_every=ck_every)
    t_batch = time.perf_counter() - t0

    identical = all(a.estimate == b.estimate and a.cnt2_sum == b.cnt2_sum
                    and a.valid == b.valid for a, b in zip(seq, bat))
    speedup = t_seq / max(t_batch, 1e-9)
    emit("batch", "workload", "n_jobs", len(jobs))
    emit("batch", "workload", "identical_results", identical)
    emit("batch", "workload", "sequential_s", f"{t_seq:.3f}")
    emit("batch", "workload", "batch_s", f"{t_batch:.3f}")
    emit("batch", "workload", "speedup", f"{speedup:.2f}")
    record = dict(
        n_jobs=len(jobs),
        jobs=[dict(motif=mn, delta=d, k=k) for (mn, d, k) in jobs],
        graph=dict(n=g.n, m=g.m, time_span=g.time_span),
        chunk=chunk,
        sequential_s=round(t_seq, 3),
        batch_s=round(t_batch, 3),
        speedup=round(speedup, 2),
        identical_results=bool(identical),
        methodology=("sequential = cold per-request estimate() loop "
                     "(engine caches cleared per job); batch = one "
                     "estimate_many() with shared upload, deduplicated "
                     "preprocessing and shared compiled samplers"),
    )
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_batch.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=2)
    print(f"# wrote {path}", flush=True)


def engine_bench(fast: bool):
    """Fused + sharded execution engine (core/engine.py) vs the cold
    sequential loop on the 12-job workload.  Writes BENCH_engine.json.

    Cold serving legs (the batch_bench methodology, bit-identical
    counts):

    * sequential — one-motif-at-a-time serving, engine caches cleared per
      request;
    * fused      — ``estimate_many`` through the engine at 1 device: jobs
      sharing a plan key dispatch as ONE vmapped window program;
    * sharded    — the fused workload again in a subprocess with 8 forced
      host devices and a ``--mesh``-style data mesh, chunks round-robined
      over shards.

    Steady-state chunk-scaling legs: one fused 3-job window program
    (8 chunks x 1024 samples) timed after warmup at mesh sizes 1/2/8 in
    fresh subprocesses — the compile-free measure of what sharding the
    chunk range buys (virtual host devices share this machine's physical
    cores, which caps the achievable scaling at the core count).
    """
    import json
    import os
    import subprocess
    import sys

    import jax

    # the sharded and steady-state legs start child processes that
    # initialise jax on forced virtual CPU devices; on an accelerator this
    # process already holds the chip, and such a child would fail or hang
    # on the accelerator library's lock
    if jax.default_backend() != "cpu":
        raise SystemExit(
            f"--suite engine runs its mesh legs in child processes on "
            f"virtual CPU devices and cannot share this process's "
            f"{jax.default_backend()} devices: run it with "
            f"JAX_PLATFORMS=cpu (on the chip, `python chip_smoke.py "
            f"--four-chips` drives the engine mesh in one process)")

    from repro.core import engine as engine_mod
    from repro.core.batch import estimate_many
    from repro.core.estimator import estimate
    from repro.core.motif import get_motif
    from repro.graphs import powerlaw_temporal_graph

    gspec = dict(n=300, m=4_000, time_span=60_000, seed=7)
    g = powerlaw_temporal_graph(**gspec)
    motifs = ("M4-2", "M5-3")
    deltas = (2_000, 4_000)
    ks = (1 << 10, 1 << 11, 1 << 12) if fast else (1 << 11, 1 << 12, 1 << 13)
    jobs = [(mn, d, k) for mn in motifs for d in deltas for k in ks]
    # chunk/checkpoint_every chosen so every budget is whole windows of
    # the same static length (the batch_bench serving grid)
    chunk, ck_every = 1 << 10, 2
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    t0 = time.perf_counter()
    seq = []
    for (mn, d, k) in jobs:
        clear_engine_caches()  # each request starts cold, like a serving process
        seq.append(estimate(g, get_motif(mn), d, k, seed=0, chunk=chunk,
                            checkpoint_every=ck_every))
    t_seq = time.perf_counter() - t0

    clear_engine_caches()
    engine_mod.STATS.reset()
    t0 = time.perf_counter()
    fused = estimate_many(g, jobs, seed=0, chunk=chunk,
                          checkpoint_every=ck_every)
    t_fused = time.perf_counter() - t0
    fused_dispatches = engine_mod.STATS.dispatches
    job_windows = engine_mod.STATS.job_windows

    identical = all(a.estimate == b.estimate and a.cnt2_sum == b.cnt2_sum
                    and a.valid == b.valid for a, b in zip(seq, fused))

    # sharded leg: own process (device count is fixed at first jax init)
    child = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, time, json
sys.path.insert(0, "src")
from repro.core.batch import estimate_many
from repro.launch.mesh import make_estimator_mesh
from repro.graphs import powerlaw_temporal_graph
g = powerlaw_temporal_graph(**{gspec!r})
mesh = make_estimator_mesh()
t0 = time.perf_counter()
res = estimate_many(g, {jobs!r}, seed=0, chunk={chunk},
                    checkpoint_every={ck_every}, mesh=mesh)
dt = time.perf_counter() - t0
print(json.dumps(dict(t=round(dt, 3), cnt2=[r.cnt2_sum for r in res],
                      mesh_shape=res[0].mesh_shape)))
"""
    r = subprocess.run([sys.executable, "-c", child], capture_output=True,
                       text=True, cwd=repo)
    assert r.returncode == 0, r.stderr
    shard = json.loads(r.stdout.strip().splitlines()[-1])
    t_shard = shard["t"]
    identical_sharded = shard["cnt2"] == [x.cnt2_sum for x in fused]

    # steady-state: s/window of one fused window program vs mesh size
    steady_child = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%d"
import sys, time, json
sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from repro.core.engine import make_engine_window_fn
from repro.core.estimator import choose_tree
from repro.core.motif import get_motif
from repro.launch.mesh import make_estimator_mesh
from repro.graphs import powerlaw_temporal_graph
D = %d
g = powerlaw_temporal_graph(**%r)
dev = g.device_arrays()
tree, wts = choose_tree(g, get_motif("M5-3"), 4_000, dev=dev)
mesh = make_estimator_mesh() if D > 1 else None
fn = make_engine_window_fn(tree, %d, mesh=mesh)
keys = jnp.stack([jax.random.PRNGKey(s) for s in range(3)])
n = 8
jax.block_until_ready(fn(dev, wts, keys, 0, n)["cnt2"])  # compile
reps = %d
t0 = time.perf_counter()
for rr in range(reps):
    jax.block_until_ready(fn(dev, wts, keys, rr * n, n)["cnt2"])
dt = time.perf_counter() - t0
print(json.dumps(dict(window_s=round(dt / reps, 4),
                      samples_per_s=round(reps * n * 3 * %d / dt, 1))))
"""
    reps = 8 if fast else 24
    steady = {}
    for D in (1, 2, 8):
        r = subprocess.run(
            [sys.executable, "-c",
             steady_child % (D, D, gspec, chunk, reps, chunk)],
            capture_output=True, text=True, cwd=repo)
        assert r.returncode == 0, r.stderr
        steady[D] = json.loads(r.stdout.strip().splitlines()[-1])
    scaling = {D: round(steady[1]["window_s"] / steady[D]["window_s"], 2)
               for D in steady}

    speedup_fused = t_seq / max(t_fused, 1e-9)
    speedup_shard = t_seq / max(t_shard, 1e-9)
    emit("engine", "workload", "n_jobs", len(jobs))
    emit("engine", "workload", "identical_results",
         identical and identical_sharded)
    emit("engine", "workload", "sequential_s", f"{t_seq:.3f}")
    emit("engine", "workload", "fused_s", f"{t_fused:.3f}")
    emit("engine", "workload", "sharded8_s", f"{t_shard:.3f}")
    emit("engine", "workload", "fused_dispatches", fused_dispatches)
    emit("engine", "workload", "job_windows", job_windows)
    emit("engine", "workload", "speedup_fused", f"{speedup_fused:.2f}")
    emit("engine", "workload", "speedup_sharded8", f"{speedup_shard:.2f}")
    for D in steady:
        emit("engine", f"steady/D={D}", "window_s", steady[D]["window_s"])
        emit("engine", f"steady/D={D}", "scaling_vs_1dev", scaling[D])
    record = dict(
        n_jobs=len(jobs),
        jobs=[dict(motif=mn, delta=d, k=k) for (mn, d, k) in jobs],
        graph=dict(n=g.n, m=g.m, time_span=g.time_span),
        chunk=chunk,
        checkpoint_every=ck_every,
        sequential_s=round(t_seq, 3),
        fused_s=round(t_fused, 3),
        sharded8_s=round(t_shard, 3),
        sharded8_mesh=shard["mesh_shape"],
        dispatches_fused=fused_dispatches,
        dispatches_sequential=job_windows,
        speedup_fused=round(speedup_fused, 2),
        speedup_sharded8=round(speedup_shard, 2),
        steady_state={str(D): dict(**steady[D],
                                   scaling_vs_1dev=scaling[D])
                      for D in steady},
        host_cores=os.cpu_count(),
        identical_results=bool(identical and identical_sharded),
        methodology=("cold legs: sequential = per-request estimate() "
                     "loop with engine caches cleared per job; fused = "
                     "one estimate_many() through core/engine.py at 1 "
                     "device (jobs sharing a plan key dispatch as one "
                     "vmapped window program); sharded8 = the fused "
                     "workload in a fresh process with 8 forced host "
                     "devices and a (data,) mesh, chunks round-robined "
                     "over shards.  All legs return bit-identical "
                     "counts.  dispatches_sequential counts job-windows "
                     "(what the old per-job loop launched); "
                     "dispatches_fused is what the engine launched. "
                     "steady_state: one fused 3-job window program (8 "
                     "chunks x 1024 samples) timed after warmup at mesh "
                     "sizes 1/2/8 in fresh processes — the compile-free "
                     "chunk-scaling measure."),
        note=("virtual host devices share this machine's physical cores "
              "(host_cores), which caps steady-state scaling: chunk "
              "round-robin reduces per-shard work 8x, but wall-clock "
              "gains saturate at the core count; the dispatch counts "
              "are the hardware-independent signal"),
    )
    path = os.path.join(repo, "BENCH_engine.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=2)
    print(f"# wrote {path}", flush=True)


def serve_bench(fast: bool):
    """Warm-session serving (repro.api.Session) vs cold one-shot
    ``estimate()`` on a 6-request burst.  Writes BENCH_serve.json.

    * cold — one-motif-at-a-time serving: each request pays its own
      preprocessing and compiled-program caches (engine caches cleared
      per request, the batch_bench methodology);
    * warm — a resident ``Session`` that already served one identical
      burst: the device upload, the (tree, delta) preprocess cache and
      the compiled window programs are all hot, and the burst's submits
      coalesce into one engine plan (requests sharing a plan key fuse).

    Results are bit-identical between legs (same seeds, engine
    determinism contract); the acceptance bar is warm >= 2x cold.
    """
    import json
    import os

    from repro.api import EstimateConfig, Request, Session
    from repro.core.estimator import estimate
    from repro.core.motif import get_motif
    from repro.graphs import powerlaw_temporal_graph

    g = powerlaw_temporal_graph(n=300, m=4_000, time_span=60_000, seed=7)
    delta = 2_000
    ks = (1 << 10, 1 << 11, 1 << 12) if fast else (1 << 11, 1 << 12, 1 << 13)
    burst = [(mn, delta, k) for mn in ("M4-2", "M5-3") for k in ks]
    chunk, ck_every = 1 << 10, 2   # whole same-length windows per budget

    t0 = time.perf_counter()
    cold = []
    for (mn, d, k) in burst:
        clear_engine_caches()  # each request starts cold, like a fresh process
        cold.append(estimate(g, get_motif(mn), d, k, seed=0, chunk=chunk,
                             checkpoint_every=ck_every))
    t_cold = time.perf_counter() - t0

    clear_engine_caches()
    cfg = EstimateConfig(chunk=chunk, checkpoint_every=ck_every,
                         coalesce_window_s=60.0)
    with Session(g, cfg) as session:
        def run_burst():
            handles = [session.submit(Request(mn, d, k, seed=0))
                       for (mn, d, k) in burst]
            return [h.result() for h in handles]

        run_burst()                       # warm the session
        t0 = time.perf_counter()
        warm = run_burst()                # the measured burst
        t_warm = time.perf_counter() - t0

    identical = all(a.estimate == b.estimate and a.cnt2_sum == b.cnt2_sum
                    and a.valid == b.valid for a, b in zip(cold, warm))
    speedup = t_cold / max(t_warm, 1e-9)
    emit("serve", "burst6", "n_requests", len(burst))
    emit("serve", "burst6", "identical_results", identical)
    emit("serve", "burst6", "cold_s", f"{t_cold:.3f}")
    emit("serve", "burst6", "warm_session_s", f"{t_warm:.3f}")
    emit("serve", "burst6", "speedup", f"{speedup:.2f}")
    record = dict(
        n_requests=len(burst),
        requests=[dict(motif=mn, delta=d, k=k) for (mn, d, k) in burst],
        graph=dict(n=g.n, m=g.m, time_span=g.time_span),
        chunk=chunk,
        checkpoint_every=ck_every,
        cold_estimate_s=round(t_cold, 3),
        warm_session_s=round(t_warm, 3),
        speedup=round(speedup, 2),
        identical_results=bool(identical),
        methodology=("cold = 6 one-shot estimate() calls with engine "
                     "caches cleared per request (one process per "
                     "request); warm = the same 6 requests submitted "
                     "into one coalescing window of a resident Session "
                     "that already served an identical burst (hot "
                     "upload/preprocess/compiled-program caches, "
                     "plan-key fusion).  Bit-identical results."),
    )
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_serve.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=2)
    print(f"# wrote {path}", flush=True)


def sampler_bench(fast: bool):
    """XLA gather-chain vs fused Pallas sampler (kernels/tree_sampler)
    across sample budgets K and motif sizes.  Writes BENCH_sampler.json.

    Measures the sampler alone (``make_sample_fn``, both backends drawing
    bit-identical samples) — steady-state throughput after one warmup
    call, host-blocked per repetition.
    """
    import json
    import os

    import jax

    from repro.core.estimator import choose_tree
    from repro.core.motif import get_motif
    from repro.core.sampler import make_sample_fn
    from repro.kernels.tree_sampler.ops import pallas_sampler_eligible

    g, delta = _graph(fast)
    dev = g.device_arrays()
    motifs = ("M4-2", "M5-3") if fast else ("M4-2", "M5-3", "M6-3")
    Ks = (1 << 11, 1 << 13) if fast else (1 << 11, 1 << 13, 1 << 15)
    reps = 3 if fast else 8
    cases = []
    for mn in motifs:
        m = get_motif(mn)
        tree, wts = choose_tree(g, m, delta, dev=dev)
        ok, why = pallas_sampler_eligible(dev, wts)
        for K in Ks:
            case = dict(motif=mn, K=K, tree_edges=list(tree.edge_ids))
            for backend in ("xla", "pallas"):
                if backend == "pallas" and not ok:
                    case["pallas_skipped"] = why
                    continue
                fn = make_sample_fn(tree, K, backend=backend, guard=False)
                key = jax.random.PRNGKey(0)
                jax.block_until_ready(fn(dev, wts, key)["edges"])  # compile
                t0 = time.perf_counter()
                for i in range(reps):
                    jax.block_until_ready(
                        fn(dev, wts, jax.random.fold_in(key, i))["edges"])
                dt = time.perf_counter() - t0
                case[f"{backend}_samples_per_s"] = round(reps * K / dt, 1)
                case[f"{backend}_us_per_sample"] = round(
                    1e6 * dt / (reps * K), 3)
                emit("sampler", f"{mn}/K={K}", f"{backend}_samples_per_s",
                     f"{reps * K / dt:.0f}")
            if "pallas_samples_per_s" in case:
                case["speedup"] = round(case["pallas_samples_per_s"]
                                        / case["xla_samples_per_s"], 2)
                emit("sampler", f"{mn}/K={K}", "speedup", case["speedup"])
            cases.append(case)
    speedups = [c["speedup"] for c in cases if "speedup" in c]
    record = dict(
        graph=dict(n=g.n, m=g.m, time_span=g.time_span),
        backend=jax.default_backend(),
        reps=reps,
        cases=cases,
        speedup_min=min(speedups) if speedups else None,
        speedup_max=max(speedups) if speedups else None,
        methodology=("per-backend steady-state sampler throughput of "
                     "make_sample_fn (bit-identical draws), warmup "
                     "excluded, host-blocked per rep; pallas = one fused "
                     "tree_sampler pallas_call per chunk (interpret mode "
                     "off-TPU), xla = the per-step gather-chain sampler"),
        note=("off-TPU the pallas kernel runs in interpret mode, i.e. "
              "lowered through the Pallas interpreter to the host "
              "backend — the measured ratio reflects XLA:interpreter "
              "fusion on this host, not TPU VMEM-residency gains"),
    )
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_sampler.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=2)
    print(f"# wrote {path}", flush=True)


def stream_bench(fast: bool):
    """Epoch-advance cost on a live stream: warm StreamingSession (padded
    snapshots, compiled-program reuse) vs cold per-epoch Session rebuild.
    Writes BENCH_stream.json.

    * cold — each epoch materializes an UNPADDED snapshot and estimates
      through a fresh one-shot path with engine caches cleared (like a
      fresh process per epoch, the batch_bench methodology): every
      advance pays tree preprocess traces and window-program compiles
      against that epoch's unique array shapes;
    * warm — a resident ``StreamingSession``: snapshots are padded to
      power-of-two buckets, so steady-state epochs present identical
      shapes and re-hit every compiled program.

    Both legs see identical retained edge sets per epoch and must report
    bit-identical per-epoch estimates (padding invisibility + the epoch
    determinism contract).  Headline: steady-state warm advance vs cold
    rebuild; the acceptance bar is warm >= 2x cold.
    """
    import json
    import os

    from repro.api import EstimateConfig
    from repro.core.estimator import estimate
    from repro.core.motif import get_motif
    from repro.graphs import powerlaw_temporal_graph
    from repro.stream import StandingQuery, StreamingSession, StreamStore

    n_epochs = 4 if fast else 6
    k = (1 << 11) if fast else (1 << 13)
    chunk = 1 << 10
    delta = 2_500
    horizon = 40_000
    queries = ("M4-2", "M5-3")
    g = powerlaw_temporal_graph(n=300, m=6_000 if fast else 12_000,
                                time_span=120_000, seed=7)
    order = np.argsort(g.t, kind="stable")
    src = g.src[order].astype(np.int64)
    dst = g.dst[order].astype(np.int64)
    t = g.t[order].astype(np.int64)
    B = len(src) // n_epochs

    def batches():
        for e in range(n_epochs):
            lo = e * B
            hi = len(src) if e == n_epochs - 1 else lo + B
            yield src[lo:hi], dst[lo:hi], t[lo:hi]

    # -- cold leg: unpadded snapshot + cleared caches per epoch ----------
    cold_times, cold_res = [], []
    store = StreamStore(horizon=horizon, pad=False)
    for bs, bd, bt in batches():
        store.ingest(bs, bd, bt)
        clear_engine_caches()
        t0 = time.perf_counter()
        ep = store.advance()
        cold_res.append([estimate(ep.graph, get_motif(mn), delta, k, seed=0,
                                  chunk=chunk) for mn in queries])
        cold_times.append(time.perf_counter() - t0)

    # -- warm leg: resident streaming session over padded snapshots ------
    clear_engine_caches()
    warm_times, warm_res = [], []
    with StreamingSession(config=EstimateConfig(chunk=chunk),
                          horizon=horizon) as ss:
        qids = [ss.subscribe(StandingQuery(mn, delta, k, seed=0))
                for mn in queries]
        for bs, bd, bt in batches():
            ss.ingest(bs, bd, bt)
            t0 = time.perf_counter()
            er = ss.advance()
            warm_times.append(time.perf_counter() - t0)
            warm_res.append([er.results[q] for q in qids])

    identical = all(
        a.estimate == b.estimate and a.cnt2_sum == b.cnt2_sum
        for ra, rb in zip(cold_res, warm_res) for a, b in zip(ra, rb))
    # steady state: skip the warm-up epochs whose buckets differ from the
    # horizon-limited steady shapes (first 2 of the run)
    steady = slice(2, None)
    cold_s = float(np.mean(cold_times[steady]))
    warm_s = float(np.mean(warm_times[steady]))
    speedup = cold_s / max(warm_s, 1e-9)
    emit("stream", "epochs", "n_epochs", n_epochs)
    emit("stream", "epochs", "identical_results", identical)
    emit("stream", "epochs", "cold_epoch_s", f"{cold_s:.3f}")
    emit("stream", "epochs", "warm_epoch_s", f"{warm_s:.3f}")
    emit("stream", "epochs", "speedup", f"{speedup:.2f}")
    record = dict(
        n_epochs=n_epochs, queries=list(queries), k=k, delta=delta,
        horizon=horizon, chunk=chunk,
        graph=dict(n=g.n, m=g.m, time_span=g.time_span),
        cold_epoch_times_s=[round(x, 3) for x in cold_times],
        warm_epoch_times_s=[round(x, 3) for x in warm_times],
        cold_epoch_s=round(cold_s, 3),
        warm_epoch_s=round(warm_s, 3),
        speedup=round(speedup, 2),
        identical_results=bool(identical),
        methodology=("one edge stream replayed through both legs with the "
                     "same sliding horizon; cold = per epoch, unpadded "
                     "snapshot + engine/preprocess caches cleared + "
                     "one-shot estimates (in-process model of a fresh "
                     "process per advance; XLA-internal reuse may still "
                     "flatter the cold leg); warm = "
                     "resident StreamingSession over power-of-two padded "
                     "snapshots (standing queries, compiled window "
                     "programs and preprocess traces re-hit across "
                     "epochs).  Means over the steady-state epochs "
                     "(index >= 2); per-epoch estimates bit-identical "
                     "between legs."),
    )
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_stream.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=2)
    print(f"# wrote {path}", flush=True)


def multimotif_bench(fast: bool):
    """Shared-sample tree-cohort serving: 12 standing queries over one
    live stream, shared-stream vs per-job sampling.  Writes
    BENCH_multimotif.json.

    All 12 motifs extend the ``0-1,1-2`` wedge, and every query is
    PINNED to the wedge spanning tree over its first two edges via the
    ``Request.tree=``/``wts=`` injection seam — the odeN deployment
    pattern: pick the shared structure once, instead of letting per-
    snapshot min-W selection scatter structurally-equivalent queries
    across trees (which it does on partial-stream snapshots).  The
    pinned trees share one structural signature by construction, so the
    engine fuses all 12 into a single tree-cohort:

    * shared  — all 12 standing queries re-estimated per epoch in one
      ``submit_many`` batch: ONE sampled tree-instance stream per
      window, 12 motif-count lanes over it;
    * per-job — the same 12 queries served one at a time against the
      same epoch snapshot (12 cohorts of one: the pre-cohort engine's
      sampling cost, with compiled programs still warm — the baseline
      pays only the redundant sampling + dispatches, not compiles).

    Both legs must report bit-identical per-epoch estimates (cohort
    membership is invisible in the numbers).  Headline: credited
    samples/s multiplier over the steady-state epochs; the acceptance
    bar is shared >= 3x per-job.
    """
    import json
    import os

    from repro.api import EstimateConfig, Request, Session
    from repro.core import engine as engine_mod
    from repro.core.motif import get_motif
    from repro.core.spanning_tree import build_tree, tree_signature
    from repro.core.weights import preprocess
    from repro.graphs import powerlaw_temporal_graph
    from repro.stream import StreamStore

    motifs = ("0-1,1-2", "0-1,1-2,1-0", "0-1,1-2,1-2",
              "0-1,1-2,1-0,1-0", "0-1,1-2,1-0,1-2", "0-1,1-2,1-0,0-2",
              "0-1,1-2,1-2,1-0", "0-1,1-2,1-2,1-2", "0-1,1-2,1-2,2-0",
              "0-1,1-2,2-0,0-1", "0-1,1-2,2-0,2-1",
              "0-1,1-2,1-0,1-0,1-0")
    delta = 2_500
    horizon = 40_000
    k, chunk = ((1 << 10), (1 << 9)) if fast else ((1 << 11), (1 << 10))
    ck_every = 2
    n_epochs = 3 if fast else 5
    reps = 3 if fast else 6

    # every motif's first two edges are the wedge 0-1,1-2: root the
    # shared tree over that subset the way the planner roots the wedge
    # itself, so all 12 pinned trees carry ONE structural signature
    trees = [build_tree(get_motif(mn), (0, 1),
                        root_edge=1) for mn in motifs]
    sig0 = tree_signature(trees[0])
    assert all(tree_signature(tr) == sig0 for tr in trees[1:])

    g = powerlaw_temporal_graph(n=300, m=6_000, time_span=120_000, seed=7)
    order = np.argsort(g.t, kind="stable")
    src = g.src[order].astype(np.int64)
    dst = g.dst[order].astype(np.int64)
    t = g.t[order].astype(np.int64)
    B = len(src) // n_epochs

    clear_engine_caches()
    store = StreamStore(horizon=horizon)
    cfg = EstimateConfig(chunk=chunk, checkpoint_every=ck_every, seed=0)
    sh_times, pj_times = [], []
    identical = True
    cohort_stats = None
    for e in range(n_epochs):
        lo = e * B
        hi = len(src) if e == n_epochs - 1 else lo + B
        store.ingest(src[lo:hi], dst[lo:hi], t[lo:hi])
        ep = store.advance()
        # one preprocess serves every pinned query on this snapshot (the
        # weight DP reads only signature fields)
        dev = ep.graph.device_arrays()
        wts0 = preprocess(ep.graph, trees[0], delta, dev=dev)
        session = Session(ep.graph, cfg, dev=dev)

        def reqs():
            return [Request(motif=get_motif(mn), delta=delta, k=k,
                            tree=tr, wts=wts0)
                    for mn, tr in zip(motifs, trees)]

        # warm both legs (first-epoch compiles), untimed
        shared = [h.result() for h in session.submit_many(reqs())]
        perjob = [session.submit_many([r])[0].result() for r in reqs()]
        identical &= all(
            a.estimate == b.estimate and a.cnt2_sum == b.cnt2_sum
            and a.valid == b.valid for a, b in zip(shared, perjob))
        if e == 0:
            continue  # compile epoch: steady-state timings start at 1
        engine_mod.STATS.reset()
        t0 = time.perf_counter()
        for _ in range(reps):
            for h in session.submit_many(reqs()):
                h.result()
        sh_times.append((time.perf_counter() - t0) / reps)
        cohort_stats = dict(
            tree_cohorts=engine_mod.STATS.tree_cohorts // reps,
            motifs_per_cohort=engine_mod.STATS.motifs_per_cohort,
            samples_shared=engine_mod.STATS.samples_shared // reps)
        t0 = time.perf_counter()
        for _ in range(reps):
            for r in reqs():
                session.submit_many([r])[0].result()
        pj_times.append((time.perf_counter() - t0) / reps)

    sh_s = float(np.mean(sh_times))
    pj_s = float(np.mean(pj_times))
    served = len(motifs) * k                    # samples credited per epoch
    sps_shared = served / max(sh_s, 1e-9)
    sps_perjob = served / max(pj_s, 1e-9)
    multiplier = sps_shared / max(sps_perjob, 1e-9)
    emit("multimotif", "epochs", "n_queries", len(motifs))
    emit("multimotif", "epochs", "identical_results", identical)
    emit("multimotif", "epochs", "shared_epoch_s", f"{sh_s:.4f}")
    emit("multimotif", "epochs", "perjob_epoch_s", f"{pj_s:.4f}")
    emit("multimotif", "epochs", "samples_per_s_shared", f"{sps_shared:.0f}")
    emit("multimotif", "epochs", "samples_per_s_perjob", f"{sps_perjob:.0f}")
    emit("multimotif", "epochs", "multiplier", f"{multiplier:.2f}")
    emit("multimotif", "epochs", "motifs_per_cohort",
         cohort_stats["motifs_per_cohort"])
    record = dict(
        n_queries=len(motifs), motifs=list(motifs), k=k, delta=delta,
        horizon=horizon, chunk=chunk, checkpoint_every=ck_every,
        n_epochs=n_epochs, reps_per_epoch=reps,
        graph=dict(n=g.n, m=g.m, time_span=g.time_span),
        shared_epoch_times_s=[round(x, 4) for x in sh_times],
        perjob_epoch_times_s=[round(x, 4) for x in pj_times],
        shared_epoch_s=round(sh_s, 4),
        perjob_epoch_s=round(pj_s, 4),
        samples_per_s_shared=round(sps_shared, 1),
        samples_per_s_perjob=round(sps_perjob, 1),
        multiplier=round(multiplier, 2),
        cohort_stats=cohort_stats,
        identical_results=bool(identical),
        methodology=("one edge stream replayed epoch by epoch through a "
                     "sliding-horizon StreamStore; each steady epoch "
                     "re-estimates 12 standing wedge-family queries, "
                     "each pinned (Request.tree/wts injection) to the "
                     "wedge tree over its first two edges — one tree "
                     "signature, one shared Weights.  shared = one "
                     "submit_many batch (one tree-cohort: one sampled "
                     "instance stream, 12 count lanes); per-job = the "
                     "same queries one at a time (12 single-job cohorts "
                     "= per-job sampling), programs warm in both legs so "
                     "the delta is redundant sampling + dispatch, not "
                     "compiles.  Epoch 0 is the untimed compile epoch; "
                     "times are means over reps and steady epochs; "
                     "samples/s credits each query's k against the leg's "
                     "wall-clock.  Per-epoch estimates are asserted "
                     "bit-identical between legs (the cohort determinism "
                     "contract)."),
    )
    assert identical, "shared-stream leg diverged from per-job estimates"
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_multimotif.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=2)
    print(f"# wrote {path}", flush=True)


def resilience_bench(fast: bool):
    """Cost of the resilience layer (repro.resilience).  Writes
    BENCH_resilience.json.

    * WAL replay vs cold rebuild — recovering a streaming store from its
      write-ahead log (``StreamStore.recover``: replay ingest batches +
      epoch manifests, NO snapshot materialization) vs rebuilding by
      re-running the original command stream from upstream (every
      ``advance`` re-materializes its snapshot — what a crash without a
      WAL would cost, assuming the upstream even kept the edges);
    * fault-free seam overhead — ``fire()`` ns/call with no injector
      installed, and a warm ``estimate()`` with vs without a no-op
      ``FaultInjector`` resident.  The retry/ladder/deadline machinery is
      always on, so the "with" leg measures the whole resilient dispatch
      path; the acceptance bar is ~zero overhead (< 5%).
    """
    import json
    import os
    import tempfile

    from repro.core.estimator import estimate
    from repro.core.motif import get_motif
    from repro.graphs import powerlaw_temporal_graph
    from repro.resilience import FaultInjector, FaultSpec
    from repro.resilience.faultinject import fire
    from repro.stream import StreamStore

    # -- WAL replay vs cold rebuild --------------------------------------
    rng = np.random.default_rng(0)
    n_batches = 48 if fast else 160
    bsz = 2_000
    nv = 500
    horizon = 200_000
    advance_every = 8
    batches = []
    tbase = 0
    for _ in range(n_batches):
        s = rng.integers(0, nv, bsz)
        d = (s + rng.integers(1, nv, bsz)) % nv
        tt = np.sort(rng.integers(tbase, tbase + 10_000, bsz))
        tbase += 5_000
        batches.append((s, d, tt))

    def drive(store):
        for i, (s, d, tt) in enumerate(batches):
            store.ingest(s, d, tt)
            if (i + 1) % advance_every == 0:
                store.advance()
        return store

    wal_path = os.path.join(tempfile.mkdtemp(prefix="bench_wal_"),
                            "bench.wal")
    logged = drive(StreamStore.recover(wal_path, horizon=horizon))
    wal_mb = logged.wal.offset / 2 ** 20

    t0 = time.perf_counter()
    replayed = StreamStore.recover(wal_path, horizon=horizon)
    t_replay = time.perf_counter() - t0

    t0 = time.perf_counter()
    rebuilt = drive(StreamStore(horizon=horizon))
    t_rebuild = time.perf_counter() - t0

    def fp(st):
        return (st.epoch, st.buffered, st.retained, st.stats.ingested)

    assert fp(replayed) == fp(logged) == fp(rebuilt), \
        (fp(replayed), fp(logged), fp(rebuilt))
    replay_speedup = t_rebuild / max(t_replay, 1e-9)
    emit("resilience", "wal", "records", logged.wal.records)
    emit("resilience", "wal", "wal_mb", f"{wal_mb:.2f}")
    emit("resilience", "wal", "replay_s", f"{t_replay:.3f}")
    emit("resilience", "wal", "cold_rebuild_s", f"{t_rebuild:.3f}")
    emit("resilience", "wal", "replay_speedup", f"{replay_speedup:.2f}")

    # -- fire() seam: ns/call with no injector ---------------------------
    n_fire = 200_000
    t0 = time.perf_counter()
    for _ in range(n_fire):
        fire("engine.dispatch", tag="xla")
    fire_ns = 1e9 * (time.perf_counter() - t0) / n_fire
    emit("resilience", "seam", "fire_ns_per_call", f"{fire_ns:.0f}")

    # -- warm estimate with vs without a resident no-op injector ---------
    g = powerlaw_temporal_graph(n=300, m=4_000, time_span=60_000, seed=7)
    m = get_motif("M5-3")
    k = 1 << (12 if fast else 14)
    chunk, ck = 1 << 10, 2
    reps = 3 if fast else 6

    def leg():
        t0 = time.perf_counter()
        for _ in range(reps):
            r = estimate(g, m, 3_000, k, seed=0, chunk=chunk,
                         checkpoint_every=ck)
        return (time.perf_counter() - t0) / reps, r

    leg()                                         # warm both caches fully
    t_bare, r_bare = leg()
    with FaultInjector([FaultSpec("no.such.site", hits=None)]):
        t_inj, r_inj = leg()
    assert r_bare.estimate == r_inj.estimate      # injector changed nothing
    overhead_pct = 100.0 * (t_inj - t_bare) / max(t_bare, 1e-9)
    emit("resilience", "overhead", "warm_estimate_s", f"{t_bare:.4f}")
    emit("resilience", "overhead", "warm_estimate_injected_s",
         f"{t_inj:.4f}")
    emit("resilience", "overhead", "fault_free_overhead_pct",
         f"{overhead_pct:.2f}")

    record = dict(
        wal=dict(records=logged.wal.records, wal_mb=round(wal_mb, 2),
                 n_batches=n_batches, batch_edges=bsz,
                 advance_every=advance_every, horizon=horizon,
                 replay_s=round(t_replay, 3),
                 cold_rebuild_s=round(t_rebuild, 3),
                 replay_speedup=round(replay_speedup, 2)),
        seam=dict(fire_ns_per_call=round(fire_ns, 1)),
        overhead=dict(warm_estimate_s=round(t_bare, 4),
                      warm_estimate_injected_s=round(t_inj, 4),
                      fault_free_overhead_pct=round(overhead_pct, 2),
                      reps=reps, k=k),
        methodology=("wal: one synthetic edge stream driven through a "
                     "WAL-attached StreamStore (ingest batches + periodic "
                     "advances); replay = StreamStore.recover on the "
                     "resulting log (no snapshot materialization), cold "
                     "rebuild = re-running the identical command stream "
                     "with full epoch snapshots, both verified to land on "
                     "the same store fingerprint.  overhead: warm "
                     "estimate() reps with vs without a resident no-op "
                     "FaultInjector (the retry/ladder/deadline path is "
                     "always active; results bit-identical).  The "
                     "overhead delta is noise-dominated at these "
                     "runtimes — the acceptance bar is |overhead| small, "
                     "not its sign."),
    )
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_resilience.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=2)
    print(f"# wrote {path}", flush=True)


def gateway_bench(fast: bool):
    """The gateway's three pillars, costed (repro.gateway).  Writes
    BENCH_gateway.json.

    * overlap — a two-tenant request burst through the gateway wire loop
      (intake/emit threads overlap the dispatcher; each tenant's burst
      fuses into one coalescing window) vs the same burst served
      serialized: one request at a time, each its own drain;
    * tenancy — marginal cold-cost of tenant N+1: stream tenants whose
      padded snapshots land in the SAME buckets re-hit the pool's
      compiled window programs (advance cost ~ preprocessing alone),
      where a different-bucket tenant pays the full trace again;
    * witnesses — warm per-request cost at ``witnesses=0`` (must pin to
      the no-capture path: zero witness dispatches, ~zero overhead vs
      the count-only baseline) and at ``witnesses=8`` (the capture
      price), counts bit-identical across all legs.
    """
    import io
    import json
    import os

    from repro.api import EstimateConfig, Request, Session
    from repro.core import engine
    from repro.gateway import GatewayState, Work
    from repro.stream import StandingQuery

    delta = 2_000
    chunk, ck_every = 1 << 10, 2
    k = 1 << (11 if fast else 13)
    cfg = EstimateConfig(chunk=chunk, checkpoint_every=ck_every,
                         coalesce_window_s=60.0)
    spec_a = "powerlaw:n=300,m=4000,time_span=60000,seed=7"
    spec_b = "fintxn:n_accounts=300,m=4000,time_span=60000,seed=3"

    # -- overlap: 2-tenant burst, gateway vs serialized drains -----------
    from repro.gateway.serve import _Gateway

    # same motif, different seeds: a confidence fan-out per tenant —
    # the dispatcher batches each tenant's run into ONE coalescing
    # window where the requests share a plan key and fuse into one
    # vmapped dispatch; serialized serving drains them one by one
    burst = [(t, "M5-3", k, seed) for seed in range(6) for t in ("a", "b")]
    out = io.StringIO()
    gw = _Gateway(cfg, out, max_tenants=4, quota=64, wal_dir=None,
                  mesh=None)
    try:
        for t, spec in (("a", spec_a), ("b", spec_b)):
            gw.sched.submit_control(Work(
                "open_tenant", dict(cmd="open_tenant", tenant=t,
                                    graph=spec)))

        def run_burst():
            t0 = time.perf_counter()
            for i, (t, mn, kk, seed) in enumerate(burst):
                gw.sched.submit(t, Work("request", dict(
                    tenant=t, id=i, motif=mn, delta=delta, k=kk,
                    seed=seed), tenant=t))
            t_submit = time.perf_counter() - t0   # intake-blocked time
            gw.sched.barrier()                    # all drains answered
            return t_submit, time.perf_counter() - t0

        run_burst()                             # warm (opens fold in here)
        t_intake, t_gateway = run_burst()
        assert gw.served == 2 * len(burst)
    finally:
        gw.sched.stop()
        gw.state.close_all()
        gw.emitter.close()
    resp = {o["id"]: o for o in map(json.loads, out.getvalue().splitlines())
            if o.get("id") is not None and not o.get("progress")}

    from repro.launch.estimate import parse_graph
    graphs = {"a": parse_graph(spec_a), "b": parse_graph(spec_b)}
    sessions = {t: Session(g, cfg) for t, g in graphs.items()}
    try:
        def run_serialized():
            t0 = time.perf_counter()
            res = []
            for (t, mn, kk, seed) in burst:     # one drain per request
                h = sessions[t].submit(Request(mn, delta, kk, seed=seed))
                res.append(h.result())
            return time.perf_counter() - t0, res

        run_serialized()                        # warm
        t_serial, solo = run_serialized()
    finally:
        for s in sessions.values():
            s.close()
    identical = all(resp[i]["estimate"] == r.estimate
                    for i, r in enumerate(solo))
    # a serialized client is intake-blocked for the WHOLE burst (each
    # submit waits on the previous drain); gateway intake just enqueues
    overlap_factor = t_serial / max(t_intake, 1e-9)
    overlap_speedup = t_serial / max(t_gateway, 1e-9)
    emit("gateway", "overlap", "burst_requests", len(burst))
    emit("gateway", "overlap", "intake_blocked_s", f"{t_intake:.5f}")
    emit("gateway", "overlap", "completion_s", f"{t_gateway:.3f}")
    emit("gateway", "overlap", "serialized_s", f"{t_serial:.3f}")
    emit("gateway", "overlap", "intake_unblock_factor",
         f"{overlap_factor:.0f}")
    emit("gateway", "overlap", "throughput_ratio", f"{overlap_speedup:.2f}")
    emit("gateway", "overlap", "identical_results", identical)

    # -- tenancy: marginal cold-cost of tenant N+1 -----------------------
    nv, ne = 300, 4_000

    def edge_batch(seed, n_edges=ne):
        r = np.random.default_rng(seed)
        s = r.integers(0, nv, n_edges)
        return (s, (s + r.integers(1, nv, n_edges)) % nv,
                np.sort(r.integers(0, 60_000, n_edges)))

    clear_engine_caches()
    state = GatewayState(cfg, max_tenants=8)
    advance_s = {}
    try:
        for i, name in enumerate(("t0", "t1", "t2")):   # same buckets
            tn = state.open_tenant(name, stream=True)
            tn.stream.subscribe(StandingQuery("M5-3", delta, k, seed=0))
            tn.stream.ingest(*edge_batch(i))
            t0 = time.perf_counter()
            tn.stream.advance()
            advance_s[name] = time.perf_counter() - t0
        # 4x the edges -> different padded buckets -> full retrace
        tn = state.open_tenant("big", stream=True)
        tn.stream.subscribe(StandingQuery("M5-3", delta, k, seed=0))
        tn.stream.ingest(*edge_batch(9, 4 * ne))
        t0 = time.perf_counter()
        tn.stream.advance()
        advance_s["big"] = time.perf_counter() - t0
    finally:
        state.close_all()
    marginal = (advance_s["t1"] + advance_s["t2"]) / 2
    cold_ratio = marginal / max(advance_s["t0"], 1e-9)
    emit("gateway", "tenancy", "tenant0_cold_s", f"{advance_s['t0']:.3f}")
    emit("gateway", "tenancy", "same_bucket_marginal_s", f"{marginal:.3f}")
    emit("gateway", "tenancy", "same_bucket_cold_ratio",
         f"{cold_ratio:.3f}")
    emit("gateway", "tenancy", "diff_bucket_s", f"{advance_s['big']:.3f}")

    # -- witnesses: n=0 pinned to the no-capture path --------------------
    g = graphs["a"]
    reps = 3 if fast else 6

    def leg(n_wit):
        with Session(g, cfg) as s:
            s.submit_many([Request("M5-3", delta, k, seed=0,
                                   witnesses=n_wit)])[0].result()  # warm
            engine.STATS.reset()
            t0 = time.perf_counter()
            for _ in range(reps):
                h, = s.submit_many([Request("M5-3", delta, k, seed=0,
                                            witnesses=n_wit)])
                r = h.result()
            return (time.perf_counter() - t0) / reps, r, \
                engine.STATS.witness_dispatches
    t_w0, r_w0, disp0 = leg(0)
    t_w8, r_w8, disp8 = leg(8)
    assert disp0 == 0 and disp8 > 0             # n=0 never dispatches
    assert r_w0.estimate == r_w8.estimate       # capture never moves bits
    # witnesses=0 IS the pre-feature count path (Request defaults to 0,
    # zero witness dispatches) — the overhead pin is structural
    w0_overhead_pct = 0.0
    capture_pct = 100.0 * (t_w8 - t_w0) / max(t_w0, 1e-9)
    emit("gateway", "witness", "warm_w0_s", f"{t_w0:.4f}")
    emit("gateway", "witness", "warm_w8_s", f"{t_w8:.4f}")
    emit("gateway", "witness", "w0_witness_dispatches", disp0)
    emit("gateway", "witness", "capture_overhead_pct", f"{capture_pct:.2f}")

    record = dict(
        overlap=dict(burst_requests=len(burst), k=k,
                     intake_blocked_s=round(t_intake, 5),
                     completion_s=round(t_gateway, 3),
                     serialized_s=round(t_serial, 3),
                     intake_unblock_factor=round(overlap_factor),
                     throughput_ratio=round(overlap_speedup, 2),
                     identical_results=bool(identical)),
        tenancy=dict(tenant0_cold_s=round(advance_s["t0"], 3),
                     same_bucket_marginal_s=round(marginal, 3),
                     same_bucket_cold_ratio=round(cold_ratio, 3),
                     diff_bucket_s=round(advance_s["big"], 3),
                     edges_per_tenant=ne),
        witness=dict(warm_w0_s=round(t_w0, 4), warm_w8_s=round(t_w8, 4),
                     w0_witness_dispatches=int(disp0),
                     w8_witness_dispatches=int(disp8),
                     w0_overhead_pct=w0_overhead_pct,
                     capture_overhead_pct=round(capture_pct, 2),
                     reps=reps),
        methodology=("overlap: a 12-request 2-tenant seed fan-out "
                     "(same motif, seeds 0..5 per tenant) enqueued "
                     "through the gateway scheduler on resident tenants "
                     "vs the same burst served one-request-per-drain on "
                     "resident Sessions, both warm, bit-identical.  "
                     "intake_blocked_s is the client-visible submission "
                     "latency: gateway intake only enqueues (the "
                     "dispatcher drains behind it, each tenant's burst "
                     "fused into one coalescing window) where the "
                     "serialized client is blocked for the whole burst; "
                     "completion vs serialized time is throughput — "
                     "~parity on one device, since both are "
                     "compute-bound on the same drains.  tenancy: stream "
                     "tenants with "
                     "same-size ingests present the same padded snapshot "
                     "buckets, so tenant N+1's advance re-hits the "
                     "pool's compiled window programs — its marginal "
                     "cost is preprocessing alone; the 4x-edges tenant "
                     "lands in different buckets and pays the full "
                     "trace.  witness: warm single-request reps at "
                     "witnesses=0 vs witnesses=8 — n=0 is pinned to the "
                     "no-capture path (zero witness dispatches, no "
                     "overhead source), n=8 prices the reservoir "
                     "dispatch; counts bit-identical."),
    )
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_gateway.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=2)
    print(f"# wrote {path}", flush=True)


BENCHES = dict(t3=t3_speed, t4=t4_accuracy, t5=t5_small, t6=t6_ablation,
               t7=t7_trees, f6=f6_sweep, perf=perf_micro, batch=batch_bench,
               sampler=sampler_bench, engine=engine_bench, serve=serve_bench,
               stream=stream_bench, multimotif=multimotif_bench,
               resilience=resilience_bench, gateway=gateway_bench)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="small graph + fewer motifs (CI-sized)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--suite", default=None,
                    help="alias for --only (e.g. --suite batch)")
    args = ap.parse_args()
    sel = args.suite or args.only
    names = sel.split(",") if sel else list(BENCHES)
    t0 = time.perf_counter()
    for name in names:
        print(f"# --- {name} ---", flush=True)
        BENCHES[name](args.fast)
    print(f"# done in {time.perf_counter() - t0:.1f}s")


if __name__ == "__main__":
    main()
