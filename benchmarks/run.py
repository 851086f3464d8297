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


BENCHES = dict(t3=t3_speed, t4=t4_accuracy, t5=t5_small, t6=t6_ablation,
               t7=t7_trees, f6=f6_sweep)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="small graph + fewer motifs (CI-sized)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--suite", default=None,
                    help="alias for --only (e.g. --suite t6)")
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
