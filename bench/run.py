#!/usr/bin/env python3
"""One run of one benchmark cell on the chip, through the served path.

    python3 bench/run.py --workload aml.batch --seed 7 --seconds 30 --trace 0

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<config>.json``: graph generator parameters, standing
(motif, delta) pairs, the motifs' edges) and a traffic mix
(``bench/traffic/<mix>.json``); its limits for ``correct`` are in
``bench/limits/<cell>.json`` and each metric's reader in
``bench/metrics/<metric>.py``.  A run:

1. generates the cell's graph from ``--seed`` (``bench/synth.py``;
   cached as ``bench/data/<config>.<seed>.npz``);
2. starts the gateway, ``python -m repro.launch.estimate --serve
   --gateway``, as a child with ``JAX_PLATFORMS=tpu`` and the mix's
   server flags, and with ``--mesh <chips>`` where the cell asks for
   more than one chip; the parent never imports jax while the child
   lives;
3. loads the graph into a stream tenant (``ingest``, one ``advance``),
   plans the standing pairs and
   warms every window shape the mix uses (one fused dispatch of 1 to
   ``clients`` request streams per motif) -- all of this is ``setup_s``;
4. drives the mix's closed-loop clients for ``--seconds``, awaits the
   requests still out, reads the device's peak memory and stops the
   child;
5. checks every answer of the window against the plain reference
   (``bench/check.py``) and prints one JSON line: the end-to-end metrics
   with ``--trace 0``; with ``--trace 1`` (telemetry at ``trace``, a
   profiler capture of the first engine windows) the per-layer metrics,
   ``busy_s``/``window_s`` and a breakdown.  The window's answers go to
   ``<out>/answers.json`` (``bench/out`` unless the caller names
   another directory).

No chip, fewer chips than the cell asks for, or a served mesh of another
size: exit 1, no result.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import math
import os
import shutil
import signal
import sys
import time
from dataclasses import dataclass, field

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import check as checker  # noqa: E402
from bench import reference, synth, traffic  # noqa: E402
from bench import trace as tracing  # noqa: E402
from bench.server import (Server, ServerError, child_env,  # noqa: E402
                          served_mesh)

#: whole-run cap for the child (the first run of a checkout compiles)
RUN_DEADLINE_S = 1150.0
TENANT = "bench"
#: edges per ingest line when loading the graph
INGEST_BATCH = 65536


class BenchError(RuntimeError):
    """The run cannot produce a result (no chip, server failure)."""


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    """A workload entry with everything the harness finds by its names."""
    name: str
    chips: int
    config_name: str
    config: dict
    mix: dict
    limits: dict
    metrics: dict = field(default_factory=dict)   # name -> BENCHMARK entry

    @classmethod
    def load(cls, name: str, trace: bool, root: str = ROOT) -> "Cell":
        bench = _json(os.path.join(root, "BENCHMARK.json"))
        w = next((w for w in bench["workloads"] if w["name"] == name), None)
        if w is None:
            raise BenchError(f"no workload {name!r} in BENCHMARK.json")
        cfg = next(c for c in bench["configs"] if c["name"] == w["config"])
        pick = bench["per_layer" if trace else "end_to_end"]
        metrics = {m["name"]: m for m in pick
                   if name in m.get("workloads", [name])}
        return cls(name=name, chips=int(w["chips"]), config_name=cfg["name"],
                   config=_json(os.path.join(root, cfg["file"])),
                   mix=_json(os.path.join(root, "bench", "traffic",
                                          w["traffic"] + ".json")),
                   limits=_json(os.path.join(root, "bench", "limits",
                                             name + ".json")),
                   metrics=metrics)


def reader(metric: str, root: str = ROOT):
    """The ``read(ctx)`` function of ``bench/metrics/<metric>.py``."""
    path = os.path.join(root, "bench", "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclass
class Context:
    """What the metric readers read: the run's clocks, scrapes, client
    records, trace reduction and check."""
    cell: Cell
    seconds: float
    setup_s: float
    setup_scrape: dict
    window: traffic.Window
    trace: dict | None
    check: dict
    peaks: dict | None = None
    xplane: str | None = None       # the run's profiler capture


def warm(server: Server, cell: Cell, seed: int) -> list:
    """Plan every standing pair and run each window shape the mix uses:
    for each motif, one dispatch of J fused request streams, J = 1 ..
    clients (a burst of J requests written at once lands in one drain;
    ``fused_jobs`` in the replies confirms it, else the burst repeats).
    Returns ``[motif, J, seconds, fused]`` per burst."""
    k = traffic.window_samples(cell.mix)
    n, log = 0, []
    for motif, delta in cell.config["standing"]:
        for J in range(1, int(cell.mix["clients"]) + 1):
            for _ in range(4):
                burst = [dict(tenant=TENANT, id=f"warm.{n + j}", motif=motif,
                              delta=int(delta), k=k,
                              seed=traffic._mix(seed, 9, n + j))
                         for j in range(J)]
                n += J
                t = server.send(*burst)
                replies = [server.wait(("id", b["id"]))[1] for b in burst]
                bad = [r for r in replies if r.get("ok") is not True]
                if bad:
                    server.fail(f"warm-up request failed: {bad[0]}")
                fused = all(r.get("fused_jobs") == J for r in replies)
                log.append([motif, J, time.monotonic() - t, fused])
                if fused:
                    break
    return log


def load_tenant(server: Server, gpath: str, graph: dict) -> None:
    """Open a stream tenant and load the graph as a deployment loads its
    own data through the gateway (which takes no server-side file on the
    wire): ``ingest`` in batches, then one ``advance`` to the snapshot
    that every request of the run is served on."""
    server.call({"cmd": "open_tenant", "tenant": TENANT, "stream": True})
    src, dst, t = synth.load(gpath)
    for lo in range(0, len(t), INGEST_BATCH):
        sl = slice(lo, lo + INGEST_BATCH)
        edges = np.stack([src[sl], dst[sl], t[sl]], axis=1).tolist()
        server.call({"cmd": "ingest", "tenant": TENANT, "edges": edges})
    ep = server.call({"cmd": "advance", "tenant": TENANT})
    if (ep["n"], ep["m"]) != (graph["n"], graph["m"]):
        raise BenchError(f"tenant has n={ep['n']} m={ep['m']}, the "
                         f"configuration n={graph['n']} m={graph['m']}")


def _cache_entries(root: str) -> int:
    """Programs in the persistent compile cache (one ``*-cache`` file
    per compiled program)."""
    d = os.path.join(root, "bench", "cache", "jax")
    return (sum(1 for f in os.listdir(d) if f.endswith("-cache"))
            if os.path.isdir(d) else 0)


def answers_of(window: traffic.Window) -> list:
    """The window's requests as the check reads them."""
    out = []
    for rec in window.records:
        r = rec.reply or {}
        out.append(dict(id=rec.req["id"], motif=rec.req["motif"],
                        delta=rec.req["delta"], k=r.get("k"), W=r.get("W"),
                        estimate=r.get("estimate"), failed=not rec.ok))
    return out


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             root: str = ROOT, out_dir: str | None = None,
             platform: str = "tpu", alter=None, grace: float = 60.0) -> dict:
    """One run; returns the result object (see the module docstring).

    ``out_dir`` (emptied first; default ``bench/out``) takes the run's
    files: the server's log, the profile, the answers.  ``platform`` and
    ``alter`` are the seams the tests drive: the CPU instead of the
    chip, and a function applied to each reply as the clients receive it
    (a fault planted under the timed path)."""
    t_start = time.monotonic()
    if out_dir is None:
        out_dir = os.path.join(root, "bench", "out")
    shutil.rmtree(out_dir, ignore_errors=True)
    gpath = synth.graph_path(os.path.join(root, "bench", "data"),
                             cell.config_name, cell.config["graph"], seed)
    parts = {"graph_s": time.monotonic() - t_start}
    cache_start = _cache_entries(root)
    srv = cell.mix["server"]
    flags = ["--chunk", str(srv["chunk"]),
             "--checkpoint-every", str(srv["checkpoint_every"])]
    if cell.chips > 1:
        flags += ["--mesh", str(cell.chips)]
    if trace:
        flags += ["--profile-dir", os.path.join(out_dir, "profile")]
    os.makedirs(out_dir)
    server = Server(root, flags, child_env(root, platform,
                                           "trace" if trace else "metrics"),
                    deadline=t_start + RUN_DEADLINE_S,
                    log=os.path.join(out_dir, "server.err"))
    if alter is not None:
        wait = server.wait

        def altered(key, until=None):
            t, r = wait(key, until)
            return t, (alter(r) if key[0] == "id" else r)
        server.wait = altered
    try:
        dev = server.call({"cmd": "health"})["device"]
        if dev["platform"] != platform or dev["count"] < cell.chips:
            raise BenchError(f"server computes on {dev}; the cell needs "
                             f"{cell.chips} {platform} device(s)")
        mesh = served_mesh(server.wait_banner())
        if (math.prod(mesh.values()) if mesh else 1) != cell.chips:
            raise BenchError(f"server serves on mesh {mesh}; the cell "
                             f"needs {cell.chips} chip(s)")
        t = time.monotonic()
        load_tenant(server, gpath, cell.config["graph"])
        parts["load_s"] = time.monotonic() - t
        t = time.monotonic()
        parts["warm"] = warm(server, cell, seed)
        parts["warm_s"] = time.monotonic() - t
        setup_scrape = server.stages()
        cache_setup = _cache_entries(root)
        if trace:
            server.call({"cmd": "profile",
                         "windows": int(cell.mix["profile_windows"])})
        m0 = server.stages()
        setup_s = time.monotonic() - t_start
        reqs = traffic.Requests(cell.mix, cell.config["standing"], seed)
        window = traffic.run_closed_loop(server, TENANT, reqs,
                                         int(cell.mix["clients"]), seconds,
                                         grace=grace)
        m1 = server.stages()
        dev = server.call({"cmd": "health"})["device"]
        server.close()
        xplane = (tracing.find_xplane(os.path.join(out_dir, "profile"))
                  if trace else None)
    except (ServerError, TimeoutError, OSError) as e:
        raise BenchError(f"{type(e).__name__}: {e}") from e
    finally:
        server.kill()
    compiles = _cache_entries(root) - cache_setup
    t = time.monotonic()
    g = reference.Graph(*synth.load(gpath))
    answers = answers_of(window)
    with open(os.path.join(out_dir, "answers.json"), "w") as f:
        json.dump(answers, f)
    verdict = checker.check(answers, cell.config, g, cell.limits, seed)
    parts["check_s"] = time.monotonic() - t
    red = None
    if xplane is not None:
        red = tracing.reduce_trace(tracing.load_planes(xplane))
    from bench.peaks import peaks
    ctx = Context(cell=cell, seconds=seconds, setup_s=setup_s,
                  setup_scrape=setup_scrape,
                  window=window, trace=red, check=verdict,
                  peaks=peaks(dev["kind"]) if platform == "tpu" else None,
                  xplane=xplane)
    metrics = {}
    for name, spec in cell.metrics.items():
        v = reader(name, root)(ctx)
        if v is not None:
            metrics[name] = {"value": v, "unit": spec["unit"]}
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"], "mesh": mesh,
              "memory_peak_bytes": dev["peak_bytes"]}
    result = {"correct": verdict["correct"],
              "attempted": len(window.records),
              "failed": verdict["numbers"]["failed"][0],
              "metrics": metrics, "device": device}
    if trace and red is not None:
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    result["diagnostics"] = {
        "setup_stages": setup_scrape["stage"],
        "compiles_in_setup": cache_setup - cache_start,
        "compiles_in_window": compiles,
        "lru_misses_in_window": (m1["lru"].get("miss", 0)
                                 - m0["lru"].get("miss", 0)),
        "client_late_s": window.late,
        "trace_devices": red["devices"] if red else None,
        **parts, "info": verdict["info"]}
    result["check"] = {k: {"value": v, "limit": lim}
                       for k, (v, lim) in verdict["numbers"].items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # a run ended from outside still stops its server (the finally below)
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    try:
        cell = Cell.load(args.workload, bool(args.trace))
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except (BenchError, OSError, KeyError, ValueError) as e:
        print(f"bench: FAILED: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    for name, c in result["check"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result, default=_plain), flush=True)
    return 0


def _plain(x):
    """numpy scalars in the diagnostics, as plain numbers."""
    return x.item() if hasattr(x, "item") else str(x)


if __name__ == "__main__":
    sys.exit(main())
