"""TIMEST estimation launcher.

    PYTHONPATH=src python -m repro.launch.estimate \
        --graph powerlaw:n=2000,m=40000 --motif M5-3 --delta 5000 \
        --k 1048576 --checkpoint /tmp/timest.ckpt

Batched serving mode — comma lists fan out into the full cross product
and run through the shared-preprocess ``estimate_many`` engine, which
fuses jobs sharing a plan key into one dispatch per window:

    PYTHONPATH=src python -m repro.launch.estimate \
        --graph powerlaw:n=2000,m=40000 --motif M5-1,M5-3 \
        --delta 2000,5000 --k 262144

Mesh sharding — ``--mesh auto`` (or ``--mesh D``) shards every window's
chunk range over a 1-axis data mesh (``launch.mesh.make_estimator_mesh``)
with bit-identical results; ``--devices N`` forces N virtual host (CPU)
devices first, so a laptop can rehearse the 8-way layout:

    PYTHONPATH=src python -m repro.launch.estimate \
        --graph powerlaw:n=2000,m=40000 --motif M5-3 --delta 5000 \
        --k 1048576 --devices 8 --mesh auto

Serving mode — ``--serve`` answers line-delimited-JSON requests on
stdin with JSON responses on stdout through the gateway loop
(``repro.gateway``; wire format in ``gateway/wire.py``), which keeps
resident sessions (graph upload, preprocess cache, compiled window
programs) alive between requests.  The flags choose the tenant the loop
adopts as ``"default"``, which lines without ``"tenant"`` go to:
``--serve`` builds a session on ``--graph``, ``--serve --stream`` a live
stream, and ``--serve --gateway`` adopts nothing (tenants open over the
wire).  A tenant's consecutive requests fuse like ``estimate_many``
jobs; ``target_rse`` requests grow their budget adaptively:

    printf '%s\\n' '{"id":1,"motif":"M5-3","delta":5000,"k":65536}' \\
                   '{"id":2,"motif":"0-1,1-2,2-0","delta":5000,"k":65536}' \\
      | PYTHONPATH=src python -m repro.launch.estimate \\
          --graph powerlaw:n=2000,m=40000 --serve

``--motif`` (and serve requests) accept inline edge-list specs like
``0-1,1-2,2-0`` (directed edges in pi order) besides catalog names.

Streaming mode — ``--serve --stream`` adopts an EMPTY live graph
(``repro.stream``): clients ingest edge batches, advance epoch
snapshots, and register standing queries over NDJSON (``{"cmd":
"ingest" | "advance" | "subscribe"}``; protocol in ``repro.gateway``).
``--horizon`` sets the sliding retention window.  Offline,
``--stream-replay FILE`` replays a recorded edge list (text/.gz/.npz)
through the same machinery: each ``--replay-batch`` edges ingest as one
batch, every ``--advance-every`` batches an epoch advances and the
``--motif`` x ``--delta`` standing queries re-estimate — per the stream
determinism contract, each printed count is bit-identical to a cold
``estimate()`` on that epoch's snapshot:

    PYTHONPATH=src python -m repro.launch.estimate \\
        --stream-replay data/stream.txt.gz --horizon 100000 \\
        --motif M5-3 --delta 5000 --k 65536 --replay-batch 20000

Graphs: ``powerlaw:...`` / ``er:...`` / ``fintxn:...`` synthetic specs or
a path to an edge-list file.  The chunk loop checkpoints and resumes
(fault tolerance — checkpoints are mesh-shape-free, so a 1-device
checkpoint resumes on an 8-device mesh and vice versa).
``--depsum-backend pallas`` routes weight preprocessing through the fused
interval-weight kernel (exact-int64 XLA fallback on overflow);
``--sampler-backend pallas`` routes sampling through the fused
kernels/tree_sampler kernel (one ``pallas_call`` per chunk, bit-identical
samples; ineligible jobs fall back per job without downgrading fused
siblings).  Both kernels run in CPU interpret mode only: the TPU compiler
refuses them until ROADMAP S2, so the default ``xla`` backends are the
chip path.

The persistent compile cache lives where ``JAX_COMPILATION_CACHE_DIR``
says, else at ``<checkout>/.jax_cache`` (``launch.compile_cache``).
"""
from __future__ import annotations

import argparse


def parse_graph(spec: str):
    from ..graphs import (er_temporal_graph, fintxn_temporal_graph,
                          load_edge_list, powerlaw_temporal_graph)
    if ":" in spec:
        kind, _, args = spec.partition(":")
        kw = {}
        for item in args.split(","):
            if item:
                k, _, v = item.partition("=")
                kw[k] = float(v) if "." in v else int(v)
        fn = dict(powerlaw=powerlaw_temporal_graph, er=er_temporal_graph,
                  fintxn=fintxn_temporal_graph)[kind]
        return fn(**kw)
    return load_edge_list(spec)


def build_mesh(spec: str | None):
    """``--mesh`` value -> Mesh | None ("auto" = every device)."""
    if not spec or spec == "none":
        return None
    from .mesh import make_estimator_mesh
    return make_estimator_mesh(None if spec == "auto" else int(spec))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--graph", default="powerlaw:n=500,m=8000")
    ap.add_argument("--motif", default="M5-3",
                    help="motif name, or comma list for batched serving")
    ap.add_argument("--delta", default="5000",
                    help="window, or comma list for batched serving")
    ap.add_argument("--k", type=int, default=1 << 18)
    ap.add_argument("--chunk", type=int, default=1 << 13)
    ap.add_argument("--checkpoint-every", type=int, default=64,
                    help="chunks per engine window: the granularity of "
                         "dispatches, checkpoints, progress and batch-means "
                         "RSE (counts do not depend on it)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--mesh", default=None,
                    help="shard chunks over a data mesh: 'auto' (all "
                         "devices) or a shard count; results are "
                         "bit-identical to the unsharded run")
    ap.add_argument("--devices", type=int, default=None,
                    help="force this many virtual host (CPU) devices "
                         "before jax initializes — rehearse a multi-"
                         "device mesh on one machine")
    ap.add_argument("--depsum-backend", choices=("xla", "pallas"),
                    default=None, help="weight-preprocess inner loop")
    ap.add_argument("--sampler-backend", choices=("xla", "pallas"),
                    default=None,
                    help="sampling path: fused kernels/tree_sampler "
                         "pallas kernel, or the XLA gather chain "
                         "(bit-identical; pallas falls back to xla "
                         "outside the f32-exact/VMEM envelope)")
    ap.add_argument("--exact", action="store_true",
                    help="also run the exact oracle (slow!)")
    ap.add_argument("--serve", action="store_true",
                    help="persistent serving: answer line-delimited-JSON "
                         "requests on stdin through the gateway loop, "
                         "with a session on --graph as the default "
                         "tenant (see repro.gateway for the protocol)")
    ap.add_argument("--coalesce-window", type=float, default=0.05,
                    help="serve: seconds a submit window stays open so "
                         "concurrent requests can fuse")
    ap.add_argument("--coalesce-max", type=int, default=64,
                    help="serve: max requests per submit window")
    ap.add_argument("--stream", action="store_true",
                    help="with --serve: the default tenant is an EMPTY "
                         "live graph that accepts ingest/advance/subscribe "
                         "verbs (repro.stream; --graph is ignored)")
    ap.add_argument("--gateway", action="store_true",
                    help="with --serve: adopt no default tenant — start "
                         "with an empty pool and open graph/stream "
                         "tenants over the wire (--graph is ignored)")
    ap.add_argument("--max-tenants", type=int, default=8,
                    help="serve: tenant pool capacity (idle-LRU "
                         "eviction past it)")
    ap.add_argument("--tenant-quota", type=int, default=16,
                    help="serve: max pending work items per tenant; "
                         "submits past it answer error_kind=overloaded")
    ap.add_argument("--wal-dir", default=None, metavar="DIR",
                    help="serve: directory for per-tenant WAL files "
                         "(enables '\"wal\": true' stream tenants opened "
                         "over the wire; paths derive from the tenant "
                         "name server-side)")
    ap.add_argument("--stream-replay", default=None, metavar="FILE",
                    help="replay an edge-list file (text/.gz/.npz) as a "
                         "live stream: ingest in batches, advance epochs, "
                         "re-estimate the --motif x --delta standing "
                         "queries per epoch")
    ap.add_argument("--horizon", type=int, default=None,
                    help="stream: sliding retention window in time units "
                         "(edges older than newest-t minus horizon are "
                         "evicted at compaction; default: keep all)")
    ap.add_argument("--replay-batch", type=int, default=65536,
                    help="stream replay: edges per ingest batch")
    ap.add_argument("--advance-every", type=int, default=1,
                    help="stream replay: ingest batches per epoch advance")
    ap.add_argument("--wal", default=None, metavar="PATH",
                    help="with --serve --stream: crash-safe write-ahead "
                         "log; ingest/advance history is fsynced to PATH "
                         "and replayed on restart (torn tail truncated) "
                         "so a killed server resumes bit-identically")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the obs flight recorder as NDJSON to "
                         "PATH at process exit (implies REPRO_OBS=trace; "
                         "works in every mode — see repro.obs)")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="serve modes: enable the 'profile' wire verb — "
                         "jax.profiler traces of the next N engine "
                         "dispatches land under DIR (profiler paths are "
                         "server-side only, never from the wire)")
    args = ap.parse_args()
    if args.stream and not args.serve:
        ap.error("--stream requires --serve (for offline replay use "
                 "--stream-replay FILE)")
    if args.horizon is not None and not (args.stream or args.stream_replay):
        ap.error("--horizon only applies to stream modes (--serve --stream "
                 "or --stream-replay)")
    if args.wal is not None and not (args.serve and args.stream):
        ap.error("--wal requires --serve --stream (the WAL logs the live "
                 "ingest/advance history)")
    if args.gateway and not args.serve:
        ap.error("--gateway requires --serve (it is a serving mode)")
    if args.wal_dir is not None and not args.serve:
        ap.error("--wal-dir requires --serve (it holds the WAL files of "
                 "stream tenants opened over the wire)")
    if args.profile_dir is not None and not args.serve:
        ap.error("--profile-dir requires --serve (the 'profile' verb "
                 "arms the profiler over the wire)")
    from .compile_cache import use_compile_cache
    use_compile_cache()
    if args.serve:
        from .. import obs
        obs.install_compile_listener()      # compile time by stage
    if args.devices:
        from .mesh import force_host_device_count
        force_host_device_count(args.devices)
    if args.trace_out:
        import atexit
        import sys as _sys

        from .. import obs
        if obs.level() < obs.TRACE:
            obs.set_level("trace")       # the flag implies trace recording

        @atexit.register
        def _dump_trace(path=args.trace_out):
            with open(path, "w") as f:
                f.write(obs.RECORDER.export_ndjson())
            print(f"trace: {obs.RECORDER.recorded} spans recorded, "
                  f"{len(obs.RECORDER)} in ring -> {path}",
                  file=_sys.stderr)

    from ..core.estimator import estimate
    from ..core.motif import get_motif, is_motif_spec

    mesh = build_mesh(args.mesh)

    if args.serve:
        import sys

        from ..api import EstimateConfig, Session
        from ..gateway import gateway_serve_loop
        from ..gateway.wire import device_block
        from ..stream import StreamingSession, StreamStore
        cfg = EstimateConfig(chunk=args.chunk, seed=args.seed,
                             checkpoint_every=args.checkpoint_every,
                             coalesce_window_s=args.coalesce_window,
                             coalesce_max_requests=args.coalesce_max,
                             sampler_backend=args.sampler_backend,
                             depsum_backend=args.depsum_backend)
        # the default tenant: a live stream, nothing, or --graph
        if args.stream:
            if args.wal is not None:
                store = StreamStore.recover(args.wal, horizon=args.horizon)
                print(f"WAL {args.wal}: recovered epoch={store.epoch} "
                      f"buffered={store.buffered} "
                      f"ingested={store.stats.ingested}",
                      file=sys.stderr, flush=True)
            else:
                store = StreamStore(horizon=args.horizon)
            adopt = StreamingSession(store=store, config=cfg, mesh=mesh)
            what = f"stream horizon={args.horizon} wal={args.wal}"
        elif args.gateway:
            adopt = None
        else:
            g = parse_graph(args.graph)
            adopt = Session(g, cfg, mesh=mesh)
            what = f"graph n={g.n} m={g.m} span={g.time_span}"
        dv = device_block()
        # stdout is the response stream — logs go to stderr
        print(f"serving GATEWAY  max_tenants={args.max_tenants}  "
              f"quota={args.tenant_quota}  wal_dir={args.wal_dir}  "
              f"mesh={dict(mesh.shape) if mesh is not None else None}  "
              f"device={dv['platform']}:{dv['kind']}x{dv['count']}",
              file=sys.stderr, flush=True)
        if adopt is not None:
            print(f"default tenant: {what}", file=sys.stderr, flush=True)
        try:
            served = gateway_serve_loop(cfg, max_tenants=args.max_tenants,
                                        quota=args.tenant_quota,
                                        wal_dir=args.wal_dir, mesh=mesh,
                                        profile_dir=args.profile_dir,
                                        adopt=adopt)
        finally:
            if adopt is not None:
                adopt.close()
        print(f"served {served} responses", file=sys.stderr)
        return

    if args.stream_replay:
        from ..api import EstimateConfig
        from ..stream import StandingQuery, StreamingSession, replay_epochs
        motifs = ([args.motif] if is_motif_spec(args.motif)
                  else args.motif.split(","))
        deltas = [int(d) for d in str(args.delta).split(",")]
        cfg = EstimateConfig(chunk=args.chunk, seed=args.seed,
                             checkpoint_every=args.checkpoint_every,
                             sampler_backend=args.sampler_backend,
                             depsum_backend=args.depsum_backend)
        with StreamingSession(config=cfg, horizon=args.horizon,
                              mesh=mesh) as ss:
            qids = {ss.subscribe(StandingQuery(m, d, args.k,
                                               seed=args.seed)): (m, d)
                    for m in motifs for d in deltas}
            print(f"replaying {args.stream_replay}  horizon={args.horizon}  "
                  f"batch={args.replay_batch}  queries={len(qids)}")
            for er in replay_epochs(ss, args.stream_replay,
                                    batch_size=args.replay_batch,
                                    advance_every=args.advance_every):
                ep = er.epoch
                print(f"epoch {ep.index}: m={ep.m_real} n={ep.n_real} "
                      f"t=[{ep.t_lo},{ep.t_hi}] evicted={ep.evicted} "
                      f"buckets={ep.buckets} ({er.advance_s:.2f}s)")
                for qid in sorted(er.results):
                    res = er.results[qid]
                    rse = res.rse
                    print(f"  {qids[qid][0]:12s} delta={qids[qid][1]:<8d} "
                          f"C^={res.estimate:12.4g}  "
                          f"rse={'inf' if rse is None else f'{rse:.3f}'}  "
                          f"k={res.k}")
        return

    g = parse_graph(args.graph)

    # an inline DSL motif contains commas itself — treat a --motif that
    # parses as ONE spec as a single motif, not a comma list
    motifs = ([args.motif] if is_motif_spec(args.motif)
              else args.motif.split(","))
    deltas = [int(d) for d in str(args.delta).split(",")]
    print(f"graph: n={g.n} m={g.m} span={g.time_span}  "
          f"motifs={motifs} deltas={deltas}  k={args.k}  "
          f"mesh={mesh.shape if mesh is not None else None}")

    if len(motifs) > 1 or len(deltas) > 1:
        if args.checkpoint:
            raise SystemExit("--checkpoint is per-job and not supported in "
                             "batched mode yet; run jobs singly to resume")
        from ..core.batch import estimate_many
        jobs = [(m, d, args.k) for m in motifs for d in deltas]
        exact_cache: dict = {}
        for res in estimate_many(g, jobs, seed=args.seed, chunk=args.chunk,
                                 checkpoint_every=args.checkpoint_every,
                                 sampler_backend=args.sampler_backend,
                                 backend=args.depsum_backend, mesh=mesh):
            print(f"delta={res.delta}  fused={res.fused_jobs}  "
                  f"{res.summary()}")
            if args.exact:
                from ..core.exact import count_exact
                key = (res.motif, res.delta)
                if key not in exact_cache:
                    exact_cache[key] = count_exact(
                        g, get_motif(res.motif), res.delta)
                c = exact_cache[key]
                err = abs(res.estimate - c) / max(c, 1)
                print(f"  exact={c}  error={100 * err:.2f}%")
        return

    motif = get_motif(motifs[0])
    res = estimate(g, motif, deltas[0], args.k, seed=args.seed,
                   chunk=args.chunk, checkpoint_path=args.checkpoint,
                   checkpoint_every=args.checkpoint_every,
                   sampler_backend=args.sampler_backend,
                   depsum_backend=args.depsum_backend, mesh=mesh)
    print(res.summary())
    print(f"  fail: vmap={res.fail_vmap} delta={res.fail_delta} "
          f"order={res.fail_order} overflow={res.overflow}  "
          f"sampler={res.sampler_backend}"
          + (f" (fallback: {res.fallback_reason})"
             if res.fallback_reason else "")
          + f"  mesh={res.mesh_shape}")
    if args.exact:
        from ..core.exact import count_exact
        c = count_exact(g, motif, deltas[0])
        err = abs(res.estimate - c) / max(c, 1)
        print(f"  exact={c}  error={100 * err:.2f}%")


if __name__ == "__main__":
    main()
