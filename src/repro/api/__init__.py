"""Public session-based TIMEST API — the canonical usage guide.

TIMEST's value proposition is interactive-speed approximate counting,
and real workloads are *streams of related count queries* over one
resident graph (odeN-style multi-motif serving).  This package is the
public surface for that: a long-lived :class:`Session` that keeps the
graph on device, the preprocess cache warm and the compiled window
programs alive between requests, instead of the old one-shot kwargs
sprawl.

Quick start
-----------
::

    from repro.api import EstimateConfig, Request, Session
    from repro.graphs import powerlaw_temporal_graph

    g = powerlaw_temporal_graph(n=2_000, m=40_000, time_span=1_000_000)

    with Session(g, EstimateConfig(chunk=8192)) as s:
        # submits coalesce: requests landing in one window that share a
        # plan key (same spanning tree/weights) fuse into ONE vmapped
        # dispatch per checkpoint window, exactly like estimate_many
        h1 = s.submit(Request("M5-3", delta=50_000, k=1 << 18))
        h2 = s.submit(Request("M5-3", delta=50_000, k=1 << 18, seed=1))
        print(h1.result().summary())

        # inline motif DSL: "u-v" directed edges, comma-separated, in
        # temporal (pi) order — no need to touch the catalog
        h3 = s.submit(Request("0-1,1-2,2-0", delta=50_000, k=1 << 16))

        # progressive results: one snapshot per checkpoint window
        for snap in h3.stream():
            print(f"k={snap.k_done}  C^={snap.estimate:.4g}  "
                  f"rse={snap.rse:.3f}")

        # error-targeted adaptive budget: k grows geometrically until
        # the empirical relative standard error crosses the target
        h4 = s.submit(Request("M5-1", delta=50_000, k=1 << 14,
                              target_rse=0.05, k_max=1 << 22))
        res = h4.result()
        print(res.k, h4.rse)

Multi-motif shared sampling (tree-cohorts)
------------------------------------------
Queries whose chosen spanning trees share a *structural signature*
(``core.spanning_tree.tree_signature``) fuse further: the engine draws
ONE tree-instance sample stream for the whole cohort and scores every
member motif's own count lane against it (the odeN pattern), so N
standing queries on one tree cost ~one sampling pass instead of N.
Wedge-family motifs do this naturally — all of these extend ``0-1,1-2``
and (graph permitting) plan onto its two-edge tree::

    with Session(g, EstimateConfig(chunk=8192)) as s:
        hs = s.submit_many([
            Request("0-1,1-2",         delta=50_000, k=1 << 16),
            Request("0-1,1-2,1-0",     delta=50_000, k=1 << 16),
            Request("0-1,1-2,1-2,1-2", delta=50_000, k=1 << 16),
        ])
        for h in hs:
            print(h.result().summary())
        from repro.core.engine import STATS
        print(STATS.motifs_per_cohort, STATS.samples_shared)

Each estimate stays bit-identical to its solo run (the shared stream's
keys derive from ``(seed, chunk)`` alone — lint rule
``det-cohort-key``); to PIN a cohort rather than rely on per-graph
min-W selection, pass the same rooted structure explicitly via
``Request(tree=..., wts=...)``.

Key objects
-----------
``EstimateConfig`` (api/config.py)
    One frozen config instead of per-call kwargs; ``REPRO_*`` env
    defaults are resolved exactly once, at session construction.
``Session`` (api/session.py)
    Owns the device upload, the ``(tree_signature, delta, wd, use_c2,
    backend)`` preprocess cache, the engine plan/LRU state and an
    optional mesh
    (pass ``mesh=launch.mesh.make_estimator_mesh()`` to shard every
    window's chunk range over the mesh's data axes).
``Request`` / ``Handle`` / ``Progress``
    ``submit(Request) -> Handle``; ``Handle.result()`` blocks,
    ``Handle.stream()`` yields per-window :class:`Progress` snapshots,
    ``Handle.rse`` is the live batch-means error measure.

Coalescing-window semantics
---------------------------
A submit window stays open ``coalesce_window_s`` seconds or until
``coalesce_max_requests`` are pending, whichever closes first; any
``result()``/``stream()``/``flush()`` closes it early.  Draining runs
every pending request through ``core.engine.plan_jobs``/``run_plan`` in
ONE plan, so window-mates sharing a plan key fuse.

Determinism contract
--------------------
Coalescing, fusion, adaptive growth and mesh sharding are pure execution
optimizations: chunk ``j`` of a request always draws from
``fold_in(PRNGKey(seed), j)``, so every result is bit-identical to a
solo ``estimate()`` with the same seed and the same final budget — on
any mesh shape, in any submit order.  Adaptive rounds RESUME from the
previous round's ``(chunks_done, acc)`` cursor; no sample is ever drawn
twice.

Compatibility shims
-------------------
``repro.core.estimator.estimate`` and ``repro.core.batch.estimate_many``
are thin wrappers that build a one-shot ``Session`` per call —
bit-identical to their pre-session behavior (pinned by
tests/test_api.py golden values).  New code should hold a ``Session``.

Live graphs
-----------
A ``Session`` holds one immutable snapshot.  For edge STREAMS — ingest
continuously, keep standing motif estimates fresh over a sliding window
— use ``repro.stream.StreamingSession``, which swaps a fresh session
onto each epoch snapshot while compiled window programs and preprocess
traces carry over (power-of-two padded snapshots keep array shapes
stable).  Each per-epoch standing count is bit-identical to a cold
``estimate()`` on that epoch's snapshot.

Serving
-------
This package holds no wire code.  The NDJSON front door is
``repro.gateway`` (``python -m repro.launch.estimate --serve``): one
loop that pools graph and stream tenants in one process behind a fair
single-dispatcher scheduler.  ``--serve`` adopts a ``Session`` on
``--graph``, ``--serve --stream`` a ``StreamingSession``, and ``--serve
--gateway`` starts with an empty pool; tenants open over the wire.  See
the ``repro.gateway`` package docstring for the canonical usage guide.
"""
from .config import EstimateConfig
from .session import Handle, Progress, Request, Session, SessionStats

__all__ = ["EstimateConfig", "Handle", "Progress", "Request", "Session",
           "SessionStats"]
