"""Async gateway: overlapped drains, multi-graph tenancy, witness streaming.

One process, many independent graphs/streams, and the only NDJSON loop:
every ``--serve`` mode runs ``gateway_serve_loop``, and the wire format
lives in ``gateway/wire.py``.  The gateway layers over
``api.Session`` / ``stream.StreamingSession`` and adds exactly three
things — everything below it (sampling keys, counts, estimates) is
untouched, so gateway results are bit-identical to solo synchronous
``estimate()`` runs at the same seed and budget:

* **Overlapped execution** (``scheduler.FairScheduler``): request
  intake, NDJSON emit and engine drains run on separate threads; a
  drain for one tenant never blocks another tenant's enqueue, tenants
  are served round-robin, and a tenant past its pending quota is shed
  with a structured ``{"ok": false, "error_kind": "overloaded"}`` —
  never a silent stall.
* **Multi-graph tenancy** (``state.GatewayState``): tenants pool in one
  process under ``open_tenant``/``close_tenant`` wire verbs, with
  idle-LRU eviction and per-tenant WAL paths derived server-side.
  Because the engine keys compiled window programs on tree signature +
  padded bucket shapes (never graph identity), tenant N+1 on
  same-bucket graphs re-hits tenant N's compiled programs: its marginal
  cold-cost is preprocessing alone.
* **Witness streaming**: ``Request(witnesses=n)`` returns up to ``n``
  accepted full-match edge tuples alongside the count — a deterministic
  seeded reservoir (priorities from ``(seed, chunk, position)`` only),
  streamed per checkpoint window over the wire and exposed on
  ``EstimateResult.witnesses``.

Canonical usage — the wire loop (``launch/estimate.py --serve``) or
directly::

    import io
    from repro.gateway import gateway_serve_loop

    lines = "\\n".join([
        '{"cmd": "open_tenant", "tenant": "fin",'
        ' "graph": "fintxn:n_accounts=500,n_events=4000,seed=5"}',
        '{"tenant": "fin", "id": 1, "motif": "M5-3", "delta": 4000,'
        ' "k": 16384, "witnesses": 5}',
        '{"cmd": "stats"}',
        '{"cmd": "quit"}',
    ]) + "\\n"
    out = io.StringIO()
    gateway_serve_loop(infile=io.StringIO(lines), outfile=out)

A session built by the caller joins the pool as tenant ``"default"``,
and lines without ``"tenant"`` go to it (the CLI's ``--serve`` and
``--serve --stream``; the caller keeps ownership and closes it)::

    with Session(g, EstimateConfig(chunk=2048)) as s:
        gateway_serve_loop(adopt=s)   # {"id": 1, "motif": "M5-3", ...}

or in-process, scripting the same pieces the loop wires up::

    from repro.api import EstimateConfig, Request
    from repro.gateway import GatewayState

    state = GatewayState(EstimateConfig(chunk=2048), max_tenants=4)
    fin = state.open_tenant("fin", graph="fintxn:n_accounts=500,"
                            "n_events=4000,seed=5")
    h = fin.cur_session().submit(Request("M5-3", delta=4000, k=16384,
                                         witnesses=5))
    res = h.result()          # res.witnesses: ((edges, cnt, prio), ...)
    state.close_all()

See ``gateway/serve.py`` for the full wire protocol and
``examples/streaming_fraud.py`` for a two-tenant fraud-monitoring run
that prints witness edge tuples per epoch.
"""
from .io import Emitter, LineSource
from .scheduler import FairScheduler, SchedulerStats, Work
from .serve import gateway_serve_loop
from .state import GatewayState, Tenant, TenantStats

__all__ = [
    "Emitter", "LineSource",
    "FairScheduler", "SchedulerStats", "Work",
    "gateway_serve_loop",
    "GatewayState", "Tenant", "TenantStats",
]
