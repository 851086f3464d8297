"""The NDJSON wire format: request parsing and response shapes.

One JSON object per line in, one per line out (stderr carries logs).
A request line::

    {"tenant": "fin", "id": 1, "motif": "M5-3", "delta": 4000,
     "k": 65536, "seed": 7, "target_rse": 0.1, "k_max": 1048576}

``motif`` accepts catalog names or inline edge-list specs (the
``core.motif`` DSL).  Optional fields: ``id`` (echoed back), ``seed``,
``target_rse``/``k_max`` (adaptive budgets), ``witnesses`` (the top-n
accepted edge tuples), ``deadline_ms`` (soft wall-clock budget: an
expired request answers ``ok: true`` with ``degraded: true``, the
samples actually drawn as ``k`` and the achieved ``rse`` — a deadline is
never an error).  Unknown fields are rejected (``checkpoint_path`` in
particular stays CLI/library-only: a request line must not name
server-side files to overwrite).

A response line::

    {"id": 1, "tenant": "fin", "ok": true, "estimate": 4636.58,
     "W": 412857, "k": 65536, "valid": 27210, "rse": 0.18,
     "motif": "M5-3", "delta": 4000, "sampler_backend": "xla",
     "fused_jobs": 2, "windows": 8}

A failing line answers ``{"ok": false, "error": "...", "error_kind":
"retryable" | "fatal" | "bad_request" | "overloaded"}`` (the
``repro.resilience.errors`` taxonomy — clients branch on ``error_kind``,
never on message text).

The verbs that route these (tenants, streams, telemetry) are in
``gateway/serve.py``.
"""
from __future__ import annotations

import math

from .. import obs
from ..api.session import Handle, Request


def response(rid, handle: Handle) -> dict:
    res = handle.result()
    rse = handle.rse
    d = dict(
        id=rid, ok=True, estimate=res.estimate, W=res.W, k=res.k,
        valid=res.valid, rse=None if math.isinf(rse) else rse,
        motif=res.motif, delta=res.delta,
        sampler_backend=res.sampler_backend,
        fallback_reason=res.fallback_reason, fused_jobs=res.fused_jobs,
        windows=handle.windows)
    if res.degraded:
        d.update(degraded=True, degrade_reason=res.degrade_reason,
                 k_done=res.k)
    if res.witnesses is not None:
        d.update(witnesses=[dict(edges=[list(e) for e in w["edges"]],
                                 cnt=w["cnt"]) for w in res.witnesses])
    return d


def progress_line(rid, tenant: str, p) -> dict:
    """One per-checkpoint-window witness line (emitted before the final
    response, oldest window first)."""
    return dict(id=rid, tenant=tenant, progress=True, window=p.window,
                k_done=p.k_done, estimate=p.estimate,
                rse=None if math.isinf(p.rse) else p.rse,
                witnesses=[dict(edges=[list(e) for e in w["edges"]],
                                cnt=w["cnt"]) for w in (p.witnesses or ())])


_REQUEST_FIELDS = frozenset(
    ("id", "motif", "delta", "k", "seed", "target_rse", "k_max",
     "deadline_ms", "witnesses"))


def parse_request(obj: dict) -> Request:
    for k in ("motif", "delta", "k"):
        if k not in obj:
            raise ValueError(f"request missing required field {k!r}")
    unknown = set(obj) - _REQUEST_FIELDS
    if unknown:
        # checkpoint_path is deliberately NOT exposed on the wire: it
        # names a server-side file to create/overwrite, which an
        # untrusted request line must never control (CLI/library only)
        raise ValueError(f"unknown request field(s) {sorted(unknown)}; "
                         f"accepted: {sorted(_REQUEST_FIELDS)}")
    return Request(
        motif=str(obj["motif"]), delta=int(obj["delta"]), k=int(obj["k"]),
        seed=None if obj.get("seed") is None else int(obj["seed"]),
        target_rse=(None if obj.get("target_rse") is None
                    else float(obj["target_rse"])),
        k_max=None if obj.get("k_max") is None else int(obj["k_max"]),
        deadline_s=(None if obj.get("deadline_ms") is None
                    else float(obj["deadline_ms"]) / 1000.0),
        witnesses=int(obj.get("witnesses") or 0))


def engine_stats() -> dict:
    """Process-wide ``engine.STATS`` as a wire dict (tree-cohort fan-out).

    ``motifs_per_cohort`` > 1.0 means standing queries are sharing
    sample streams (one tree-instance draw scoring several motifs);
    ``samples_shared`` counts the samples that were consumed by a job
    without being redrawn for it.
    """
    from ..core.engine import STATS as ESTATS
    return dict(dispatches=ESTATS.dispatches,
                fused_dispatches=ESTATS.fused_dispatches,
                job_windows=ESTATS.job_windows,
                tree_cohorts=ESTATS.tree_cohorts,
                motifs_per_cohort=round(ESTATS.motifs_per_cohort, 3),
                samples_shared=ESTATS.samples_shared,
                witness_dispatches=ESTATS.witness_dispatches)


def metrics() -> dict:
    """The ``metrics`` verb: full registry as Prometheus text exposition
    (one NDJSON response; scrapers unwrap the ``text`` field)."""
    return dict(ok=True, cmd="metrics",
                content_type="text/plain; version=0.0.4",
                text=obs.REGISTRY.prometheus_text())


def trace_export() -> dict:
    """The ``trace`` verb: the flight recorder's span ring, oldest first
    (each entry is one NDJSON record of the ``--trace-out`` export)."""
    recs = obs.RECORDER.records()
    return dict(ok=True, cmd="trace", level=obs.level_name(),
                count=len(recs), recorded=obs.RECORDER.recorded,
                ring=obs.RECORDER.capacity, spans=recs)


def profile(obj: dict, profile_dir: str | None) -> dict:
    """The ``profile`` verb: arm a jax.profiler capture around the next
    N engine window dispatches.  The capture directory comes from the
    server's ``--profile-dir`` flag — the wire never names server paths."""
    if profile_dir is None:
        return dict(ok=False, cmd="profile",
                    error="server started without --profile-dir")
    try:
        st = obs.arm_profile(int(obj.get("windows") or 1), profile_dir)
    except (ValueError, RuntimeError, TypeError) as e:
        return dict(ok=False, cmd="profile", error=str(e))
    return dict(ok=True, cmd="profile", **st)


def device_block() -> dict:
    """The ``device`` block of the ``health`` verb: the devices this
    process computes on, as JAX reports them, and the first device's
    peak memory in bytes where the backend reports one (else ``None``)."""
    import jax
    devs = jax.devices()
    mem = devs[0].memory_stats() or {}
    return dict(platform=devs[0].platform, kind=devs[0].device_kind,
                count=len(devs), peak_bytes=mem.get("peak_bytes_in_use"))


SUBSCRIBE_FIELDS = frozenset(
    ("cmd", "motif", "delta", "k", "seed", "target_rse", "k_max", "name",
     "witnesses"))


def parse_ingest(obj: dict):
    import numpy as np
    edges = obj.get("edges")
    if not isinstance(edges, list) or not edges:
        raise ValueError('ingest needs "edges": [[src, dst, t], ...]')
    a = np.asarray(edges, dtype=np.int64)
    if a.ndim != 2 or a.shape[1] != 3:
        raise ValueError(f"edges must be [N, 3] int triples, got "
                         f"shape {a.shape}")
    return a[:, 0], a[:, 1], a[:, 2]


def sub_response(qid: int, query, epoch_idx: int, res) -> dict:
    rse = res.rse
    d = dict(sub=qid, epoch=epoch_idx, ok=True, name=query.label,
             estimate=res.estimate, W=res.W, k=res.k, valid=res.valid,
             rse=None if rse is None or math.isinf(rse) else rse,
             motif=res.motif, delta=res.delta,
             sampler_backend=res.sampler_backend,
             fused_jobs=res.fused_jobs)
    if res.witnesses is not None:
        d.update(witnesses=[dict(edges=[list(e) for e in w["edges"]],
                                 cnt=w["cnt"]) for w in res.witnesses])
    return d
