"""Process-wide observability: tracing, metrics, flight recorder,
profiling — THE canonical guide to the telemetry layer.

Why this layer exists
---------------------
The ROADMAP's north star is serving motif estimates at production scale,
and the paper's core claims are time-vs-error tradeoffs — so "where did
this request's 400 ms go?" and "what is the p99 advance latency per
tenant?" must be answerable from a running process.  Before this layer
the only visibility was a handful of hand-rolled counters with no
timing, no per-request causality, and no scrapable surface.

The three facilities (gated by the ``REPRO_OBS`` knob: ``off`` |
``metrics`` | ``trace``)
------------------------------------------------------------------
**Tracing** (``trace``) — :func:`span` opens a lightweight host-side
span; a trace id is minted at intake (gateway wire line /
``Session.submit`` / ``StreamingSession.advance``) and propagated
intake → scheduler ``Work`` → session drain → engine cohort dispatch →
emitter, explicitly across threads and ambiently (thread-local) within
one.  Closed spans land in the bounded ring-buffer flight recorder
(:data:`RECORDER`), exportable as NDJSON via the ``{"cmd": "trace"}``
wire verb or ``--trace-out PATH``.  One gateway request yields a
connected chain: ``gateway.intake`` → ``stage.queue_wait`` →
``gateway.drain`` → ``engine.dispatch`` ×W → ``gateway.emit``, all
sharing the request's trace id.

**Metrics** (``metrics``) — a typed registry (:mod:`.registry`) of
monotonic counters, gauges, and fixed log2-bucket latency histograms:
per-stage latency (``repro_stage_seconds{stage=...}``), compile time by
stage (``repro_stage_seconds{stage="compile.<stage>"}``, from
:mod:`.compiles`) and backend compiles by cache outcome
(``repro_engine_compiles_total{stage,cache}``), per-tenant
request/advance histograms, samples drawn
(``repro_engine_samples_drawn_total``; take its rate), window-program
LRU hit/miss, WAL fsync latency.  ``engine.STATS`` and
``resilience.STATS`` are :class:`~.registry.CounterBlock` facades over
the same registry (their legacy attribute API still works), so every
legacy counter is also a Prometheus series — scraped via the
``{"cmd": "metrics"}`` wire verb and embedded in ``health``/``stats``.

**Profiling** — ``{"cmd": "profile", "windows": n}`` arms a one-shot
``jax.profiler`` capture around the next n engine window dispatches
(server started with ``--profile-dir``).  During the capture every span
is also a ``jax.profiler.TraceAnnotation`` (attrs as event stats, e.g.
``engine.dispatch``'s ``samples``/``j0``/``n``), so host spans share the
device trace's clock; the window program names its phases with
``jax.named_scope`` (``sample/{window,center,child,vertex_map}``,
``validate``, ``score``), which the trace's op metadata carries.

Contracts
---------
* **Bit-identity.**  Obs never touches sampling keys or traced code:
  spans are host-side, trace ids come from a splitmix64-mixed process
  counter (no entropy), and estimates are bit-identical at every
  ``REPRO_OBS`` level (pinned by goldens in ``tests/test_obs.py``).
* **Structurally free when off.**  At ``off`` nothing is recorded —
  no ring appends, no histogram updates, no span-stack or stage
  bookkeeping, no profiler annotation (PERF.md gives the chip-measured
  cost of tracing).
* **Monotonic counters.**  Registry counters survive
  ``clear_window_cache()`` and session teardown; ``reset`` exists only
  as a test seam.
* **Clock discipline.**  ``time.monotonic``/``perf_counter`` live in
  :mod:`.clock` alone; the ``obs-span-discipline`` lint rule errors on
  any other wall-clock read in ``repro/gateway/`` /
  ``repro/core/engine.py`` — all timing flows through this API.
* **Stdlib only** (jax imported lazily inside the profiler seam), so
  ``repro.resilience`` and everything above can depend on this package
  without cycles.
"""
from __future__ import annotations

from .clock import monotonic, perf_counter
from .registry import (BUCKET_BOUNDS, N_BUCKETS, REGISTRY, Counter,
                       CounterBlock, Family, Gauge, Histogram, Registry)
from .trace import (METRICS, OFF, RECORDER, TRACE, FlightRecorder, Span,
                    arm_profile, bind, current_stage, current_trace, enabled,
                    event, level, level_name, new_trace, observe_stage,
                    profile_armed, profile_status, profile_window_end,
                    profile_window_start, set_level, span, summary,
                    trace_context)
from .compiles import install as install_compile_listener

__all__ = [
    "monotonic", "perf_counter",
    "BUCKET_BOUNDS", "N_BUCKETS", "REGISTRY", "Counter", "CounterBlock",
    "Family", "Gauge", "Histogram", "Registry",
    "METRICS", "OFF", "RECORDER", "TRACE", "FlightRecorder", "Span",
    "arm_profile", "bind", "current_stage", "current_trace", "enabled",
    "event", "install_compile_listener", "level",
    "level_name", "new_trace", "observe_stage", "profile_armed",
    "profile_status", "profile_window_end", "profile_window_start",
    "set_level", "span", "summary", "trace_context",
]
