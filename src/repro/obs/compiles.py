"""Compile time by serving stage, from ``jax.monitoring``'s own events.

JAX reports three time spans per compiled program: tracing to a jaxpr
(``jaxpr_trace_duration``), lowering to an MLIR module
(``jaxpr_to_mlir_module_duration``) and the backend compile
(``backend_compile_duration``, which includes a persistent-cache
retrieval).  :func:`install` registers one listener for them; each span
is charged to the innermost ``stage=`` span open on the compiling thread
(:func:`.trace.current_stage`, carried into pool workers by
:func:`.trace.bind`) and lands in
``repro_stage_seconds{stage="compile.<stage>"}`` (``compile.none``
outside any stage).

Spans nest (an inner jit traces inside its caller's trace) and overlap
(the batch planner compiles its candidate DPs on several threads), so
each stage counts the **union** of its spans on the wall clock: JAX
announces each span's start (a scalar event carrying its start time)
and its end, the listener counts the spans open per stage, and a stage
is charged from the moment its count leaves zero until it returns to
zero.  A second of compiling counts once however many programs shared
it, and a stage's compile seconds never exceed the wall time they fall
in.

``repro_engine_compiles_total{stage, cache="hit|miss"}`` counts backend
compiles by whether the persistent compile cache supplied the
executable — a ``miss`` in steady state is a recompile worth an alert.

Installed once, at serve start-up, from the ``metrics`` level up.
"""
from __future__ import annotations

import threading

from .registry import REGISTRY
from .trace import METRICS, _stage_hist, current_stage, level

_PREFIX = "/jax/core/compile/"
_SPANS = frozenset(_PREFIX + e for e in (
    "jaxpr_trace_duration", "jaxpr_to_mlir_module_duration",
    "backend_compile_duration"))
_BACKEND = _PREFIX + "backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_COMPILES = REGISTRY.counter(
    "repro_engine_compiles_total",
    "backend compiles by stage and persistent-cache outcome",
    labels=("stage", "cache"))

_LOCK = threading.Lock()
_OPEN: dict = {}            # stage label -> compile spans open, all threads
_SINCE: dict = {}           # stage label -> start of its open union
#: per thread: .open, the stage labels of its open spans (they nest);
#: .hit, a persistent-cache hit inside its current backend compile
_TLS = threading.local()
_INSTALLED = False


def install() -> bool:
    """Register the listeners (once; only from the ``metrics`` level
    up).  Returns whether they are registered."""
    global _INSTALLED
    with _LOCK:
        if not _INSTALLED and level() >= METRICS:
            import jax.monitoring as monitoring
            monitoring.register_scalar_listener(_on_start)
            monitoring.register_event_time_span_listener(_on_end)
            monitoring.register_event_listener(_on_event)
            _INSTALLED = True
        return _INSTALLED


def _on_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT:
        _TLS.hit = True


def _on_start(event: str, value: float, **_kw) -> None:
    """A compile span opens; ``value`` is its start time."""
    if event not in _SPANS:
        return
    label = "compile." + (current_stage() or "none")
    stack = getattr(_TLS, "open", None)
    if stack is None:
        stack = _TLS.open = []
    stack.append(label)
    with _LOCK:
        n = _OPEN.get(label, 0)
        if n == 0:
            _SINCE[label] = float(value)
        _OPEN[label] = n + 1


def _on_end(event: str, start: float, end: float, **_kw) -> None:
    if event not in _SPANS or not getattr(_TLS, "open", None):
        return
    label = _TLS.open.pop()
    if event == _BACKEND:
        hit = getattr(_TLS, "hit", False)
        _TLS.hit = False
        _COMPILES.labels(stage=label[len("compile."):],
                         cache="hit" if hit else "miss").inc()
    with _LOCK:
        n = _OPEN[label] - 1
        _OPEN[label] = n
        added = float(end) - _SINCE.pop(label) if n == 0 else 0.0
    if added > 0 and level() >= METRICS:
        _stage_hist(label).observe(added)
