"""Host-side spans, trace-id propagation, and the flight recorder.

A **trace id** is minted once per unit of external work — a gateway wire
line, a ``Session.submit``, a ``StreamingSession.advance`` — and rides
along every hop that serves it: intake thread → scheduler ``Work`` →
dispatcher drain → engine cohort dispatch → emitter thread.  Propagation
is explicit across threads (the gateway stores the id on the ``Work``
item and re-enters it via :class:`trace_context` on the dispatcher) and
ambient within one (a ``threading.local`` that :func:`span` consults).

A **span** times a host-side region.  It ALWAYS measures (callers like
the engine consume ``elapsed_s`` for result metadata at every obs
level); what varies with ``REPRO_OBS`` is recording:

* ``off``     — nothing is recorded anywhere (no ring append, no
  histogram update, no span-stack bookkeeping);
* ``metrics`` — spans that declare a ``stage=`` feed the
  ``repro_stage_seconds`` histogram family;
* ``trace``   — additionally every span/event lands in the bounded
  ring-buffer **flight recorder**, exportable as NDJSON via the
  ``{"cmd": "trace"}`` wire verb or ``--trace-out PATH``.

From ``metrics`` up, a span that declares a ``stage=`` is also the
thread's **current stage** until it closes (one thread-local set and
restore; :func:`current_stage`) — what the compile listener
(:mod:`.compiles`) charges a compile to — and :func:`bind` carries the
caller's stage and trace into pool workers.

Spans never enter traced code: ids derive from a process counter mixed
through splitmix64 (no entropy, no wall-clock in keys), clock reads stay
on the host, and estimates are bit-identical at every level.

The :func:`profile` seam arms a one-shot ``jax.profiler`` capture around
the next N engine window dispatches (wire verb ``{"cmd": "profile"}``).
While a capture runs (and the level is ``metrics`` or above), every span
also enters a ``jax.profiler.TraceAnnotation`` of its name, its scalar
attrs as event stats, so host spans land on the device trace's clock;
stage spans already open when the capture starts are annotated from
that moment, and closed when it stops.  Outside a capture no annotation
is built.  jax is imported lazily there — everything else in this
module is stdlib.
"""
from __future__ import annotations

import itertools
import json
import os
import threading
from collections import deque

from ..knobs import get_knob
from .clock import perf_counter
from .registry import REGISTRY

OFF, METRICS, TRACE = 0, 1, 2
_LEVEL_NAMES = {"off": OFF, "metrics": METRICS, "trace": TRACE}
_LEVEL: int | None = None          # resolved lazily from REPRO_OBS


def level() -> int:
    global _LEVEL
    if _LEVEL is None:
        _LEVEL = _LEVEL_NAMES[get_knob("REPRO_OBS")]
    return _LEVEL


def level_name() -> str:
    return ("off", "metrics", "trace")[level()]


def enabled(min_level: int = METRICS) -> bool:
    return level() >= min_level


def set_level(value: str | None) -> None:
    """Override the obs level in-process (tests / CLI); None re-resolves
    from the ``REPRO_OBS`` knob on next use."""
    global _LEVEL
    if value is None:
        _LEVEL = None
        return
    if value not in _LEVEL_NAMES:
        raise ValueError(f"REPRO_OBS level {value!r} "
                         f"(want {'|'.join(_LEVEL_NAMES)})")
    _LEVEL = _LEVEL_NAMES[value]


# ---------------------------------------------------------------------------
# trace ids + ambient context
# ---------------------------------------------------------------------------
def _splitmix64(x: int) -> int:
    x = (x + 0x9E3779B97F4A7C15) & 0xFFFFFFFFFFFFFFFF
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & 0xFFFFFFFFFFFFFFFF
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & 0xFFFFFFFFFFFFFFFF
    return x ^ (x >> 31)

_TRACE_SEQ = itertools.count(1)
_SPAN_SEQ = itertools.count(1)
_CTX = threading.local()


def new_trace() -> str:
    """Mint a trace id: process counter mixed through splitmix64 — no
    entropy, no wall-clock, deterministic per mint order."""
    n = next(_TRACE_SEQ)
    return f"{_splitmix64((os.getpid() << 32) ^ n):016x}"


def current_trace() -> str | None:
    return getattr(_CTX, "trace", None)


class trace_context:
    """Context manager: make ``tid`` the ambient trace on this thread."""

    __slots__ = ("tid", "_prev")

    def __init__(self, tid: str | None):
        self.tid = tid
        self._prev = None

    def __enter__(self):
        self._prev = getattr(_CTX, "trace", None)
        _CTX.trace = self.tid
        return self

    def __exit__(self, *exc):
        _CTX.trace = self._prev
        return False


def current_stage() -> str | None:
    """The ``stage=`` of the innermost stage span open on this thread
    (tracked from the ``metrics`` level up; None outside any)."""
    sp = getattr(_CTX, "stage_span", None)
    return None if sp is None else sp.stage


def bind(fn):
    """``fn`` wrapped to run under the calling thread's ambient trace and
    innermost stage span — for work handed to a thread pool, whose
    workers would otherwise charge their stage time (and compiles) to no
    stage.  Returns ``fn`` itself below ``metrics``."""
    if level() < METRICS:
        return fn
    outer = getattr(_CTX, "stage_span", None)
    tid = outer.trace if outer is not None else current_trace()

    def run(*args, **kwargs):
        prev = (getattr(_CTX, "trace", None),
                getattr(_CTX, "stage_span", None))
        _CTX.trace, _CTX.stage_span = tid, outer
        try:
            return fn(*args, **kwargs)
        finally:
            _CTX.trace, _CTX.stage_span = prev

    return run


def _span_stack() -> list:
    stack = getattr(_CTX, "stack", None)
    if stack is None:
        stack = _CTX.stack = []
    return stack


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------
class FlightRecorder:
    """Bounded ring of span/event records (oldest overwritten first)."""

    def __init__(self, capacity: int):
        self.capacity = int(capacity)
        self._ring: deque = deque(maxlen=self.capacity)
        self._recorded = 0          # total appended (exceeds len once wrapped)

    def append(self, rec: dict) -> None:
        self._ring.append(rec)
        self._recorded += 1

    def __len__(self) -> int:
        return len(self._ring)

    @property
    def recorded(self) -> int:
        return self._recorded

    def records(self) -> list:
        return list(self._ring)

    def export_ndjson(self) -> str:
        recs = self.records()
        if not recs:
            return ""
        return "\n".join(json.dumps(r, sort_keys=True) for r in recs) + "\n"

    def clear(self) -> None:
        self._ring.clear()
        self._recorded = 0


RECORDER = FlightRecorder(get_knob("REPRO_OBS_RING"))

_STAGE_SECONDS = REGISTRY.histogram(
    "repro_stage_seconds",
    "per-stage serving latency (intake, queue_wait, preprocess, drain, "
    "dispatch, device, emit, advance, wal_fsync) and compile time by "
    "stage (compile.<stage>)", labels=("stage",))
_STAGE_CHILDREN: dict = {}          # stage -> Histogram child (hot-path cache)


def _stage_hist(stage: str):
    h = _STAGE_CHILDREN.get(stage)
    if h is None:
        h = _STAGE_CHILDREN[stage] = _STAGE_SECONDS.labels(stage=stage)
    return h


class Span:
    """One timed host-side region (always times; records per level)."""

    __slots__ = ("name", "stage", "trace", "attrs", "span_id", "parent_id",
                 "t0", "elapsed_s", "_lvl", "_outer", "_ann")

    def __init__(self, name: str, stage: str | None, trace: str | None,
                 attrs: dict):
        self.name = name
        self.stage = stage
        self.trace = trace
        self.attrs = attrs
        self.span_id = 0
        self.parent_id = 0
        self.t0 = 0.0
        self.elapsed_s = 0.0
        self._lvl = level()
        self._outer = None          # enclosing stage span (metrics and up)
        self._ann = None            # open TraceAnnotation during a capture

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    def __enter__(self) -> "Span":
        if self._lvl >= TRACE:
            stack = _span_stack()
            if self.trace is None:
                self.trace = (stack[-1].trace if stack
                              else current_trace())
            self.span_id = next(_SPAN_SEQ)
            self.parent_id = stack[-1].span_id if stack else 0
            stack.append(self)
        elif self.trace is None:
            self.trace = current_trace()
        if self._lvl >= METRICS:
            if self.stage is not None:
                self._outer = getattr(_CTX, "stage_span", None)
                _CTX.stage_span = self
            if _PROFILE["active"]:
                self._ann = _annotate(self)
        self.t0 = perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.elapsed_s = perf_counter() - self.t0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if self._lvl >= METRICS and self.stage is not None:
            _CTX.stage_span = self._outer
            if level() >= METRICS:
                _stage_hist(self.stage).observe(self.elapsed_s)
        if self._lvl >= TRACE:
            stack = _span_stack()
            if stack and stack[-1] is self:
                stack.pop()
            rec = {"name": self.name, "trace": self.trace,
                   "span": self.span_id, "parent": self.parent_id,
                   "t0": round(self.t0, 6),
                   "dur_s": round(self.elapsed_s, 9),
                   "thread": threading.current_thread().name}
            if self.stage is not None:
                rec["stage"] = self.stage
            if exc_type is not None:
                rec["error"] = exc_type.__name__
            if self.attrs:
                rec["attrs"] = self.attrs
            RECORDER.append(rec)
        return False


def span(name: str, *, stage: str | None = None, trace: str | None = None,
         **attrs) -> Span:
    """Open a span.  ``stage=`` feeds ``repro_stage_seconds`` at the
    metrics level; other kwargs become recorder attrs at trace level,
    and event stats of its annotation during a profiler capture."""
    return Span(name, stage, trace, attrs)


def event(name: str, *, trace: str | None = None, **attrs) -> None:
    """Zero-duration recorder entry (trace level only) — e.g. per-window
    RSE-vs-samples trajectory points."""
    if level() < TRACE:
        return
    if trace is None:
        trace = current_trace()
    rec = {"name": name, "trace": trace, "span": next(_SPAN_SEQ),
           "parent": 0, "t0": round(perf_counter(), 6), "dur_s": 0.0,
           "thread": threading.current_thread().name}
    if attrs:
        rec["attrs"] = attrs
    RECORDER.append(rec)


def observe_stage(stage: str, dt: float, *, trace: str | None = None,
                  **attrs) -> None:
    """Record a DERIVED duration (e.g. queue-wait measured between two
    threads) into the stage histogram + flight recorder."""
    lvl = level()
    if lvl < METRICS:
        return
    _stage_hist(stage).observe(dt)
    if _PROFILE["active"]:
        # an instant on the profiler clock; the duration rides as a stat
        _annotate_instant(f"stage.{stage}", dur_ns=int(dt * 1e9), **attrs)
    if lvl >= TRACE:
        if trace is None:
            trace = current_trace()
        rec = {"name": f"stage.{stage}", "trace": trace,
               "span": next(_SPAN_SEQ), "parent": 0,
               "t0": round(perf_counter(), 6), "dur_s": round(float(dt), 9),
               "thread": threading.current_thread().name, "stage": stage}
        if attrs:
            rec["attrs"] = attrs
        RECORDER.append(rec)


def summary() -> dict:
    """Small obs block embedded in ``health`` / ``stats`` responses."""
    return {"level": level_name(), "spans": len(RECORDER),
            "recorded": RECORDER.recorded, "ring": RECORDER.capacity}


# ---------------------------------------------------------------------------
# jax.profiler capture seam ({"cmd": "profile", "windows": n})
# ---------------------------------------------------------------------------
_PROFILE = {"remaining": 0, "dir": None, "active": False, "error": None,
            "captured": 0}
_PROFILE_LOCK = threading.Lock()


def _stats(attrs: dict) -> dict:
    """The attrs a TraceAnnotation can carry as event stats."""
    return {k: v for k, v in attrs.items()
            if isinstance(v, (int, float, str))}


def _annotate(sp: Span):
    """Open a ``TraceAnnotation`` for ``sp`` (only while a capture runs,
    so jax is already imported)."""
    from jax.profiler import TraceAnnotation
    stats = _stats(sp.attrs)
    if sp.trace is not None:
        stats["trace"] = sp.trace
    ann = TraceAnnotation(sp.name, **stats)
    ann.__enter__()
    return ann


def _annotate_instant(name: str, **attrs) -> None:
    from jax.profiler import TraceAnnotation
    with TraceAnnotation(name, **_stats(attrs)):
        pass


def _open_stage_spans() -> list:
    """This thread's open stage spans, outermost first."""
    out = []
    sp = getattr(_CTX, "stage_span", None)
    while sp is not None:
        out.append(sp)
        sp = sp._outer
    return out[::-1]


def arm_profile(windows: int, logdir: str) -> dict:
    """Arm a one-shot device-level capture around the next N engine
    window dispatches."""
    windows = int(windows)
    if windows < 1:
        raise ValueError("profile windows must be >= 1")
    with _PROFILE_LOCK:
        if _PROFILE["active"] or _PROFILE["remaining"] > 0:
            raise RuntimeError("a profiler capture is already armed")
        _PROFILE.update(remaining=windows, dir=logdir, error=None,
                        captured=0)
    return {"armed": windows, "dir": logdir}


def profile_armed() -> bool:
    """Cheap pre-dispatch check (one dict read on the engine hot path)."""
    return _PROFILE["remaining"] > 0 or _PROFILE["active"]


def profile_window_start() -> None:
    with _PROFILE_LOCK:
        if _PROFILE["active"] or _PROFILE["remaining"] <= 0:
            return
        try:
            import jax
            jax.profiler.start_trace(_PROFILE["dir"])
            _PROFILE["active"] = True
        except Exception as e:          # profiler failure must not kill serving
            _PROFILE["error"] = f"{type(e).__name__}: {e}"
            _PROFILE["remaining"] = 0
            return
        # the stage spans this window runs inside (a drain) opened before
        # the capture: annotate them from here, so the trace sees them
        if level() >= METRICS:
            for sp in _open_stage_spans():
                if sp._ann is None:
                    sp._ann = _annotate(sp)


def profile_window_end() -> None:
    with _PROFILE_LOCK:
        if not _PROFILE["active"]:
            return
        _PROFILE["remaining"] -= 1
        _PROFILE["captured"] += 1
        if _PROFILE["remaining"] <= 0:
            # close the enclosing annotations while the capture still
            # records them (an annotation open at the stop is dropped)
            for sp in reversed(_open_stage_spans()):
                if sp._ann is not None:
                    sp._ann.__exit__(None, None, None)
                    sp._ann = None
            try:
                import jax
                jax.profiler.stop_trace()
            except Exception as e:
                _PROFILE["error"] = f"{type(e).__name__}: {e}"
            _PROFILE["active"] = False


def profile_status() -> dict:
    with _PROFILE_LOCK:
        return {k: _PROFILE[k] for k in
                ("remaining", "dir", "active", "error", "captured")}
