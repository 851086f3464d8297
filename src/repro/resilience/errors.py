"""Failure taxonomy: every fault in the serving stack gets ONE kind.

Three kinds, chosen for what the caller should *do* next:

* ``retryable`` — transient device/host conditions (device OOM /
  RESOURCE_EXHAUSTED while a program runs, connection resets,
  timeouts): retry with backoff, then degrade down the ladder (engine:
  pallas -> xla -> smaller dispatch windows).
* ``bad_request`` — the input is wrong (unknown motif, malformed
  fields): retrying is useless, but the server stays up and answers
  ``ok: false``.
* ``fatal`` — everything else (logic errors, assertion failures, and a
  compiler's refusal of a program — the same program is refused again
  on every attempt, whatever status code the refusal carries): never
  retried, never laddered to another backend; surfaces to the caller.
* ``overloaded`` — admission control shed the request before executing
  it (a bounded per-tenant quota was full — the gateway's backpressure
  seam).  The client backs off and resubmits; the server never retries
  shed work itself, which is what distinguishes it from ``retryable``.

:func:`classify` is the single decision point — the engine's retry
ladder, ``train/fault_tolerance.py`` and the serve loop all consult it,
so "is this worth retrying" can never drift between layers (pinned by
tests/test_train.py's cross-layer parity test).

JAX device errors arrive as ``jaxlib...XlaRuntimeError`` whose *status*
lives in the message text; we match by type NAME (no jax import — this
module stays stdlib-only) and grep the message for the transient gRPC
status codes.
"""
from __future__ import annotations


RETRYABLE = "retryable"
FATAL = "fatal"
BAD_REQUEST = "bad_request"
OVERLOADED = "overloaded"


class TransientError(RuntimeError):
    """Marker: a fault the raiser already knows is worth retrying."""


class OverloadedError(RuntimeError):
    """Marker: the server shed this request at admission (a bounded
    per-tenant quota was full — the gateway's backpressure seam).  The
    request was never executed; the client should back off and resubmit,
    but unlike ``retryable`` the *server* will not retry on its behalf.
    """


class FatalError(RuntimeError):
    """Marker: a fault the raiser already knows must NOT be retried."""


class BadRequestError(ValueError):
    """Marker: the request itself is invalid (never retried)."""


# host-side exception types that model transient conditions
_TRANSIENT_TYPES = (ConnectionError, TimeoutError, InterruptedError,
                    MemoryError)

# type names (checked against the MRO, so no jax import is needed) whose
# message text carries the real status
_DEVICE_ERROR_NAMES = ("XlaRuntimeError", "JaxRuntimeError")

# transient gRPC/XLA status markers inside a device error message
_TRANSIENT_STATUS = ("RESOURCE_EXHAUSTED", "UNAVAILABLE",
                     "DEADLINE_EXCEEDED", "ABORTED", "CANCELLED",
                     "OUT OF MEMORY", "OOM")

# markers of a compiler's refusal, checked before the transient ones.  The
# TPU compiler refuses a program over HBM with "RESOURCE_EXHAUSTED: XLA:TPU
# compile permanent error. Ran out of memory in memory space hbm ...", a
# kernel over VMEM with "RESOURCE_EXHAUSTED: Allocation (size=...) would
# exceed memory (size=...)", and a kernel Mosaic cannot build with
# "Mosaic failed to compile TPU kernel".  A refusal is deterministic:
# retrying recompiles the same refusal, and a backend swap would hide
# which program was refused (tests/test_tpu_compile.py feeds the real
# compiler's refusals through classify)
_COMPILE_REFUSAL = ("COMPILE PERMANENT ERROR", "WOULD EXCEED MEMORY",
                    "FAILED TO COMPILE")


def classify(exc: BaseException) -> str:
    """Map an exception to ``retryable`` / ``fatal`` / ``bad_request`` /
    ``overloaded``."""
    if isinstance(exc, OverloadedError):
        return OVERLOADED
    if isinstance(exc, BadRequestError):
        return BAD_REQUEST
    if isinstance(exc, FatalError):
        return FATAL
    if isinstance(exc, TransientError) or isinstance(exc, _TRANSIENT_TYPES):
        return RETRYABLE
    mro_names = {c.__name__ for c in type(exc).__mro__}
    if mro_names & set(_DEVICE_ERROR_NAMES):
        msg = str(exc).upper()
        if any(marker in msg for marker in _COMPILE_REFUSAL):
            return FATAL
        if any(status in msg for status in _TRANSIENT_STATUS):
            return RETRYABLE
        return FATAL
    if isinstance(exc, (ValueError, TypeError, KeyError)):
        return BAD_REQUEST
    return FATAL


def is_retryable(exc: BaseException) -> bool:
    return classify(exc) == RETRYABLE


def error_payload(exc: BaseException) -> dict:
    """The wire encoding of a failure: ``{"error": ..., "error_kind": ...}``.

    Every ``ok: false`` response the serve loop emits goes through here,
    so clients can branch on ``error_kind`` instead of parsing message
    strings.
    """
    return dict(error=f"{type(exc).__name__}: {exc}",
                error_kind=classify(exc))
