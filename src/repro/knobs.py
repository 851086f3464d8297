"""Process-level ``REPRO_*`` knob registry — THE environment seam.

Every ``REPRO_*`` environment variable the system responds to is declared
in :data:`KNOBS`, and this module is the ONLY one allowed to read them
(statically enforced: ``repro.analysis`` rule ``env-seam`` errors on any
``os.environ``/``os.getenv`` touch of a ``REPRO_*`` name outside this
file, and on ANY env read under ``core/``/``kernels/``;  ``scripts/ci.sh``
runs the linter as its first gate).

Why a registry
--------------
PR 4's config contract ("``REPRO_*`` defaults are resolved exactly once,
in ``api/config.py``") had quietly eroded: six reads were scattered
across ``core/engine.py``, ``core/sampler.py``, ``core/weights.py`` and
``kernels/tree_sampler/ops.py``, each with its own inline default — an
out-of-seam read in a warm serving process can silently disagree with
the session's resolved config and break the bit-identity contract
without failing a test.  Centralizing the reads makes the seam
auditable:

* **result-affecting** knobs (the backends) are resolved once, at
  ``EstimateConfig.resolve()`` time, and flow everywhere as explicit
  values;
* **perf-only** knobs (cache sizes, trip counts, VMEM budgets) may be
  read at use sites — but only through :func:`get_knob`, so the full
  set is enumerable and each carries a declared default + validation.

``get_knob(name)`` is the single ``os.environ`` read site.  Callers
never pass defaults — the registry owns them.
"""
from __future__ import annotations

import os
from dataclasses import dataclass


@dataclass(frozen=True)
class Knob:
    """One declared environment knob."""

    name: str
    default: object
    cast: type                      # int | str — applied to the env string
    doc: str
    choices: tuple | None = None    # validated against the cast value
    result_affecting: bool = False  # True: must flow through EstimateConfig


KNOBS: dict[str, Knob] = {k.name: k for k in (
    Knob("REPRO_SAMPLER_BACKEND", "xla", str,
         "sampling path: XLA gather chain or the fused kernels/"
         "tree_sampler pallas kernel (bit-identical; CPU-interpret only — "
         "the TPU compiler refuses the kernel until ROADMAP S2)",
         choices=("xla", "pallas"), result_affecting=True),
    Knob("REPRO_DEPSUM_BACKEND", "xla", str,
         "weight-preprocess dep-sum inner loop: exact int64 XLA or the "
         "kernels/interval_weight pallas kernel (f32-exact audited; "
         "CPU-interpret only — the TPU compiler refuses the kernel until "
         "ROADMAP S2)",
         choices=("xla", "pallas"), result_affecting=True),
    Knob("REPRO_ENGINE_CACHE", 32, int,
         "bounded LRU capacity for compiled engine window programs"),
    Knob("REPRO_BISECT_ITERS", 0, int,
         "fixed bisection trip count override (0 = adaptive "
         "ceil(log2(m))+1; A/B tuning only — converged extra iterations "
         "are no-ops, so results never change)"),
    Knob("REPRO_SAMPLER_VMEM_MB", 192, int,
         "VMEM budget (MiB) the eligibility gate allows the fused "
         "tree_sampler kernel's resident CSR/prefix structure; ineligible "
         "jobs fall back to xla.  Sized for interpret mode: a v5e's "
         "compiler caps VMEM at 128 MiB, and no budget is known to work "
         "on a chip until the kernel compiles there (ROADMAP S2)"),
    Knob("REPRO_SAMPLER_BLOCK", 1024, int,
         "sample-axis block width of the fused tree_sampler kernel"),
    Knob("REPRO_OBS", "off", str,
         "observability level: 'off' (no-op recorder), 'metrics' "
         "(counters/gauges/histograms), 'trace' (metrics + host-side "
         "spans into the flight recorder); never result-affecting — "
         "estimates are bit-identical at every level",
         choices=("off", "metrics", "trace")),
    Knob("REPRO_OBS_RING", 4096, int,
         "flight-recorder capacity (spans); the ring overwrites the "
         "oldest span when full"),
)}


def get_knob(name: str):
    """Read one declared knob: env value (cast + validated) or default.

    The only ``os.environ`` read of a ``REPRO_*`` name in the tree.
    """
    knob = KNOBS[name]
    raw = os.environ.get(name)
    if raw is None:
        return knob.default
    try:
        val = knob.cast(raw)
    except ValueError:
        raise ValueError(
            f"{name}={raw!r} (want {knob.cast.__name__})") from None
    if knob.choices is not None and val not in knob.choices:
        raise ValueError(f"{name}={val!r} (want {'|'.join(knob.choices)})")
    return val
