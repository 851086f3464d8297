"""jit'd wrapper: interpret auto-select (padding lives in the kernel call).

CPU-interpret only until ROADMAP S2: the TPU compiler refuses the kernel
(see ``kernel.py``)."""
from __future__ import annotations

from functools import partial

import jax

from .kernel import interval_weight_call


@partial(jax.jit, static_argnames=("bq", "interpret"))
def interval_weight(csr_t, ps_own, ps_prev, p0, p1, tlo, thi, brk, *,
                    bq: int = 1024, interpret: bool | None = None):
    """Batched two-piece interval weight sums (see kernel.py).

    Ragged query batches are padded to a ``bq`` multiple inside
    ``interval_weight_call`` and the bisection trip count adapts to the
    shard size, so any (Q, m) combination is accepted.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return interval_weight_call(csr_t, ps_own, ps_prev, p0, p1, tlo, thi,
                                brk, bq=bq, interpret=interpret)
