"""Small shared utilities."""
from __future__ import annotations

import jax


def ensure_x64() -> None:
    """Enable 64-bit jax types.

    TIMEST's sampling weights are exact integer match-counts that reach ~1e15
    on real graphs (paper Table 7); the estimator therefore runs all weight
    arithmetic in int64 (exact — no floating-point CDF error at all).  Model
    code elsewhere in the framework uses explicit f32/bf16 dtypes throughout,
    so flipping the global default is safe for the rest of the system.
    """
    jax.config.update("jax_enable_x64", True)
