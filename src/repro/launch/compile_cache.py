"""Persistent XLA compilation cache for the launchers.

A cold start on an accelerator spends most of its time compiling the
weight-preprocess DP and the engine window programs.  JAX's persistent
cache keeps those executables on disk, and the directory is part of every
entry's key, so the path must stay put across processes and runs:

* ``JAX_COMPILATION_CACHE_DIR`` (read by JAX itself) wins — a deployment
  places the cache from outside and nothing here overrides it;
* otherwise the cache lives at ``<checkout>/.jax_cache`` (git-ignored),
  never at a temporary, pid- or time-derived path that a second start
  could not find again.
"""
from __future__ import annotations

import os

#: the repository checkout holding ``src/repro``
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory
    (see the module docstring) and return that directory.  Call before
    the first compile."""
    import jax
    cur = jax.config.jax_compilation_cache_dir
    if cur:
        return cur
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
