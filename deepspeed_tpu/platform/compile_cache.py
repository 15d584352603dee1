"""Persistent XLA compile cache for the entry scripts.

A cold start compiles every program the run touches — minutes for a
trainer plus a server at real widths — and a fresh machine has nothing
compiled.  The entry scripts (``chip_smoke.py``,
``python -m deepspeed_tpu.gateway``, ``python -m deepspeed_tpu.comm.bench``)
call :func:`enable_compile_cache` before
their first compile; ``import deepspeed_tpu`` does not, and neither does
the test suite.
"""

from __future__ import annotations

import os

import jax

# the directory is part of every cache key's lookup, so it never moves:
# one fixed, git-ignored place at the root of the checkout
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_compile_cache")


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this sets
    no directory.  Unset: :data:`DEFAULT_DIR`."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    # a run is hundreds of sub-second programs around a few long ones;
    # the default 1 s floor would leave the many uncached
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir
