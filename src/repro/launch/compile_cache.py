"""Where JAX keeps its persistent compilation cache.

A fresh process on the chip compiles every program from cold.  The
persistent cache lets later processes that see the same directory skip
that.  The directory is never built from a temporary name, a process id or
the time, so a run that repeats finds what an earlier one wrote:

* if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
  module sets nothing;
* otherwise the cache goes to ``.jax_cache`` at the root of the checkout
  (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: the fixed in-checkout location used when the environment names none
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
