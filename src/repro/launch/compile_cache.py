"""JAX's persistent compilation cache, at one fixed place per checkout.

A chip run may start with no compiled code at all, and compiling is a
large share of a cold run. Every entry point (``chip_smoke.py``, the
trainer CLI, ``benchmarks.run`` and the figure CLIs) calls
:func:`enable_compile_cache` first, so the processes of one run share
what they compile. A later run finds those entries only at the same
directory, so it is a fixed path — never a tempdir, a pid or a time.
"""

from __future__ import annotations

import os

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

# <checkout>/.jax_cache (this file is <checkout>/src/repro/launch/...)
CHECKOUT_CACHE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, os.pardir,
    os.pardir, ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here; otherwise the cache goes to
    :data:`CHECKOUT_CACHE_DIR` (gitignored)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
