"""Where JAX keeps its persistent compilation cache.

Entry points that compile for the chip (``chip_smoke.py``,
``examples/serve_stream.py``, ``benchmarks/run.py``) call
``use_compile_cache()`` before their first compile, so repeated runs from
one checkout reuse the compiled stream-engine kernels and serve steps.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout's own cache directory (listed in .gitignore). A fixed path:
#: a temp- or run-named directory would start empty on every run.
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    no other directory is set here. Otherwise the cache lives at
    ``<checkout>/.jax_cache``. Every compile is cached, however quick, so
    a second run skips compilation entirely."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
