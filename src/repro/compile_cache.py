"""Where the entry points keep JAX's persistent compilation cache.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` from the environment itself; when
it is set, this module sets nothing. Otherwise the cache goes to
``.jax_cache`` at the root of the checkout, one fixed directory, so a
second run of the same program finds what the first one compiled.

Entry points call ``enable_compile_cache()`` at start-up; importing the
library never touches the cache.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turns the persistent compilation cache on; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
