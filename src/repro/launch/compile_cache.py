"""Where JAX's persistent compilation cache lives.

One helper, called by the entry points (``chip_smoke.py``,
``repro.launch.serve``, ``benchmarks.run``) and never on import: a library
that sets process-wide JAX config behind its callers' backs would surprise
every embedding program and test.
"""

from __future__ import annotations

import os
from pathlib import Path

# a fixed directory inside the checkout (git-ignored): a temporary or
# per-process directory would be empty on every run and never hit
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache goes to
    :data:`CHECKOUT_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    CHECKOUT_CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
