"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``python -m repro.service``) call :func:`use_compile_cache` before their
first jit; importing ``repro`` never changes JAX's configuration.

The rule: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and this sets nothing.  Otherwise the cache goes to a fixed
``.jax_cache/`` at the checkout root.  The path is part of the cache
key, so it is never built from a temp name, a pid or the time: a
directory that moves never hits.
"""
from __future__ import annotations

import importlib.util
import os
from pathlib import Path
from typing import Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> Optional[str]:
    """Apply the rule above and return the cache directory in use
    (``None`` when JAX is not installed)."""
    if importlib.util.find_spec("jax") is None:
        return None
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
