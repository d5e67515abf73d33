"""JAX's persistent compilation cache for the entry points.

`enable_compile_cache()` is called by the programs a user runs
(`repro.launch.serve`, `repro.launch.train`, `chip_smoke.py`), never on
library import.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
reads it and nothing else is configured; otherwise compiled programs go
to ``.jax_cache/`` at the root of the checkout.  The directory is part of
the cache key, so it is fixed: no temporary name, PID or timestamp.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Mapping

__all__ = ["ENV_COMPILE_CACHE", "CHECKOUT_CACHE", "compile_cache_dir",
           "enable_compile_cache"]

ENV_COMPILE_CACHE = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir(env: Mapping[str, str] | None = None) -> Path:
    """The directory compiled programs land in: the env var, else the
    checkout's fixed ``.jax_cache/``."""
    env = os.environ if env is None else env
    value = env.get(ENV_COMPILE_CACHE, "").strip()
    return Path(value) if value else CHECKOUT_CACHE


def enable_compile_cache(env: Mapping[str, str] | None = None) -> Path:
    """Point JAX's persistent cache at `compile_cache_dir` and return it."""
    import jax

    env = os.environ if env is None else env
    path = compile_cache_dir(env)
    if not env.get(ENV_COMPILE_CACHE, "").strip():
        path.mkdir(parents=True, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", str(path))
    return path
