"""Where the entry points put JAX's persistent compilation cache.

Each scenario runs in its own python: the cache directory is process-wide
JAX configuration, and the test process must keep its own.
"""

import subprocess
import sys
import textwrap
from pathlib import Path

from conftest import subprocess_env

from repro.launch.compile_cache import (
    CHECKOUT_CACHE,
    ENV_COMPILE_CACHE,
    compile_cache_dir,
)

REPO = Path(__file__).resolve().parents[1]


def _run(code: str, env: dict) -> str:
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout


def test_env_var_wins():
    assert compile_cache_dir({ENV_COMPILE_CACHE: "/elsewhere/cache"}) == \
        Path("/elsewhere/cache")


def test_default_is_the_fixed_gitignored_checkout_dir():
    assert compile_cache_dir({}) == REPO / ".jax_cache"
    assert compile_cache_dir({ENV_COMPILE_CACHE: "  "}) == CHECKOUT_CACHE
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().splitlines()


def test_compiled_programs_land_in_the_env_dir(tmp_path):
    """With the variable set the helper configures nothing, and what JAX
    compiles lands in that directory."""
    env = subprocess_env(1)
    env[ENV_COMPILE_CACHE] = str(tmp_path)
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.launch.compile_cache import enable_compile_cache
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        print("DIR", enable_compile_cache())
        jax.jit(lambda x: x * 2 + 1)(jnp.ones(8)).block_until_ready()
    """, env)
    assert f"DIR {tmp_path}" in out
    assert any(tmp_path.iterdir())


def test_without_env_var_programs_land_in_the_checkout_dir(tmp_path):
    """Without the variable the helper points JAX at the checkout's
    directory (moved under tmp_path here, so the test writes nothing
    into the checkout)."""
    env = subprocess_env(1)
    env.pop(ENV_COMPILE_CACHE, None)
    where = tmp_path / "checkout_cache"
    out = _run(f"""
        import jax, jax.numpy as jnp
        from repro.launch import compile_cache
        compile_cache.CHECKOUT_CACHE = compile_cache.Path({str(where)!r})
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        print("DIR", compile_cache.enable_compile_cache())
        print("JAX", jax.config.jax_compilation_cache_dir)
        jax.jit(lambda x: x * 2 + 1)(jnp.ones(8)).block_until_ready()
    """, env)
    assert f"DIR {where}" in out and f"JAX {where}" in out
    assert any(where.iterdir())
