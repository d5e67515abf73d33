"""Shared helpers for the paper-table benchmarks."""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
SRC = REPO / "src"


def run_subprocess(code: str, *, devices: int = 1, timeout: int = 900) -> str:
    """Run a benchmark snippet on `devices` forced host devices.

    The child is pinned to the CPU: on a machine with a TPU it would
    otherwise claim the chip, which belongs to one process at a time."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(SRC)
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        env=env, capture_output=True, text=True, timeout=timeout,
    )
    if proc.returncode != 0:
        raise RuntimeError(f"benchmark subprocess failed:\n{proc.stderr[-2000:]}")
    return proc.stdout


def timeit(fn, *, warmup: int = 1, iters: int = 5) -> float:
    """Median wall-clock seconds per call."""
    for _ in range(warmup):
        fn()
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def row(name: str, us_per_call: float, derived: str) -> tuple[str, float, str]:
    return (name, us_per_call, derived)
