"""Production mesh construction (assignment-mandated geometry).

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (device count is locked at first backend init,
which dryrun.py configures before any jax import).
"""

from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_host_mesh"]


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> jax.sharding.Mesh:
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(*, data: int | None = None, model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over whatever devices this host actually has (tests,
    examples, the 'cluster' platform)."""
    n = len(jax.devices())
    if data is None:
        data = n // model
    assert data * model <= n, (data, model, n)
    if model > 1:
        return _auto_mesh((data, model), ("data", "model"))
    return _auto_mesh((data,), ("data",))
