"""Parameter schema: one declarative source for init / abstract / sharding.

Every parameter leaf is declared once as a `LeafSpec` (shape + logical axis
names + initializer).  From the same schema tree we derive:

  * `init_params`      — materialized arrays (smoke tests, examples);
  * `abstract_params`  — ShapeDtypeStructs (dry-run: no allocation, the
                         qwen2-72b table never touches host RAM);
  * sharding specs     — via distributed.sharding rules mapping logical
                         axes ("heads", "ff", "vocab", ...) to mesh axes.

This mirrors how the Bundle stays hardware-agnostic: the schema is part of
the portable program; the logical->mesh mapping is injected at deployment.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp

__all__ = ["LeafSpec", "init_params", "abstract_params", "map_leaves", "leaf_items"]

Tree = dict[str, Any]

# axes along which a leaf stacks independent matrices
_STACK_AXES = ("layers", "experts")


@dataclasses.dataclass(frozen=True)
class LeafSpec:
    shape: tuple[int, ...]
    axes: tuple[str | None, ...]          # logical axis name per dim
    init: str = "normal"                  # normal | zeros | ones | scaled |
                                          # log_uniform | dt_bias
    scale: float = 0.02                   # std of "normal"
    dtype: str | None = None              # None -> model default
    fan_in_dims: int = 1                  # "scaled": leading dims contracted
    gain: float = 1.0                     # "scaled": std is gain/sqrt(fan_in)
    bounds: tuple[float, float] = (1.0, 1.0)   # log_uniform / dt_bias range

    def __post_init__(self) -> None:
        if len(self.shape) != len(self.axes):
            raise ValueError(f"shape {self.shape} vs axes {self.axes} rank mismatch")

    def materialize(self, key: jax.Array, default_dtype: jnp.dtype) -> jax.Array:
        dtype = jnp.dtype(self.dtype) if self.dtype else default_dtype
        if self.init == "zeros":
            return jnp.zeros(self.shape, dtype)
        if self.init == "ones":
            return jnp.ones(self.shape, dtype)
        if self.init == "normal":
            return (jax.random.normal(key, self.shape) * self.scale).astype(dtype)
        if self.init == "scaled":  # fan-in scaled
            # a stacked leaf holds one matrix per layer (or expert): its
            # fan-in is that matrix's, never the stack's length
            dims = [n for n, a in zip(self.shape, self.axes)
                    if a not in _STACK_AXES]
            fan_in = math.prod(dims[:self.fan_in_dims])
            s = self.gain / math.sqrt(max(fan_in, 1))
            return (jax.random.normal(key, self.shape) * s).astype(dtype)
        lo, hi = self.bounds
        if self.init == "log_uniform":   # log(U[lo, hi]): Mamba's A_log
            u = jax.random.uniform(key, self.shape, minval=lo, maxval=hi)
            return jnp.log(u).astype(dtype)
        if self.init == "dt_bias":
            # softplus^-1 of dt drawn log-uniform in [lo, hi] (Mamba's
            # dt_min/dt_max), so softplus(dt_bias) starts at that dt
            dt = jnp.exp(jax.random.uniform(key, self.shape, minval=math.log(lo),
                                            maxval=math.log(hi)))
            return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
        raise ValueError(f"unknown init {self.init!r}")

    def abstract(self, default_dtype: jnp.dtype) -> jax.ShapeDtypeStruct:
        dtype = jnp.dtype(self.dtype) if self.dtype else default_dtype
        return jax.ShapeDtypeStruct(self.shape, dtype)


def leaf_items(tree: Tree, prefix: str = "") -> list[tuple[str, LeafSpec]]:
    out: list[tuple[str, LeafSpec]] = []
    for k, v in tree.items():
        path = f"{prefix}/{k}" if prefix else k
        if isinstance(v, LeafSpec):
            out.append((path, v))
        else:
            out.extend(leaf_items(v, path))
    return out


def map_leaves(fn: Callable[[str, LeafSpec], Any], tree: Tree, prefix: str = "") -> Tree:
    out: Tree = {}
    for k, v in tree.items():
        path = f"{prefix}/{k}" if prefix else k
        out[k] = fn(path, v) if isinstance(v, LeafSpec) else map_leaves(fn, v, path)
    return out


def init_params(schema: Tree, key: jax.Array, default_dtype: str) -> Tree:
    dd = jnp.dtype(default_dtype)
    leaves = leaf_items(schema)
    keys = jax.random.split(key, max(len(leaves), 1))
    key_of = {path: keys[i] for i, (path, _) in enumerate(leaves)}
    return map_leaves(lambda p, s: s.materialize(key_of[p], dd), schema)


def abstract_params(schema: Tree, default_dtype: str) -> Tree:
    dd = jnp.dtype(default_dtype)
    return map_leaves(lambda _, s: s.abstract(dd), schema)
