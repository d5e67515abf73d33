"""Quantized matmul — Pallas TPU kernel (int8/fp8 weights, fp32 math).

``y = (x @ dequantize(qw)) * 1`` where ``qw`` is a quantized [D, F]
weight with one float32 scale per *output channel* (the checkpoint's
per-channel schema, docs/quantization.md): dequantizing per-channel
along F commutes with the contraction over D, so the kernel streams the
1-byte weight from HBM, upcasts the tile in VMEM, contracts in fp32,
and applies the channel scales to the product — the memory-bound
serving matmul moves a quarter of the fp32 bytes.

Tiling: (block_m, D) activation tiles x (D, block_n) weight tiles; the
contraction dimension stays resident like the other narrow-D kernels
(rmsnorm, moe_gmm's degraded single k step).  block_m / block_n come
from the injected tuning ``config`` like every other swap op.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.tuning.config import BlockConfig, default_config

__all__ = ["quant_matmul"]

_DEFAULTS = default_config("quant_matmul")


def _quant_matmul_kernel(x_ref, qw_ref, scale_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    w = qw_ref[...].astype(jnp.float32)
    acc = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    o_ref[...] = (acc * scale_ref[...]).astype(o_ref.dtype)   # (1, bn) row


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "config", "interpret")
)
def quant_matmul(
    x: jnp.ndarray,
    qw: jnp.ndarray,
    scale: jnp.ndarray,
    *,
    block_m: int | None = None,
    block_n: int | None = None,
    config: BlockConfig | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """x [T, D] float  @  qw [D, F] int8/fp8  *  scale [F] fp32 -> [T, F].

    Output dtype follows x (fp32 accumulation inside the kernel).
    """
    if x.ndim != 2 or qw.ndim != 2 or scale.ndim != 1:
        raise ValueError(
            f"quant_matmul wants x[T,D], qw[D,F], scale[F]; got "
            f"{x.shape}, {qw.shape}, {scale.shape}"
        )
    t, d = x.shape
    f = qw.shape[1]
    if qw.shape[0] != d or scale.shape[0] != f:
        raise ValueError(f"shape mismatch: x{x.shape} qw{qw.shape} "
                         f"scale{scale.shape}")
    cfg = config if config is not None else _DEFAULTS
    if block_m is None:
        block_m = cfg.get("block_m", _DEFAULTS["block_m"])
    if block_n is None:
        block_n = cfg.get("block_n", _DEFAULTS["block_n"])
    block_m = min(block_m, t)
    block_n = min(block_n, f)
    out = pl.pallas_call(
        _quant_matmul_kernel,
        grid=(pl.cdiv(t, block_m), pl.cdiv(f, block_n)),
        in_specs=[
            pl.BlockSpec((block_m, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, block_n), lambda i, j: (0, j)),
            # 2-D (1, F) scale row: Mosaic cannot tile a 1-D operand
            pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((t, f), x.dtype),
        interpret=interpret,
    )(x, qw, scale.reshape(1, f))
    return out
