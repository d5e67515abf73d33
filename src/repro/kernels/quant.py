"""Shared quantization numerics: int8 / fp8 formats, one set of rules.

Every reduced-precision consumer in the tree — the DCN gradient
compressor in ``distributed/collectives.py``, the quantized matmul and
quantized-KV attention kernels, the checkpoint's per-channel weight
scales, and the serving engine's int8 KV pools — quantizes through this
module, so the numerics the quantization-conformance grid pins are the
numerics every layer actually runs.

Two formats (docs/quantization.md):

  * ``int8`` — symmetric linear: ``scale = amax / 127``, values clipped
    to [-127, 127] (note: -128 is never produced, so negation is exact).
    Round-trip error is bounded by ``scale / 2`` per element — the
    hypothesis property in tests/test_kernels_property.py.
  * ``fp8`` — jnp.float8_e4m3fn (simulated on hosts without fp8
    hardware): ``scale = amax / 448`` (the e4m3fn max-normal), then a
    cast through the fp8 grid.  Relative error ~2^-3 near amax; the
    conformance grid pins the looser envelope.

Scales are always float32 and always strictly positive (the ``EPS``
floor), so dequantization never divides by zero; the attention
kernels read them from an fp32 SMEM operand.
"""

from __future__ import annotations

import jax.numpy as jnp

__all__ = [
    "EPS",
    "FORMATS",
    "FP8_DTYPE",
    "FP8_MAX",
    "INT8_MAX",
    "compress_int8",
    "decompress_int8",
    "dequantize",
    "quantize",
    "quantize_per_channel",
    "storage_dtype",
]

INT8_MAX = 127.0
# max normal of float8_e4m3fn (S.1110.111 = 448)
FP8_MAX = 448.0
# amax floor: keeps every scale strictly positive (an all-zero tensor
# quantizes to zeros with a tiny, harmless scale instead of NaNs)
EPS = 1e-12

FORMATS = ("int8", "fp8")

FP8_DTYPE = jnp.float8_e4m3fn


def storage_dtype(fmt: str):
    """The cache/checkpoint storage dtype of a format (1 byte each)."""
    if fmt == "int8":
        return jnp.int8
    if fmt == "fp8":
        return FP8_DTYPE
    raise ValueError(f"unknown quantization format {fmt!r}")


def _scale_from_amax(amax: jnp.ndarray, fmt: str) -> jnp.ndarray:
    top = INT8_MAX if fmt == "int8" else FP8_MAX
    if fmt not in FORMATS:
        raise ValueError(f"unknown quantization format {fmt!r}")
    return (jnp.maximum(amax, EPS) / top).astype(jnp.float32)


def quantize(x: jnp.ndarray, fmt: str = "int8",
             scale: jnp.ndarray | None = None):
    """Whole-tensor quantization: ``(q, scale)`` with a single scalar
    scale (derived from amax unless a calibrated one is passed)."""
    if scale is None:
        scale = _scale_from_amax(jnp.max(jnp.abs(x)), fmt)
    else:
        scale = jnp.asarray(scale, jnp.float32)
    y = x.astype(jnp.float32) / scale
    if fmt == "int8":
        q = jnp.clip(jnp.round(y), -INT8_MAX, INT8_MAX).astype(jnp.int8)
    else:
        q = jnp.clip(y, -FP8_MAX, FP8_MAX).astype(FP8_DTYPE)
    return q, scale


def quantize_per_channel(x: jnp.ndarray, axis: int = -1, fmt: str = "int8"):
    """Per-channel quantization along ``axis``: ``(q, scale)`` where
    ``scale`` has ``x``'s shape with ``axis`` removed (one scale per
    output channel — the checkpoint weight-scale schema)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = _scale_from_amax(amax, fmt)
    y = x.astype(jnp.float32) / scale
    if fmt == "int8":
        q = jnp.clip(jnp.round(y), -INT8_MAX, INT8_MAX).astype(jnp.int8)
    else:
        q = jnp.clip(y, -FP8_MAX, FP8_MAX).astype(FP8_DTYPE)
    return q, jnp.squeeze(scale, axis=axis)


def dequantize(q: jnp.ndarray, scale: jnp.ndarray, axis: int = -1,
               dtype=jnp.float32) -> jnp.ndarray:
    """Invert quantize/quantize_per_channel.  ``scale`` may be a scalar
    (whole-tensor) or a per-channel vector matched to ``axis``."""
    scale = jnp.asarray(scale, jnp.float32)
    if scale.ndim and q.ndim > scale.ndim:
        scale = jnp.expand_dims(scale, axis=axis)
    return (q.astype(jnp.float32) * scale).astype(dtype)


def compress_int8(x: jnp.ndarray):
    """Whole-tensor symmetric int8 with a scalar scale — the DCN
    gradient compressor (extracted from distributed/collectives.py;
    the hierarchical all-reduce sums int32 and rescales by the pmax'd
    scale, so a conservative shared scale is exactly what it needs)."""
    return quantize(x, "int8")


def decompress_int8(q: jnp.ndarray, scale: jnp.ndarray,
                    dtype=jnp.float32) -> jnp.ndarray:
    return dequantize(q, scale, dtype=dtype)
