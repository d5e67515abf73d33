"""Composable transformer layers (pure JAX; swappable ops via OpBinding).

Every hardware-sensitive op goes through the container's op binding
(`binding["attention"]`, `binding["rmsnorm"]`, ...) — the model never
imports a kernel directly, which is the whole point of the paper's
portability discipline.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.schema import LeafSpec

__all__ = [
    "ParallelCtx",
    "rotary",
    "norm_apply",
    "norm_schema",
    "attention_schema",
    "attention_apply",
    "attention_decode",
    "attention_chunk",
    "dequant_param",
    "mlp_schema",
    "mlp_apply",
    "sinusoidal_positions",
]


def dequant_param(p, dtype=jnp.float32):
    """Materialize a quantized weight subtree ``{"q", "scale"}`` (the
    ``restore_checkpoint(dequantize=False)`` layout — codes with axis -2
    reduced to per-channel scales) back to a dense array; full-precision
    leaves pass through untouched."""
    if isinstance(p, dict) and "q" in p and "scale" in p:
        from repro.kernels.quant import dequantize

        return dequantize(p["q"], p["scale"], axis=-2, dtype=dtype)
    return p


@dataclasses.dataclass(frozen=True)
class ParallelCtx:
    """Deployment-injected parallel context (None mesh = laptop)."""

    mesh: jax.sharding.Mesh | None = None
    batch_axes: tuple[str, ...] = ()       # e.g. ("pod", "data")
    model_axis: str | None = None          # e.g. "model"
    seq_shard: bool = False                # SP: shard activations' seq dim

    @property
    def active(self) -> bool:
        return self.mesh is not None

    def constrain(self, x: jnp.ndarray, spec) -> jnp.ndarray:
        if not self.active:
            return x
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(self.mesh, spec)
        )

    def residual_spec(self, seq_len: int | None = None):
        P = jax.sharding.PartitionSpec
        seq = None
        if self.seq_shard and self.model_axis and seq_len:
            size = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))[
                self.model_axis
            ]
            if seq_len % size == 0:
                seq = self.model_axis
        return P(self.batch_axes or None, seq, None)

    def constrain_residual(self, x: jnp.ndarray) -> jnp.ndarray:
        """SP anchor on the (B, S, D) residual stream (no-op off-mesh or
        when S doesn't divide, e.g. decode's S=1)."""
        if not self.active:
            return x
        return self.constrain(x, self.residual_spec(x.shape[1]))

    def heads_spec(self, n_heads: int, head_dim: int):
        """Spec for (B, S, H, Dh) activations: heads on the model axis when
        divisible, else head_dim — anchors XLA's propagation through the
        GQA reshapes (without this the partitioner falls back to
        'involuntary full rematerialization' copies)."""
        P = jax.sharding.PartitionSpec
        if not self.active or self.model_axis is None:
            return None
        size = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))[self.model_axis]
        if n_heads % size == 0:
            return P(self.batch_axes or None, None, self.model_axis, None)
        if head_dim % size == 0:
            return P(self.batch_axes or None, None, None, self.model_axis)
        return P(self.batch_axes or None, None, None, None)

    def constrain_heads(self, x: jnp.ndarray) -> jnp.ndarray:
        spec = self.heads_spec(x.shape[2], x.shape[3])
        return self.constrain(x, spec) if spec is not None else x


# --------------------------------------------------------------------------- #
# rotary / positional
# --------------------------------------------------------------------------- #
def rotary(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (B, S, H, Dh); positions: (S,) or (B, S)."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        ang = positions.astype(jnp.float32)[None, :, None] * freqs[None, None, :]
        ang = ang[:, :, None, :]                    # (1, S, 1, half)
    else:
        ang = positions.astype(jnp.float32)[:, :, None] * freqs[None, None, :]
        ang = ang[:, :, None, :]                    # (B, S, 1, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


def sinusoidal_positions(seq: int, d: int, offset: jnp.ndarray | int = 0) -> jnp.ndarray:
    """Whisper-style sinusoidal embeddings, computed (no parameters)."""
    half = d // 2
    pos = jnp.arange(seq, dtype=jnp.float32) + jnp.asarray(offset, jnp.float32)
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32) * (jnp.log(10000.0) / max(half - 1, 1)))
    ang = pos[:, None] * freqs[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# --------------------------------------------------------------------------- #
# norms
# --------------------------------------------------------------------------- #
def norm_schema(cfg: ModelConfig, d: int | None = None) -> dict[str, LeafSpec]:
    d = d or cfg.d_model
    leaves = {"scale": LeafSpec((d,), ("norm",), init="ones")}
    if cfg.norm == "layernorm":
        leaves["bias"] = LeafSpec((d,), ("norm",), init="zeros")
    return leaves


def norm_apply(params, x, cfg: ModelConfig, binding, eps: float = 1e-6):
    """Normalize `x` into the compute dtype (an fp32 residual stream,
    `cfg.residual_fp32`, is read here and cast down on the way out)."""
    cdt = jnp.dtype(cfg.dtype)
    if cfg.norm == "rmsnorm":
        return binding["rmsnorm"](x, params["scale"], eps=eps).astype(cdt)
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    return y.astype(cdt)


# --------------------------------------------------------------------------- #
# attention
# --------------------------------------------------------------------------- #
def attention_schema(cfg: ModelConfig, n_heads: int | None = None) -> dict[str, LeafSpec]:
    d, h, kv, dh = cfg.d_model, n_heads or cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    leaves = {
        "wq": LeafSpec((d, h, dh), ("embed", "heads", "head_dim"), init="scaled"),
        "wk": LeafSpec((d, kv, dh), ("embed", "kv_heads", "head_dim"), init="scaled"),
        "wv": LeafSpec((d, kv, dh), ("embed", "kv_heads", "head_dim"), init="scaled"),
        "wo": LeafSpec((h, dh, d), ("heads", "head_dim", "embed"), init="scaled",
                       fan_in_dims=2),
    }
    if cfg.qkv_bias:
        leaves["bq"] = LeafSpec((h, dh), ("heads", "head_dim"), init="zeros")
        leaves["bk"] = LeafSpec((kv, dh), ("kv_heads", "head_dim"), init="zeros")
        leaves["bv"] = LeafSpec((kv, dh), ("kv_heads", "head_dim"), init="zeros")
    return leaves


def attention_apply(
    params,
    x: jnp.ndarray,
    cfg: ModelConfig,
    binding,
    *,
    positions: jnp.ndarray | None = None,
    causal: bool = True,
    kv_source: jnp.ndarray | None = None,       # cross-attention input
    use_rope: bool = True,
    pctx: "ParallelCtx | None" = None,
    real_group: tuple[int, int] | None = None,   # (g, g') head padding
) -> tuple[jnp.ndarray, dict[str, jnp.ndarray]]:
    """Full-sequence attention (train / prefill).  Returns (out, kv)."""
    src = x if kv_source is None else kv_source
    q = jnp.einsum("bsd,dhk->bshk", x, dequant_param(params["wq"], x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", src, dequant_param(params["wk"], x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", src, dequant_param(params["wv"], x.dtype))
    if cfg.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    if use_rope and positions is not None:
        q = rotary(q, positions, cfg.rope_theta)
        k = rotary(k, positions, cfg.rope_theta)
    if pctx is not None and pctx.active:
        q = pctx.constrain_heads(q)
        k = pctx.constrain_heads(k)
        v = pctx.constrain_heads(v)
    out = binding["attention"](q, k, v, causal=causal)
    out = _mask_padded_heads(out, real_group)
    if pctx is not None and pctx.active:
        out = pctx.constrain_heads(out)
    y = jnp.einsum("bshk,hkd->bsd", out, dequant_param(params["wo"], x.dtype))
    return y, {"k": k, "v": v}


def _mask_padded_heads(out: jnp.ndarray, real_group: tuple[int, int] | None):
    """Zero the outputs of TP-alignment padding heads (slots g..g'-1 of
    each GQA group), making the padded model numerically identical to the
    unpadded one (padded slots get zero forward contribution AND zero
    gradients through this mask)."""
    if real_group is None:
        return out
    g, gp = real_group
    if g == gp:
        return out
    h = out.shape[-2]
    mask = (jnp.arange(h) % gp) < g
    return out * mask[:, None].astype(out.dtype)


def _quant_update(upd: jnp.ndarray, scale: jnp.ndarray, dtype) -> jnp.ndarray:
    """Quantize a fresh k/v projection onto a quantized cache's grid using
    the slot's static calibrated scale (() or (B,) fp32).  A plain astype
    would truncate int8 codes; this divides by the scale and rounds/clips
    per format — the exact inverse of the kernels' in-VMEM dequant."""
    from repro.kernels.quant import FP8_MAX, INT8_MAX

    s = jnp.asarray(scale, jnp.float32)
    s = s.reshape(s.shape + (1,) * (upd.ndim - s.ndim))
    y = upd.astype(jnp.float32) / s
    if jnp.dtype(dtype) == jnp.int8:
        return jnp.clip(jnp.round(y), -INT8_MAX, INT8_MAX).astype(jnp.int8)
    return jnp.clip(y, -FP8_MAX, FP8_MAX).astype(dtype)


def _cache_write(buf: jnp.ndarray, upd: jnp.ndarray, pos: jnp.ndarray) -> jnp.ndarray:
    """Write `upd` (B, S, KV, Dh) into `buf` at seq offset `pos` — per batch
    row when pos is (B,) (continuous batching: every slot at its own
    position), one shared offset when pos is scalar."""
    upd = upd.astype(buf.dtype)
    if pos.ndim:
        return jax.vmap(
            lambda b, u, p: jax.lax.dynamic_update_slice_in_dim(b, u, p, axis=0)
        )(buf, upd, pos)
    return jax.lax.dynamic_update_slice_in_dim(buf, upd, pos, axis=1)


def _paged_decode_write(
    pool: jnp.ndarray,           # (P, page, KV, Dh)
    upd: jnp.ndarray,            # (B, 1, KV, Dh)
    pos: jnp.ndarray,            # () or (B,) int32
    block_tables: jnp.ndarray,   # (B, nblocks) int32
) -> jnp.ndarray:
    """Scatter each row's new token into its page: logical position p of
    row b lands in page block_tables[b, p // page] at offset p % page.
    Parked rows (table row all zeros) write into the reserved park page —
    harmless garbage, their logits are discarded by the active mask."""
    b = upd.shape[0]
    page = pool.shape[1]
    posv = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    phys = block_tables[jnp.arange(b), posv // page]
    return pool.at[phys, posv % page].set(upd[:, 0].astype(pool.dtype))


def attention_decode(
    params,
    x: jnp.ndarray,                      # (B, 1, D)
    cache: dict[str, jnp.ndarray],       # k/v: (B, Smax, KV, Dh)
    pos: jnp.ndarray,                    # () or (B,) int32 — new token index
    cfg: ModelConfig,
    binding,
    *,
    use_rope: bool = True,
    cross: bool = False,
    pctx: "ParallelCtx | None" = None,
    real_group: tuple[int, int] | None = None,
    block_tables: jnp.ndarray | None = None,   # (B, nblocks) — paged cache
    window: jnp.ndarray | None = None,         # () or (B,) i32 sliding window
) -> tuple[jnp.ndarray, dict[str, jnp.ndarray]]:
    """One-token attention against the cache; writes the new k/v (self only).

    With `block_tables` the cache k/v are page pools (P, page, KV, Dh)
    shared by all slots; the write scatters through the table and the op
    gathers through it (paged decode_attention ABI).  With `window` only
    the trailing `window` cache slots are attended (sliding-window decode
    ABI) — out-of-window pages may already have been released.

    A quantized cache carries ``"k_scale"``/``"v_scale"`` leaves (static
    per-slot calibration, () or (B,) fp32): fresh k/v are quantized onto
    the cache grid before the write and the scales ride as trailing
    binding args — the op dequantizes in-kernel (scale meta ABI)."""
    k_scale, v_scale = cache.get("k_scale"), cache.get("v_scale")
    rope_pos = pos[None] if pos.ndim == 0 else pos[:, None]
    q = jnp.einsum("bsd,dhk->bshk", x, dequant_param(params["wq"], x.dtype))
    if cfg.qkv_bias:
        q = q + params["bq"]
    if use_rope:
        q = rotary(q, rope_pos, cfg.rope_theta)
    if pctx is not None and pctx.active:
        q = pctx.constrain_heads(q)
    if cross:
        k_cache, v_cache = cache["k"], cache["v"]
        cache_len = jnp.asarray(k_cache.shape[1] - 1, jnp.int32)
        out = binding["decode_attention"](q, k_cache, v_cache, cache_len)
        new_cache = cache
    else:
        k = jnp.einsum("bsd,dhk->bshk", x, dequant_param(params["wk"], x.dtype))
        v = jnp.einsum("bsd,dhk->bshk", x, dequant_param(params["wv"], x.dtype))
        if cfg.qkv_bias:
            k, v = k + params["bk"], v + params["bv"]
        if use_rope:
            k = rotary(k, rope_pos, cfg.rope_theta)
        if k_scale is not None:
            k = _quant_update(k, k_scale, cache["k"].dtype)
            v = _quant_update(v, v_scale, cache["v"].dtype)
        if block_tables is not None:
            k_cache = _paged_decode_write(cache["k"], k, pos, block_tables)
            v_cache = _paged_decode_write(cache["v"], v, pos, block_tables)
        else:
            k_cache = _cache_write(cache["k"], k, pos)
            v_cache = _cache_write(cache["v"], v, pos)
        if k_scale is not None:
            out = binding["decode_attention"](q, k_cache, v_cache, pos,
                                              block_tables, window,
                                              k_scale, v_scale)
        elif window is not None:
            out = binding["decode_attention"](q, k_cache, v_cache, pos,
                                              block_tables, window)
        elif block_tables is not None:
            out = binding["decode_attention"](q, k_cache, v_cache, pos,
                                              block_tables)
        else:
            out = binding["decode_attention"](q, k_cache, v_cache, pos)
        new_cache = {"k": k_cache, "v": v_cache}
        if k_scale is not None:
            new_cache["k_scale"], new_cache["v_scale"] = k_scale, v_scale
    out = _mask_padded_heads(out, real_group)
    if pctx is not None and pctx.active:
        out = pctx.constrain_heads(out)
    y = jnp.einsum("bshk,hkd->bsd", out, dequant_param(params["wo"], x.dtype))
    return y, new_cache


def attention_chunk(
    params,
    x: jnp.ndarray,                      # (B, C, D) — chunk of prompt
    cache: dict[str, jnp.ndarray],       # k/v: (B, Smax, KV, Dh)
    pos: jnp.ndarray,                    # () int32 — chunk's global start
    cfg: ModelConfig,
    binding,
    *,
    use_rope: bool = True,
    pctx: "ParallelCtx | None" = None,
    real_group: tuple[int, int] | None = None,
    block_tables: jnp.ndarray | None = None,   # (nblocks,) — this slot's row
    window: jnp.ndarray | None = None,         # () i32 sliding window
) -> tuple[jnp.ndarray, dict[str, jnp.ndarray]]:
    """Chunked-prefill attention: C prompt tokens at global positions
    pos..pos+C-1 against the partially filled cache.

    Writes the chunk's k/v into the cache window [pos, pos+C) and attends
    via binding["chunk_attention"] (query i sees cache keys <= pos+i).
    Positions past the prompt's true end carry garbage k/v, but every
    later query — in-chunk (causal mask) or decode (its own write lands
    first) — sees those slots only after they are overwritten, so no
    masking is needed here; the SSM path is where padding needs care.

    With `block_tables` (the prefilling slot's (nblocks,) table row,
    B == 1) the cache k/v are page pools and the serving invariant
    page == C makes the chunk's write exactly one page: the chunk at
    global position pos fills page block_tables[pos // page] whole.
    """
    k_scale, v_scale = cache.get("k_scale"), cache.get("v_scale")
    c = x.shape[1]
    chunk_pos = pos + jnp.arange(c)
    q = jnp.einsum("bsd,dhk->bshk", x, dequant_param(params["wq"], x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, dequant_param(params["wk"], x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, dequant_param(params["wv"], x.dtype))
    if cfg.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    if use_rope:
        q = rotary(q, chunk_pos, cfg.rope_theta)
        k = rotary(k, chunk_pos, cfg.rope_theta)
    if pctx is not None and pctx.active:
        q = pctx.constrain_heads(q)
    if k_scale is not None:
        k = _quant_update(k, k_scale, cache["k"].dtype)
        v = _quant_update(v, v_scale, cache["v"].dtype)
    if block_tables is not None:
        page = cache["k"].shape[1]
        assert c == page, f"paged prefill requires chunk == page, {c} != {page}"
        blk = jax.lax.dynamic_index_in_dim(
            jnp.asarray(block_tables, jnp.int32), pos // page, keepdims=False
        )
        k_cache = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (blk, 0, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (blk, 0, 0, 0))
        if k_scale is not None:
            out = binding["chunk_attention"](q, k_cache, v_cache, pos,
                                             block_tables[None], window,
                                             k_scale, v_scale)
        elif window is not None:
            out = binding["chunk_attention"](q, k_cache, v_cache, pos,
                                             block_tables[None], window)
        else:
            out = binding["chunk_attention"](q, k_cache, v_cache, pos,
                                             block_tables[None])
    else:
        k_cache = _cache_write(cache["k"], k, pos)
        v_cache = _cache_write(cache["v"], v, pos)
        if k_scale is not None:
            out = binding["chunk_attention"](q, k_cache, v_cache, pos,
                                             None, window, k_scale, v_scale)
        elif window is not None:
            out = binding["chunk_attention"](q, k_cache, v_cache, pos,
                                             None, window)
        else:
            out = binding["chunk_attention"](q, k_cache, v_cache, pos)
    out = _mask_padded_heads(out, real_group)
    if pctx is not None and pctx.active:
        out = pctx.constrain_heads(out)
    y = jnp.einsum("bshk,hkd->bsd", out, dequant_param(params["wo"], x.dtype))
    kv = {"k": k_cache, "v": v_cache}
    if k_scale is not None:
        kv["k_scale"], kv["v_scale"] = k_scale, v_scale
    return y, kv


# --------------------------------------------------------------------------- #
# dense MLP
# --------------------------------------------------------------------------- #
def mlp_schema(cfg: ModelConfig, d_ff: int | None = None) -> dict[str, LeafSpec]:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    leaves = {
        "w_in": LeafSpec((d, f), ("embed", "ff"), init="scaled"),
        "w_out": LeafSpec((f, d), ("ff", "embed"), init="scaled"),
    }
    if cfg.activation == "silu_glu":
        leaves["w_gate"] = LeafSpec((d, f), ("embed", "ff"), init="scaled")
    return leaves


def mlp_apply(params, x, cfg: ModelConfig, binding=None):
    """Dense MLP.  Quantized weight subtrees (``{"q", "scale"}``) route
    through ``binding["quant_matmul"]`` when a binding is supplied — the
    per-output-channel dequant happens inside the kernel, so the dense
    weight matrix is never materialized; without a binding (or for
    full-precision leaves) the plain einsum path runs."""

    def matmul(y, w):
        if isinstance(w, dict) and "q" in w and "scale" in w:
            if binding is not None and "quant_matmul" in binding:
                b, s, d = y.shape
                out = binding["quant_matmul"](
                    y.reshape(b * s, d), w["q"], w["scale"])
                return out.reshape(b, s, -1)
            w = dequant_param(w, y.dtype)
        return jnp.einsum("bsd,df->bsf", y, w)

    h = matmul(x, params["w_in"])
    if cfg.activation == "silu_glu":
        g = matmul(x, params["w_gate"])
        h = jax.nn.silu(g) * h
    else:
        h = jax.nn.gelu(h)
    return matmul(h, params["w_out"])
