"""Mamba-2 (SSD) layer — used by `mamba2-780m` and the Jamba hybrid.

Layer structure (arXiv:2405.21060):
  in_proj -> [z | x | B | C | dt]; causal depthwise conv on x; SSD scan
  (via binding["ssd_scan"]: chunked jnp reference or the Pallas kernel);
  D skip; RMSNorm(gated by silu(z)); out_proj.

Decode keeps two pieces of state per layer: the (conv_k-1) trailing inputs
for the depthwise conv and the (H, N, P) SSM state — both O(1) in sequence
length, which is what makes the `long_500k` cell runnable.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.ssd_scan_ref import ssd_decode_step_ref
from repro.models.schema import LeafSpec

__all__ = [
    "ssm_schema",
    "ssm_apply",
    "ssm_decode",
    "ssm_prefill_chunk",
    "ssm_init_cache_shapes",
]

_NGROUPS = 1  # B/C shared across heads (mamba2 default ngroups=1)


def ssm_schema(cfg: ModelConfig) -> dict[str, LeafSpec]:
    d = cfg.d_model
    din = cfg.ssm_d_inner
    h = cfg.ssm_heads
    n = cfg.ssm_state
    # Mamba2's published init (mamba_ssm `Mamba2`, `_init_weights`):
    # linear and conv weights at the torch default std 1/sqrt(3 fan_in),
    # out_proj further scaled by 1/sqrt(n_layer), dt log-uniform in
    # [1e-3, 1e-1] through dt_bias, A uniform in [1, 16].  With larger
    # steps and weights a deep random stack amplifies rounding chaotically.
    lin = 3 ** -0.5
    return {
        "w_z": LeafSpec((d, din), ("embed", "ssm_inner"), init="scaled", gain=lin),
        "w_x": LeafSpec((d, din), ("embed", "ssm_inner"), init="scaled", gain=lin),
        "w_b": LeafSpec((d, _NGROUPS * n), ("embed", None), init="scaled", gain=lin),
        "w_c": LeafSpec((d, _NGROUPS * n), ("embed", None), init="scaled", gain=lin),
        "w_dt": LeafSpec((d, h), ("embed", "ssm_heads"), init="scaled", gain=lin),
        "dt_bias": LeafSpec((h,), ("ssm_heads",), init="dt_bias", bounds=(1e-3, 1e-1)),
        "a_log": LeafSpec((h,), ("ssm_heads",), init="log_uniform", bounds=(1.0, 16.0)),
        "d_skip": LeafSpec((h,), ("ssm_heads",), init="ones"),
        "conv_w": LeafSpec((cfg.ssm_conv, din), (None, "ssm_inner"), init="scaled",
                           gain=lin),
        "conv_b": LeafSpec((din,), ("ssm_inner",), init="zeros"),
        "norm_scale": LeafSpec((din,), ("ssm_inner",), init="ones"),
        "w_out": LeafSpec((din, d), ("ssm_inner", "embed"), init="scaled",
                          gain=lin / math.sqrt(cfg.num_layers)),
    }


def _causal_conv(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Depthwise causal conv as shifted adds.  x: (B, S, Din), w: (K, Din)."""
    k = w.shape[0]
    pad = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    s = x.shape[1]
    y = jnp.zeros_like(x, dtype=jnp.float32)
    for i in range(k):
        y = y + pad[:, i : i + s, :].astype(jnp.float32) * w[i].astype(jnp.float32)
    return (y + b.astype(jnp.float32)).astype(x.dtype)


def _rms(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(x.dtype)


def _projections(params, x):
    z = jnp.einsum("bsd,de->bse", x, params["w_z"])
    xs = jnp.einsum("bsd,de->bse", x, params["w_x"])
    bm = jnp.einsum("bsd,dn->bsn", x, params["w_b"])
    cm = jnp.einsum("bsd,dn->bsn", x, params["w_c"])
    dt = jnp.einsum("bsd,dh->bsh", x, params["w_dt"])
    return z, xs, bm, cm, dt


def ssm_apply(
    params,
    x: jnp.ndarray,        # (B, S, D)
    cfg: ModelConfig,
    binding,
    *,
    return_state: bool = False,
):
    b, s, _ = x.shape
    h, p, n = cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state
    z, xs, bm, cm, dt = _projections(params, x)
    xs = _causal_conv(xs, params["conv_w"], params["conv_b"])
    xs = jax.nn.silu(xs)

    xh = xs.reshape(b, s, h, p)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(params["a_log"].astype(jnp.float32))
    bmg = bm.reshape(b, s, _NGROUPS, n)
    cmg = cm.reshape(b, s, _NGROUPS, n)

    # The chunk is a pure implementation tile: a site-tuned binding knows a
    # better value than the model config's static ssm_chunk, so defer to it
    # — resolved for THIS call's geometry (prefill and decode sequences tune
    # to different chunks) — falling back to the largest divisor when it
    # doesn't divide this seq.
    tuned = getattr(binding, "tuned_config", lambda name, shapes=None: None)(
        "ssd_scan", (xh, dt, a, bmg, cmg))
    chunk = tuned["chunk"] if tuned is not None and "chunk" in tuned else cfg.ssm_chunk
    chunk = min(chunk, s)
    if s % chunk:
        chunk = math.gcd(chunk, s)
    y, state = binding["ssd_scan"](xh, dt, a, bmg, cmg, chunk=chunk)
    y = y + params["d_skip"].astype(y.dtype)[None, None, :, None] * xh
    y = y.reshape(b, s, h * p)
    y = _rms(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype), params["norm_scale"])
    out = jnp.einsum("bse,ed->bsd", y, params["w_out"])
    if return_state:
        conv_tail = _conv_tail(x, params, cfg)
        return out, {"state": state, "conv": conv_tail}
    return out


def _conv_tail(x, params, cfg: ModelConfig):
    """Last (conv_k - 1) *pre-conv* inputs, for decode continuation."""
    xs = jnp.einsum("bsd,de->bse", x, params["w_x"])
    k = cfg.ssm_conv
    return xs[:, -(k - 1):, :].astype(xs.dtype)


def ssm_init_cache_shapes(cfg: ModelConfig, batch: int):
    h, p, n = cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state
    return {
        "state": ((batch, h, n, p), "float32"),
        "conv": ((batch, cfg.ssm_conv - 1, cfg.ssm_d_inner), cfg.dtype),
    }


def ssm_prefill_chunk(
    params,
    x: jnp.ndarray,        # (B, C, D) — chunk of prompt at positions pos..
    cache: dict[str, jnp.ndarray],
    pos: jnp.ndarray,      # () int32 — chunk's global start position
    n_valid: jnp.ndarray,  # () int32 — real tokens in this chunk (<= C)
    cfg: ModelConfig,
    binding,
):
    """C-token state advance for chunked prefill.

    The SSD recurrence is *linear* in the state, so continuing from the
    cached state needs no special kernel: run the chunk's scan from a
    zero state via the bound op, then add the initial state's closed-form
    contribution —

        y_t      += C_t . (exp(cumsum(dt*A)_t) * state0)
        state_out = scan_final + exp(cumsum(dt*A)_C) * state0

    Padding (n_valid < C, the prompt's final partial chunk) is absorbed
    by clamping dt to 0 at padded steps: decay exp(0*A) = 1 and input
    contribution dt*B*x = 0, so the state is bit-exactly unchanged there.
    The conv window is reconstructed from [cached tail | chunk inputs]
    and the new tail sliced at n_valid, so partial chunks hand the next
    chunk the same window a contiguous prefill would have.  At pos == 0
    the cached state/tail are slot leftovers from the previous request
    and are zeroed instead of consumed.
    """
    b, c, _ = x.shape
    h, p, n = cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state
    k = cfg.ssm_conv
    z, xs, bm, cm, dt = _projections(params, x)

    fresh = pos > 0
    tail = jnp.where(fresh, cache["conv"].astype(xs.dtype), 0)
    state0 = jnp.where(fresh, cache["state"].astype(jnp.float32), 0)

    # causal conv over [tail | chunk]: position t of the chunk sees ext
    # window [t, t+k) — identical to a whole-sequence conv at pos+t
    ext = jnp.concatenate([tail, xs], axis=1)          # (B, k-1+C, Din)
    y = jnp.zeros_like(xs, dtype=jnp.float32)
    for i in range(k):
        y = y + ext[:, i : i + c, :].astype(jnp.float32) * params["conv_w"][i].astype(jnp.float32)
    xc = jax.nn.silu(y + params["conv_b"].astype(jnp.float32)).astype(x.dtype)

    xh = xc.reshape(b, c, h, p)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"].astype(jnp.float32))
    dt = dt * (jnp.arange(c)[None, :, None] < n_valid)
    a = -jnp.exp(params["a_log"].astype(jnp.float32))
    bmg = bm.reshape(b, c, _NGROUPS, n)
    cmg = cm.reshape(b, c, _NGROUPS, n)

    tuned = getattr(binding, "tuned_config", lambda name, shapes=None: None)(
        "ssd_scan", (xh, dt, a, bmg, cmg))
    chunk = tuned["chunk"] if tuned is not None and "chunk" in tuned else cfg.ssm_chunk
    chunk = min(chunk, c)
    if c % chunk:
        chunk = math.gcd(chunk, c)
    y, state = binding["ssd_scan"](xh, dt, a, bmg, cmg, chunk=chunk)

    decay = jnp.exp(jnp.cumsum(dt * a[None, None, :], axis=1))   # (B, C, H)
    y = y + jnp.einsum("btn,bth,bhnp->bthp", cmg[:, :, 0], decay, state0).astype(y.dtype)
    state = state + decay[:, -1][..., None, None] * state0

    y = y + params["d_skip"].astype(y.dtype)[None, None, :, None] * xh
    y = y.reshape(b, c, h * p)
    y = _rms(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype), params["norm_scale"])
    out = jnp.einsum("bse,ed->bsd", y, params["w_out"])
    new_tail = jax.lax.dynamic_slice_in_dim(ext, n_valid, k - 1, axis=1)
    return out, {"state": state, "conv": new_tail.astype(cache["conv"].dtype)}


def ssm_decode(
    params,
    x: jnp.ndarray,        # (B, 1, D)
    cache: dict[str, jnp.ndarray],
    cfg: ModelConfig,
):
    """One-token state update (pure jnp: trivially memory-bound, no swap)."""
    b = x.shape[0]
    h, p, n = cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state
    x1 = x[:, 0, :]
    z = x1 @ params["w_z"]
    xs_new = x1 @ params["w_x"]                       # (B, Din) pre-conv
    bm = (x1 @ params["w_b"]).reshape(b, _NGROUPS, n)
    cm = (x1 @ params["w_c"]).reshape(b, _NGROUPS, n)
    dt = jax.nn.softplus(
        (x1 @ params["w_dt"]).astype(jnp.float32) + params["dt_bias"].astype(jnp.float32)
    )

    # conv over [tail, new]
    window = jnp.concatenate([cache["conv"], xs_new[:, None, :]], axis=1)  # (B, K, Din)
    w = params["conv_w"]
    xc = (window.astype(jnp.float32) * w[None].astype(jnp.float32)).sum(axis=1)
    xc = jax.nn.silu(xc + params["conv_b"].astype(jnp.float32)).astype(x.dtype)

    a = -jnp.exp(params["a_log"].astype(jnp.float32))
    y, new_state = ssd_decode_step_ref(
        xc.reshape(b, h, p), dt, a, bm, cm, cache["state"].astype(jnp.float32)
    )
    y = y + params["d_skip"].astype(y.dtype)[None, :, None] * xc.reshape(b, h, p)
    y = y.reshape(b, h * p)
    y = _rms(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype), params["norm_scale"])
    out = (y @ params["w_out"])[:, None, :]
    new_cache = {"state": new_state, "conv": window[:, 1:, :]}
    return out, new_cache
