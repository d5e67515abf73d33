"""Mamba-2 (SSD) decoder: weights, plain reference, FLOPs.

Layer equations as the configuration file states them: per layer
x += OutProj(RMSNorm(y * silu(z))) with z, x', B, C, dt = InProj(RMSNorm(x));
x' through a depthwise causal conv of width ``d_conv`` (with bias) and
SiLU; dt = softplus(dt + dt_bias); A = -exp(A_log); the recurrence
h_t = exp(dt_t A) h_{t-1} + dt_t x'_t B_t^T, y_t = C_t h_t + D x'_t per
head; the residual stream in float32; final RMSNorm; logits against the
tied embedding.  The reference runs that recurrence token by token in
float32 at "highest" matmul precision, one layer at a time, over the
weights the benchmark made, and imports nothing of the program.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench import work
from bench.models.dense import _map, _rms, round_weights

__all__ = ["dims", "check_program_config", "init_weights", "reference_logits",
           "prefill_flops", "decode_flops"]

HIGHEST = jax.lax.Precision.HIGHEST


def dims(spec: dict) -> dict:
    m = spec["mamba2_defaults"]
    d = spec["d_model"]
    din = m["expand"] * d
    mult = spec["pad_vocab_size_multiple"]
    return {
        "layers": spec["n_layer"], "d_model": d, "d_inner": din,
        "ssm_heads": din // m["headdim"], "ssm_head_dim": m["headdim"],
        "ssm_state": m["d_state"], "ssm_groups": m["ngroups"], "conv": m["d_conv"],
        "vocab": -(-spec["vocab_size"] // mult) * mult,
        "token_vocab": spec["vocab_size"],
        "attn_layers": 0, "ssm_layers": spec["n_layer"],
        "eps": spec["norm_epsilon"], "init_std": spec["initializer_range"],
    }


def check_program_config(spec: dict, cfg) -> None:
    d = dims(spec)
    got = {"layers": cfg.num_layers, "d_model": cfg.d_model, "d_inner": cfg.ssm_d_inner,
           "ssm_heads": cfg.ssm_heads, "ssm_head_dim": cfg.ssm_headdim,
           "ssm_state": cfg.ssm_state, "conv": cfg.ssm_conv, "vocab": cfg.vocab_size}
    bad = {k: (v, d[k]) for k, v in got.items() if v != d[k]}
    if not cfg.tie_embeddings:
        bad["tie_embeddings"] = (False, True)
    if cfg.residual_fp32 != spec["residual_in_fp32"]:
        bad["residual_in_fp32"] = (cfg.residual_fp32, spec["residual_in_fp32"])
    if cfg.dtype != spec["dtype"]:
        bad["dtype"] = (cfg.dtype, spec["dtype"])
    if d["ssm_groups"] != 1:
        bad["ngroups"] = (1, d["ssm_groups"])
    if bad:
        raise ValueError(f"program config differs from {spec['name']}: "
                         f"{{key: (program, file)}} {bad}")


def _uniform(key, shape, bound, dtype, stacked):
    def one(k):
        return jax.random.uniform(k, shape[1:] if stacked else shape,
                                  minval=-bound, maxval=bound).astype(dtype)
    if stacked:
        return jax.lax.map(one, jax.random.split(key, shape[0]))
    return one(key)


def _leaf(path, shape, dtype, key, *, std, n_layer, d_conv):
    """Mamba-2's published initialisation (mamba_ssm ``Mamba2`` and
    ``_init_weights``): linear layers at torch's default uniform bound
    1/sqrt(fan_in), out_proj further divided by sqrt(n_layer); conv
    weight and bias at the default bound 1/sqrt(d_conv); dt log-uniform
    in [1e-3, 1e-1] through dt_bias (the inverse softplus); A uniform in
    [1, 16] through A_log; D = 1; embedding normal(0, 0.02); norm gains 1."""
    name = path.rsplit("/", 1)[-1]
    stacked = path.startswith("decoder/")
    per = shape[1:] if stacked else shape
    if name in ("scale", "norm_scale", "d_skip"):
        return jnp.ones(shape, dtype)
    if name == "tok":
        return (jax.random.normal(key, shape) * std).astype(dtype)
    if name in ("w_z", "w_x", "w_b", "w_c", "w_dt"):
        return _uniform(key, shape, per[0] ** -0.5, dtype, stacked)
    if name == "w_out":
        return _uniform(key, shape, per[0] ** -0.5 / math.sqrt(n_layer), dtype, stacked)
    if name in ("conv_w", "conv_b"):            # a depthwise conv's fan-in is d_conv
        return _uniform(key, shape, d_conv ** -0.5, dtype, stacked)
    if name == "dt_bias":
        lo, hi = math.log(1e-3), math.log(1e-1)
        dt = jnp.exp(jax.random.uniform(key, shape, minval=lo, maxval=hi))
        dt = jnp.maximum(dt, 1e-4)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    if name == "a_log":
        return jnp.log(jax.random.uniform(key, shape, minval=1.0, maxval=16.0)).astype(dtype)
    raise ValueError(f"mamba2 weights: no rule for leaf {path!r}")


def init_weights(spec: dict, abstract, key):
    d = dims(spec)
    return _map(abstract, key, functools.partial(
        _leaf, std=d["init_std"], n_layer=d["layers"], d_conv=d["conv"]))


# --------------------------------------------------------------------- #
# reference
# --------------------------------------------------------------------- #
@functools.partial(jax.jit, static_argnames=("d", "control"))
def _layer(stack, i, x, *, d, control):
    """Layer `i` over a batch of padded sequences x (B, S, D), float32."""
    d = dict(d)
    lw = jax.tree.map(lambda a: a[i].astype(jnp.float32), stack)
    if control:
        lw["ssm"] = {n: (round_weights(w, control) if w.ndim >= 2 and n.startswith("w_")
                         else w) for n, w in lw["ssm"].items()}
    p = lw["ssm"]
    mm = functools.partial(jnp.einsum, precision=HIGHEST)
    b, s, _ = x.shape
    nh, hp, n = d["ssm_heads"], d["ssm_head_dim"], d["ssm_state"]
    h = _rms(x, lw["pre_norm"]["scale"], d["eps"])
    z = mm("bsd,de->bse", h, p["w_z"])
    xs = mm("bsd,de->bse", h, p["w_x"])
    bm = mm("bsd,dn->bsn", h, p["w_b"])
    cm = mm("bsd,dn->bsn", h, p["w_c"])
    dt = jax.nn.softplus(mm("bsd,dh->bsh", h, p["w_dt"]) + p["dt_bias"])
    k = d["conv"]
    pad = jnp.pad(xs, ((0, 0), (k - 1, 0), (0, 0)))
    xs = sum(pad[:, j:j + s] * p["conv_w"][j] for j in range(k)) + p["conv_b"]
    xs = jax.nn.silu(xs).reshape(b, s, nh, hp)
    a = -jnp.exp(p["a_log"])

    def step(state, inp):                       # state (B, H, N, P)
        x_t, b_t, c_t, dt_t = inp
        decay = jnp.exp(dt_t * a)[:, :, None, None]
        state = decay * state + (dt_t[:, :, None, None] * b_t[:, None, :, None]
                                 * x_t[:, :, None, :])
        y = mm("bn,bhnp->bhp", c_t, state)
        return state, y

    state0 = jnp.zeros((b, nh, n, hp), jnp.float32)
    seq = (xs.swapaxes(0, 1), bm.swapaxes(0, 1), cm.swapaxes(0, 1), dt.swapaxes(0, 1))
    _, y = jax.lax.scan(step, state0, seq)
    y = y.swapaxes(0, 1) + p["d_skip"][None, None, :, None] * xs
    y = y.reshape(b, s, nh * hp) * jax.nn.silu(z)
    y = _rms(y, p["norm_scale"], d["eps"])
    return x + mm("bse,ed->bsd", y, p["w_out"])


@functools.partial(jax.jit, static_argnames=("d", "control"))
def _embed(tok, ids, *, d, control):
    d = dict(d)
    e = tok[: d["vocab"]].astype(jnp.float32)
    return (round_weights(e, control) if control else e)[ids]


@functools.partial(jax.jit, static_argnames=("d", "control", "rows"))
def _head(tok, final_scale, x, starts, *, d, control, rows):
    d = dict(d)
    e = tok[: d["vocab"]].astype(jnp.float32)
    e = round_weights(e, control) if control else e
    x = jax.vmap(lambda xb, s0: jax.lax.dynamic_slice_in_dim(xb, s0, rows, 0))(x, starts)
    h = _rms(x, final_scale.astype(jnp.float32), d["eps"])
    return jnp.einsum("bsd,vd->bsv", h, e, precision=HIGHEST)


def reference_logits(spec: dict, weights, seqs, starts, bucket: int,
                     rows: int, control: str | None = None):
    """As `bench.models.dense.reference_logits`; the sequences go through
    each layer together, as one padded batch."""
    import numpy as np

    d = tuple(sorted(dims(spec).items()))
    ids = np.zeros((len(seqs), bucket), np.int32)
    first = np.zeros(len(seqs), np.int32)
    for r, (seq, start) in enumerate(zip(seqs, starts)):
        ids[r, : len(seq)] = seq
        first[r] = min(start, bucket - rows)
    stack = weights["decoder"]["p0"]
    tok = weights["embed"]["tok"]
    x = _embed(tok, jnp.asarray(ids), d=d, control=control)
    for i in range(dict(d)["layers"]):
        x = _layer(stack, jnp.int32(i), x, d=d, control=control)
    lg = np.asarray(_head(tok, weights["final_norm"]["scale"], x, jnp.asarray(first),
                          d=d, control=control, rows=rows))
    return [lg[r, s0 - f: s0 - f + len(seq) - s0]
            for r, (seq, s0, f) in enumerate(zip(seqs, starts, first))]


# --------------------------------------------------------------------- #
# model FLOPs
# --------------------------------------------------------------------- #
def _token_flops(d: dict) -> float:
    """One token through one layer: projections, conv, scan, gated norm."""
    dm, din, nh, n = d["d_model"], d["d_inner"], d["ssm_heads"], d["ssm_state"]
    proj = dm * (2 * din + 2 * d["ssm_groups"] * n + nh) + din * dm
    scan = work.ssd_scan(1, heads=nh, head_dim=d["ssm_head_dim"], state=n).flops
    return 2.0 * proj + 2.0 * d["conv"] * din + scan


def prefill_flops(spec: dict, n: int, pos: int) -> float:
    d = dims(spec)
    return d["layers"] * n * _token_flops(d) + 2.0 * d["d_model"] * d["vocab"]


def decode_flops(spec: dict, contexts) -> float:
    d = dims(spec)
    rows = len(contexts)
    return rows * (d["layers"] * _token_flops(d) + 2.0 * d["d_model"] * d["vocab"])
