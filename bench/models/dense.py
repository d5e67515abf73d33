"""Dense decoder (Granite 3.0 family): weights, plain reference, FLOPs.

Layer equations as the configuration file states them (``dims``): token
embedding; per layer x += Attn(RMSNorm(x)), x += SwiGLU(RMSNorm(x)), with
grouped-query attention, rotate-half RoPE and softmax scale
``attention_multiplier``; final RMSNorm; logits against the tied
embedding.  The reference computes in float32 at "highest" matmul
precision, one layer at a time, and imports nothing of the program:
it reads the weights the benchmark made, by their names in the served
parameter tree.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench import work

__all__ = ["dims", "check_program_config", "init_weights", "reference_logits",
           "prefill_flops", "decode_flops"]

HIGHEST = jax.lax.Precision.HIGHEST


def dims(spec: dict) -> dict:
    d = spec["hidden_size"]
    h = spec["num_attention_heads"]
    return {
        "layers": spec["num_hidden_layers"], "d_model": d, "heads": h,
        "kv_heads": spec["num_key_value_heads"], "head_dim": d // h,
        "d_ff": spec["intermediate_size"], "vocab": spec["vocab_size"],
        "attn_layers": spec["num_hidden_layers"], "ssm_layers": 0,
        "eps": spec["rms_norm_eps"], "rope_theta": spec["rope_theta"],
        "softmax_scale": spec["attention_multiplier"],
        "init_std": spec["initializer_range"],
    }


def check_program_config(spec: dict, cfg) -> None:
    """The program's config must run what the file states."""
    d = dims(spec)
    got = {"layers": cfg.num_layers, "d_model": cfg.d_model, "heads": cfg.num_heads,
           "kv_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim, "d_ff": cfg.d_ff,
           "vocab": cfg.vocab_size, "rope_theta": cfg.rope_theta}
    bad = {k: (v, d[k]) for k, v in got.items() if v != d[k]}
    if not cfg.tie_embeddings:
        bad["tie_embeddings"] = (False, True)
    if cfg.dtype != spec["dtype"]:
        bad["dtype"] = (cfg.dtype, spec["dtype"])
    if not math.isclose(d["softmax_scale"], d["head_dim"] ** -0.5):
        bad["attention_multiplier"] = (d["head_dim"] ** -0.5, d["softmax_scale"])
    if bad:
        raise ValueError(f"program config differs from {spec['name']}: "
                         f"{{key: (program, file)}} {bad}")


def _leaf(path: str, shape, dtype, key, std: float):
    name = path.rsplit("/", 1)[-1]
    if name == "scale":                         # RMSNorm gains start at 1
        return jnp.ones(shape, dtype)
    if name in ("tok", "wq", "wk", "wv", "wo", "w_in", "w_gate", "w_out"):
        stacked = path.startswith("decoder/")
        if stacked:                             # one layer at a time: no
            keys = jax.random.split(key, shape[0])   # fp32 temporary of the stack
            return jax.lax.map(
                lambda k: (jax.random.normal(k, shape[1:]) * std).astype(dtype), keys)
        return (jax.random.normal(key, shape) * std).astype(dtype)
    raise ValueError(f"dense weights: no rule for leaf {path!r}")


def init_weights(spec: dict, abstract, key):
    """Weights for the served tree `abstract` (traced under one jit):
    normal(0, initializer_range) for every matrix, as the published
    models are initialised, and unit norm gains."""
    std = dims(spec)["init_std"]
    return _map(abstract, key, functools.partial(_leaf, std=std))


def _map(tree, key, fn, prefix=""):
    import zlib

    out = {}
    for k, v in tree.items():
        path = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            out[k] = _map(v, key, fn, path)
        else:
            sub = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
            out[k] = fn(path, v.shape, v.dtype, sub)
    return out


# --------------------------------------------------------------------- #
# reference
# --------------------------------------------------------------------- #
def round_weights(w, dtype: str):
    """A weight as a lower precision would hold it: float8 e4m3 with one
    scale per tensor (the control), or a plain cast (bfloat16)."""
    w = w.astype(jnp.float32)
    if dtype == "float8_e4m3fn":
        s = jnp.maximum(jnp.max(jnp.abs(w)), 1e-30) / 448.0
        return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return w.astype(dtype).astype(jnp.float32)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    s, _, dh = x.shape
    half = dh // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("d", "control", "qblock"))
def _layer(stack, i, x, *, d, control, qblock):
    """Layer `i` of the stacked served weights over one padded sequence."""
    d = dict(d)
    lw = jax.tree.map(lambda a: a[i].astype(jnp.float32), stack)
    if control:
        lw = {k: ({n: (round_weights(w, control) if w.ndim >= 2 else w)
                   for n, w in v.items()}) for k, v in lw.items()}
    mm = functools.partial(jnp.einsum, precision=HIGHEST)
    s = x.shape[0]
    g = d["heads"] // d["kv_heads"]
    h = _rms(x, lw["pre_norm"]["scale"], d["eps"])
    a = lw["attn"]
    q = _rope(mm("sd,dhk->shk", h, a["wq"]), d["rope_theta"])
    k = _rope(mm("sd,dhk->shk", h, a["wk"]), d["rope_theta"])
    v = mm("sd,dhk->shk", h, a["wv"])
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    outs = []
    for q0 in range(0, s, qblock):              # query blocks bound the scores
        qb = q[q0:q0 + qblock]
        sc = mm("qhk,shk->hqs", qb, k) * d["softmax_scale"]
        qpos = q0 + jnp.arange(qb.shape[0])[:, None]
        sc = jnp.where(jnp.arange(s)[None, :] <= qpos, sc, -jnp.inf)
        outs.append(mm("hqs,shk->qhk", jax.nn.softmax(sc, -1), v))
    o = jnp.concatenate(outs, 0)
    x = x + mm("shk,hkd->sd", o, a["wo"])
    h = _rms(x, lw["post_norm"]["scale"], d["eps"])
    m = lw["mlp"]
    f = jax.nn.silu(mm("sd,df->sf", h, m["w_gate"])) * mm("sd,df->sf", h, m["w_in"])
    return x + mm("sf,fd->sd", f, m["w_out"])


@functools.partial(jax.jit, static_argnames=("d", "control"))
def _embed(tok, ids, *, d, control):
    d = dict(d)
    e = tok[: d["vocab"]].astype(jnp.float32)
    return (round_weights(e, control) if control else e)[ids]


@functools.partial(jax.jit, static_argnames=("d", "control", "rows"))
def _head(tok, final_scale, x, start, *, d, control, rows):
    d = dict(d)
    e = tok[: d["vocab"]].astype(jnp.float32)
    e = round_weights(e, control) if control else e
    x = jax.lax.dynamic_slice_in_dim(x, start, rows, axis=0)
    h = _rms(x, final_scale.astype(jnp.float32), d["eps"])
    return jnp.einsum("sd,vd->sv", h, e, precision=HIGHEST)


def reference_logits(spec: dict, weights, seqs, starts, bucket: int,
                     rows: int, control: str | None = None):
    """Logits (len(seq) - start, vocab) of each sequence at positions
    start.., computed layer by layer in float32.  Sequences are padded on
    the right to `bucket` tokens (causal, so padding changes nothing
    before it) and `rows` positions are read from each, which keeps one
    compiled program per cell; `rows` is at least every len(seq) - start
    and start + rows stays within `bucket`.  With `control` (a dtype
    name) the weight matrices and the embedding are first rounded to
    that precision."""
    import numpy as np

    d = tuple(sorted(dims(spec).items()))
    dd = dict(d)
    stack = weights["decoder"]["p0"]
    tok = weights["embed"]["tok"]
    out = []
    for seq, start in zip(seqs, starts):
        ids = np.zeros(bucket, np.int32)
        ids[: len(seq)] = seq
        x = _embed(tok, jnp.asarray(ids), d=d, control=control)
        for i in range(dd["layers"]):
            x = _layer(stack, jnp.int32(i), x, d=d, control=control, qblock=512)
        first = min(start, bucket - rows)
        lg = np.asarray(_head(tok, weights["final_norm"]["scale"], x, jnp.int32(first),
                              d=d, control=control, rows=rows))
        out.append(lg[start - first: start - first + len(seq) - start])
    return out


# --------------------------------------------------------------------- #
# model FLOPs
# --------------------------------------------------------------------- #
def _matmul_params(d: dict) -> int:
    dm, h, kv, dh, f = d["d_model"], d["heads"], d["kv_heads"], d["head_dim"], d["d_ff"]
    return dm * h * dh + 2 * dm * kv * dh + h * dh * dm + 3 * dm * f


def prefill_flops(spec: dict, n: int, pos: int) -> float:
    """One chunk of `n` valid tokens at `pos`, plus the logits of its
    last token, which is all the step returns."""
    d = dims(spec)
    att = work.attention_chunk(n, pos, heads=d["heads"], kv_heads=d["kv_heads"],
                               head_dim=d["head_dim"]).flops
    return d["layers"] * (2.0 * n * _matmul_params(d) + att) + 2.0 * d["d_model"] * d["vocab"]


def decode_flops(spec: dict, contexts) -> float:
    """One decode step over the active rows (`contexts`: keys per row)."""
    d = dims(spec)
    rows = len(contexts)
    att = work.attention_decode(contexts, heads=d["heads"], kv_heads=d["kv_heads"],
                                head_dim=d["head_dim"]).flops
    return (d["layers"] * (2.0 * rows * _matmul_params(d) + att)
            + 2.0 * rows * d["d_model"] * d["vocab"])
