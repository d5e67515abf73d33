"""Mixture-of-Experts layer: dropless top-k routing.

Two execution paths, selected by deployment (the op-substitution story):

  * **oracle** (`dense`): every expert computes every token, combined by
    gate weights — exact, O(E/topk) wasteful, used for tiny smoke configs
    and as the numerical oracle.
  * **gmm** (default): tokens are sorted by expert and run through the
    grouped matmul op (`binding["moe_gmm"]`: ragged_dot reference or the
    Pallas kernel).  Under a mesh this runs inside shard_map with
    *expert tensor parallelism*: the expert hidden dim F is sharded over
    the model axis (every routed pair computed exactly once, split over
    the axis; balanced regardless of routing skew), expert stacks are
    stored FSDP-sharded over the data axis and gathered per layer.  The
    only collective is one psum over the model axis — the same pattern as
    the dense TP MLP, so MoE and dense layers share a collective schedule.

Routing happens once, outside shard_map (cheap; lets the load-balancing
aux loss reuse it).  Shared experts (moonshot) are a dense MLP added to
the routed output.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers import ParallelCtx, mlp_apply, mlp_schema
from repro.models.schema import LeafSpec

__all__ = ["moe_schema", "moe_apply"]


def moe_schema(cfg: ModelConfig) -> dict:
    d, e, f = cfg.d_model, cfg.num_experts, cfg.expert_d_ff
    leaves = {
        "router": LeafSpec((d, e), ("embed", None), init="scaled", dtype="float32"),
        "w_in": LeafSpec((e, d, f), ("experts", "embed", "ff"), init="scaled"),
        "w_gate": LeafSpec((e, d, f), ("experts", "embed", "ff"), init="scaled"),
        "w_out": LeafSpec((e, f, d), ("experts", "ff", "embed"), init="scaled"),
    }
    if cfg.n_shared_experts:
        leaves["shared"] = mlp_schema(cfg, d_ff=cfg.n_shared_experts * cfg.expert_d_ff)
    return leaves


def _route(x_flat: jnp.ndarray, router_w: jnp.ndarray, top_k: int):
    """(probs (T,E) f32, top_p (T,k) f32 renormalized, top_i (T,k) i32)."""
    logits = x_flat.astype(jnp.float32) @ router_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, top_k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    return probs, top_p, top_i.astype(jnp.int32)


def _load_balance_aux(probs, top_i, num_experts: int) -> jnp.ndarray:
    """Switch-style aux loss: E * sum_e(frac_routed_e * mean_prob_e)."""
    t = probs.shape[0]
    counts = jnp.zeros((num_experts,), jnp.float32).at[top_i.reshape(-1)].add(1.0)
    frac = counts / jnp.maximum(counts.sum(), 1.0)
    mean_p = probs.mean(axis=0)
    return num_experts * jnp.sum(frac * mean_p)


def _gmm_pairs(x_flat, top_p, top_i, w_in, w_gate, w_out, cfg: ModelConfig, binding):
    """Sorted grouped-matmul MoE with given routing.  Weights may be the
    ff-sharded local slice (inside shard_map) or the full stack."""
    t, d = x_flat.shape
    e, k = cfg.num_experts, cfg.top_k

    pair_expert = top_i.reshape(-1)                       # (T*k,)
    order = jnp.argsort(pair_expert)
    inv_order = jnp.argsort(order)
    token_of_pair = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    x_sorted = x_flat[token_of_pair[order]]
    group_sizes = jnp.bincount(pair_expert, length=e).astype(jnp.int32)

    # fused in+gate: one grouped matmul over [w_in | w_gate] halves the
    # pack/scatter rounds through HBM (the reference path's dominant
    # traffic; the Pallas kernel fuses these on-chip anyway)
    f = w_in.shape[-1]
    h2 = binding["moe_gmm"](
        x_sorted, jnp.concatenate([w_in, w_gate], axis=-1), group_sizes
    )
    h = jax.nn.silu(h2[:, f:]) * h2[:, :f]
    y_pairs = binding["moe_gmm"](h, w_out, group_sizes)   # (T*k, D), partial over ff shards

    y_pairs = y_pairs[inv_order] * top_p.reshape(-1, 1).astype(y_pairs.dtype)
    return jnp.zeros((t, d), y_pairs.dtype).at[token_of_pair].add(y_pairs)


def _dense_oracle(x_flat, top_p, top_i, params, cfg: ModelConfig):
    combine = jnp.zeros((x_flat.shape[0], cfg.num_experts), jnp.float32)
    combine = combine.at[jnp.arange(x_flat.shape[0])[:, None], top_i].add(top_p)
    h_in = jnp.einsum("td,edf->tef", x_flat, params["w_in"])
    h_gate = jnp.einsum("td,edf->tef", x_flat, params["w_gate"])
    h = jax.nn.silu(h_gate) * h_in
    y_e = jnp.einsum("tef,efd->ted", h, params["w_out"])
    return jnp.einsum("ted,te->td", y_e, combine.astype(y_e.dtype))


def _gmm_chunked(x_flat, top_p, top_i, w_in, w_gate, w_out, cfg, binding,
                 chunks: int, unroll: bool = False):
    """Token-chunked expert execution: lax.scan over token chunks keeps the
    peak gather/pack buffers at 1/chunks of the layer's tokens (routing is
    per-token, so chunking is exact up to per-chunk capacity).  `unroll`
    is the dry-run cost-measurement mode (while bodies count once)."""
    t, d = x_flat.shape
    if chunks <= 1 or t % chunks:
        return _gmm_pairs(x_flat, top_p, top_i, w_in, w_gate, w_out, cfg, binding)
    k = cfg.top_k
    xs = x_flat.reshape(chunks, t // chunks, d)
    tps = top_p.reshape(chunks, t // chunks, k)
    tis = top_i.reshape(chunks, t // chunks, k)

    def body(_, inp):
        xi, tpi, tii = inp
        return None, _gmm_pairs(xi, tpi, tii, w_in, w_gate, w_out, cfg, binding)

    _, ys = jax.lax.scan(body, None, (xs, tps, tis),
                         unroll=chunks if unroll else 1)
    return ys.reshape(t, -1)


def moe_apply(
    params,
    x: jnp.ndarray,                 # (B, S, D)
    cfg: ModelConfig,
    pctx: ParallelCtx,
    binding,
    *,
    oracle: bool = False,
    with_aux: bool = False,
    token_chunks: int = 1,
    unroll: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    b, s, d = x.shape
    x_flat = x.reshape(b * s, d)
    probs, top_p, top_i = _route(x_flat, params["router"], cfg.top_k)
    aux = (
        _load_balance_aux(probs, top_i, cfg.num_experts)
        if with_aux
        else jnp.zeros((), jnp.float32)
    )

    if oracle:
        y = _dense_oracle(x_flat, top_p, top_i, params, cfg)
    elif not (pctx.active and pctx.model_axis):
        y = _gmm_chunked(
            x_flat, top_p, top_i,
            params["w_in"], params["w_gate"], params["w_out"], cfg, binding,
            token_chunks, unroll,
        )
    else:
        mesh = pctx.mesh
        axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        batch_axes = tuple(a for a in pctx.batch_axes if a in axis_sizes)
        dp = 1
        for a in batch_axes:
            dp *= axis_sizes[a]
        shard_tokens = dp > 1 and (b * s) % dp == 0
        token_spec = P(batch_axes if shard_tokens else None, None)
        tk_spec = P(batch_axes if shard_tokens else None, None)
        m = pctx.model_axis
        w3 = P(None, None, m)          # (E, D, F): ff sharded over model
        w_out_spec = P(None, m, None)  # (E, F, D)

        def local(xl, tp, ti, w_in, w_gate, w_out):
            y = _gmm_chunked(xl, tp, ti, w_in, w_gate, w_out, cfg, binding,
                             token_chunks, unroll)
            return jax.lax.psum(y, m)

        y = jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(token_spec, tk_spec, tk_spec, w3, w3, w_out_spec),
            out_specs=token_spec,
            check_vma=False,
        )(x_flat, top_p, top_i, params["w_in"], params["w_gate"], params["w_out"])

    if cfg.n_shared_experts:
        y = y + mlp_apply(params["shared"], x, cfg).reshape(b * s, d).astype(y.dtype)
    return y.reshape(b, s, d).astype(x.dtype), aux
