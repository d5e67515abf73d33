"""Model assembly: one composable decoder covers all ten architectures.

The decoder stack is a `lax.scan` over *pattern blocks*: the layer pattern
repeats with period p = lcm(attn_every, moe_every) (p=1 for homogeneous
stacks, p=8 for Jamba's [attn, mamba x7] interleave with MoE every 2nd
layer).  Each position j in the pattern has its own parameter group,
stacked over num_layers/p — so a qwen2-72b traces ONE layer body, not 80.

Modes:
  train   — full-sequence forward, cross-entropy loss (labels shifted by
            the data pipeline), remat per cfg.remat.
  prefill — full-sequence forward, emits the KV/SSM caches + last logits.
  decode  — one token against the caches (the decode_32k / long_500k cells).

Modality stubs per assignment: vlm consumes precomputed patch embeddings
(prepended to token embeddings), audio consumes precomputed frame
embeddings through a bidirectional encoder (whisper enc-dec).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import layers as L
from repro.models.moe import moe_apply, moe_schema
from repro.models.schema import LeafSpec, abstract_params, init_params, map_leaves
from repro.models.ssm import (
    ssm_apply,
    ssm_decode,
    ssm_init_cache_shapes,
    ssm_prefill_chunk,
    ssm_schema,
)

__all__ = ["Model", "build_model"]

Tree = dict[str, Any]


def _stack(tree: Tree, n: int) -> Tree:
    return map_leaves(
        lambda _, s: dataclasses.replace(s, shape=(n,) + s.shape, axes=("layers",) + s.axes),
        tree,
    )


# Static KV-cache calibration: attention k/v projections from scaled-init
# weights land well inside |x| < 8 after rotary, so the quantized cache
# uses one conservative amax for every slot (scale = amax / format-top).
# A static scale is what lets the (B,) scale vector live as a cache leaf
# and ride the kernels' SMEM scale-meta rows unchanged across steps.
KV_CALIBRATION_AMAX = 8.0


class Model:
    def __init__(
        self,
        cfg: ModelConfig,
        binding=None,
        pctx: L.ParallelCtx | None = None,
        *,
        moe_oracle: bool = False,
        scan_unroll: bool = False,
        head_pad_multiple: int | None = None,
        moe_token_chunks: int = 1,
        loss_seq_chunks: int = 1,
        kv_quantize: str | None = None,
    ):
        if binding is None:
            from repro.kernels.ops import default_binding

            binding = default_binding()
        self.cfg = cfg
        self.binding = binding
        self.pctx = pctx or L.ParallelCtx()
        self.moe_oracle = moe_oracle
        self.kv_quantize = kv_quantize
        self.kv_storage_dtype = None
        self.kv_scale_init = None
        if kv_quantize is not None:
            from repro.kernels.quant import (
                FORMATS, FP8_MAX, INT8_MAX, storage_dtype)

            if kv_quantize not in FORMATS:
                raise ValueError(
                    f"kv_quantize must be one of {FORMATS}, got {kv_quantize!r}")
            if cfg.is_enc_dec or cfg.modality == "vision":
                raise NotImplementedError(
                    "quantized KV cache supports text decoders only")
            self.kv_storage_dtype = str(jnp.dtype(storage_dtype(kv_quantize)))
            top = INT8_MAX if kv_quantize == "int8" else FP8_MAX
            self.kv_scale_init = KV_CALIBRATION_AMAX / top
        # dry-run sets scan_unroll: XLA cost_analysis does not multiply
        # while-loop bodies by trip count, so the roofline pass unrolls.
        self.scan_unroll = scan_unroll
        # Megatron-style vocab padding: embedding/head tables are padded to
        # a multiple of 128 so the vocab dim shards evenly on any assigned
        # mesh axis; padded logit columns are masked to -inf.  The model's
        # *interface* vocab (token ids, labels) is the published size.
        self.padded_vocab = -(-cfg.vocab_size // 128) * 128
        # Group-aligned head padding: when num_heads doesn't divide the TP
        # degree, XLA falls back to head_dim sharding and every score
        # einsum contracts the sharded dim -> multi-GB all-reduces per
        # attention (measured: 10.7 GB fp32 ARs on qwen2.5's 40 heads @
        # TP16).  We pad the GQA *group* width g -> g' (smallest g' >= g
        # with KV*g' % tp == 0), keeping the q-head -> kv-head mapping
        # h // g' exact; padded slots are zero-init and output-masked, so
        # the padded model is numerically identical to the unpadded one.
        tp = head_pad_multiple
        if tp is None and self.pctx.active and self.pctx.model_axis:
            tp = dict(zip(self.pctx.mesh.axis_names,
                          self.pctx.mesh.devices.shape))[self.pctx.model_axis]
        tp = tp or 1
        self.q_group = (cfg.num_heads // cfg.num_kv_heads) if cfg.num_kv_heads else 0
        gp = self.q_group
        if cfg.num_heads and cfg.num_heads % tp:
            while gp * cfg.num_kv_heads % tp:
                gp += 1
        self.q_group_padded = gp
        self.padded_heads = gp * cfg.num_kv_heads if cfg.num_kv_heads else 0
        self.moe_token_chunks = moe_token_chunks
        self.loss_seq_chunks = loss_seq_chunks
        self.use_rope = cfg.family != "audio"
        p = 1
        if cfg.family == "hybrid":
            p = cfg.attn_every
        if cfg.num_experts and cfg.moe_every > 1:
            import math

            p = math.lcm(p, cfg.moe_every)
        assert cfg.num_layers % p == 0, (cfg.num_layers, p)
        self.period = p
        self.num_blocks = cfg.num_layers // p

    # ------------------------------------------------------------------ #
    # schema
    # ------------------------------------------------------------------ #
    def _layer_schema(self, j: int) -> Tree:
        cfg = self.cfg
        sch: Tree = {"pre_norm": L.norm_schema(cfg)}
        if cfg.is_attn_layer(j):
            sch["attn"] = L.attention_schema(cfg, n_heads=self.padded_heads)
        else:
            sch["ssm"] = ssm_schema(cfg)
        if cfg.is_enc_dec:
            sch["cross_norm"] = L.norm_schema(cfg)
            sch["cross_attn"] = L.attention_schema(cfg, n_heads=self.padded_heads)
        if cfg.d_ff or cfg.num_experts:
            sch["post_norm"] = L.norm_schema(cfg)
            if cfg.is_moe_layer(j):
                sch["moe"] = moe_schema(cfg)
            elif cfg.d_ff:
                sch["mlp"] = L.mlp_schema(cfg)
        return sch

    def schema(self) -> Tree:
        cfg = self.cfg
        sch: Tree = {
            "embed": {
                "tok": LeafSpec(
                    (self.padded_vocab, cfg.d_model), ("vocab", "embed"), scale=0.01
                )
            },
            "decoder": {
                f"p{j}": _stack(self._layer_schema(j), self.num_blocks)
                for j in range(self.period)
            },
            "final_norm": L.norm_schema(cfg),
        }
        if not cfg.tie_embeddings:
            sch["lm_head"] = {
                "w": LeafSpec((cfg.d_model, self.padded_vocab), ("embed", "vocab"),
                              init="scaled")
            }
        if cfg.is_enc_dec:
            enc_layer = {
                "pre_norm": L.norm_schema(cfg),
                "attn": L.attention_schema(cfg, n_heads=self.padded_heads),
                "post_norm": L.norm_schema(cfg),
                "mlp": L.mlp_schema(cfg),
            }
            sch["encoder"] = {
                "layers": _stack(enc_layer, cfg.encoder_layers),
                "final_norm": L.norm_schema(cfg),
            }
        return sch

    def init(self, key: jax.Array) -> Tree:
        return init_params(self.schema(), key, self.cfg.dtype)

    def abstract_params(self) -> Tree:
        return abstract_params(self.schema(), self.cfg.dtype)

    # ------------------------------------------------------------------ #
    # caches
    # ------------------------------------------------------------------ #
    def cache_shapes(self, batch: int, max_len: int) -> Tree:
        """Per-pattern-position cache entry shapes, stacked over blocks."""
        cfg = self.cfg
        nb = self.num_blocks
        out: Tree = {}
        for j in range(self.period):
            entry: Tree = {}
            if cfg.is_attn_layer(j):
                kv_shape = (nb, batch, max_len, cfg.num_kv_heads, cfg.head_dim)
                kv_dt = self.kv_storage_dtype or cfg.dtype
                entry["k"] = (kv_shape, kv_dt)
                entry["v"] = (kv_shape, kv_dt)
                if self.kv_quantize:
                    entry["k_scale"] = ((nb, batch), "float32")
                    entry["v_scale"] = ((nb, batch), "float32")
            else:
                ss = ssm_init_cache_shapes(cfg, batch)
                entry["state"] = ((nb,) + ss["state"][0], ss["state"][1])
                entry["conv"] = ((nb,) + ss["conv"][0], ss["conv"][1])
            if cfg.is_enc_dec:
                ckv = (nb, batch, max_len, cfg.num_kv_heads, cfg.head_dim)
                entry["ck"] = (ckv, cfg.dtype)
                entry["cv"] = (ckv, cfg.dtype)
            out[f"p{j}"] = entry
        return out

    @staticmethod
    def _to_abstract(t):
        if isinstance(t, tuple) and len(t) == 2 and isinstance(t[0], tuple):
            return jax.ShapeDtypeStruct(t[0], jnp.dtype(t[1]))
        return {k: Model._to_abstract(v) for k, v in t.items()}

    def abstract_cache(self, batch: int, max_len: int) -> Tree:
        return self._to_abstract(self.cache_shapes(batch, max_len))

    def _init_cache_tree(self, abstract: Tree) -> Tree:
        """Zeros everywhere except the quantized cache's scale leaves,
        which start at the static calibration (a zero scale would blow up
        the first quantized write)."""
        return {
            pj: {
                name: (jnp.full(s.shape, self.kv_scale_init, s.dtype)
                       if name in ("k_scale", "v_scale")
                       else jnp.zeros(s.shape, s.dtype))
                for name, s in entry.items()
            }
            for pj, entry in abstract.items()
        }

    def init_cache(self, batch: int, max_len: int) -> Tree:
        return self._init_cache_tree(self.abstract_cache(batch, max_len))

    def paged_cache_shapes(self, num_pages: int, page_size: int, slots: int) -> Tree:
        """Paged-cache entry shapes: attention k/v become page *pools*
        (nb, num_pages, page_size, KV, Dh) shared by all slots and
        addressed through per-slot block tables; recurrent (SSM) state is
        O(1) per slot and stays slot-indexed exactly as in `cache_shapes`.
        """
        cfg = self.cfg
        if cfg.is_enc_dec or cfg.modality == "vision":
            raise NotImplementedError("paged cache supports text decoders only")
        nb = self.num_blocks
        out: Tree = {}
        for j in range(self.period):
            entry: Tree = {}
            if cfg.is_attn_layer(j):
                kv_shape = (nb, num_pages, page_size, cfg.num_kv_heads, cfg.head_dim)
                kv_dt = self.kv_storage_dtype or cfg.dtype
                entry["k"] = (kv_shape, kv_dt)
                entry["v"] = (kv_shape, kv_dt)
                if self.kv_quantize:
                    entry["k_scale"] = ((nb, slots), "float32")
                    entry["v_scale"] = ((nb, slots), "float32")
            else:
                ss = ssm_init_cache_shapes(cfg, slots)
                entry["state"] = ((nb,) + ss["state"][0], ss["state"][1])
                entry["conv"] = ((nb,) + ss["conv"][0], ss["conv"][1])
            out[f"p{j}"] = entry
        return out

    def abstract_paged_cache(self, num_pages: int, page_size: int, slots: int) -> Tree:
        return self._to_abstract(self.paged_cache_shapes(num_pages, page_size, slots))

    def init_paged_cache(self, num_pages: int, page_size: int, slots: int) -> Tree:
        return self._init_cache_tree(
            self.abstract_paged_cache(num_pages, page_size, slots))

    def export_paged_slot(self, cache: Tree, pages, slot: int) -> dict:
        """One slot's state out of a paged cache, as host numpy arrays.

        ``pages`` is the slot's leased page ids in block-table order
        (only the written prefix — the KV-handoff sender passes
        ``block_tables[slot][:pages_used]``).  Attention k/v pools yield
        ``(nb, len(pages), page_size, KV, Dh)`` page stacks; SSM leaves
        yield the slot's row.  Keys are ``"p{j}/{leaf}"`` — the flat
        naming the `KVHandoff` artifact serializes.
        """
        import numpy as np

        pages = np.asarray(pages, dtype=np.int32)
        out: dict = {}
        for pj, entry in cache.items():
            for name, buf in entry.items():
                # gather on the device: only the slot's pages cross to host
                out[f"{pj}/{name}"] = np.asarray(
                    buf[:, pages] if name in ("k", "v") else buf[:, slot])
        return out

    def import_paged_slot(self, cache: Tree, arrays: Mapping[str, Any],
                          pages, slot: int) -> Tree:
        """Scatter an exported slot into this cache's own pages.

        The receiver leased ``pages`` (same count, any ids) from its own
        allocator; page numbering does not survive the trip.  Returns the
        updated cache tree; shapes are validated leaf-by-leaf so a
        mismatched artifact fails before any buffer is written.
        """
        import numpy as np

        pages_ix = jnp.asarray(np.asarray(pages, dtype=np.int32))
        new: Tree = {}
        for pj, entry in cache.items():
            upd_entry: Tree = {}
            for name, buf in entry.items():
                key = f"{pj}/{name}"
                if key not in arrays:
                    raise ValueError(f"paged-slot import: missing leaf {key}")
                src = jnp.asarray(arrays[key], dtype=buf.dtype)
                if name in ("k", "v"):
                    want = (buf.shape[0], len(pages)) + buf.shape[2:]
                    if src.shape != want:
                        raise ValueError(
                            f"paged-slot import: {key} is {src.shape}, "
                            f"target pages need {want}")
                    upd_entry[name] = buf.at[:, pages_ix].set(src)
                else:
                    want = (buf.shape[0],) + buf.shape[2:]
                    if src.shape != want:
                        raise ValueError(
                            f"paged-slot import: {key} is {src.shape}, "
                            f"slot row needs {want}")
                    upd_entry[name] = buf.at[:, slot].set(src)
            new[pj] = upd_entry
        return new

    # ------------------------------------------------------------------ #
    # layer application
    # ------------------------------------------------------------------ #
    def _layer(self, j, lp, x, mode, lc, pos, enc_out, positions, aux,
               n_valid=None, active=None, block_tables=None, window=None):
        cfg, binding = self.cfg, self.binding
        new_cache: Tree = {}
        h = L.norm_apply(lp["pre_norm"], x, cfg, binding)
        rg = (self.q_group, self.q_group_padded)
        if cfg.is_attn_layer(j):
            if mode in ("decode", "chunk"):
                attn_cache = {"k": lc["k"], "v": lc["v"]}
                if "k_scale" in lc:
                    attn_cache["k_scale"] = lc["k_scale"]
                    attn_cache["v_scale"] = lc["v_scale"]
                apply = (L.attention_decode if mode == "decode"
                         else L.attention_chunk)
                y, kv = apply(
                    lp["attn"], h, attn_cache, pos, cfg, binding,
                    use_rope=self.use_rope, pctx=self.pctx, real_group=rg,
                    block_tables=block_tables, window=window,
                )
                new_cache.update(kv)
            else:
                y, kv = L.attention_apply(
                    lp["attn"], h, cfg, binding, positions=positions,
                    causal=True, use_rope=self.use_rope, pctx=self.pctx,
                    real_group=rg,
                )
                if mode == "prefill":
                    if self.kv_quantize:
                        sc = jnp.full((h.shape[0],), self.kv_scale_init,
                                      jnp.float32)
                        sd = jnp.dtype(self.kv_storage_dtype)
                        new_cache["k"] = L._quant_update(kv["k"], sc, sd)
                        new_cache["v"] = L._quant_update(kv["v"], sc, sd)
                        new_cache["k_scale"] = sc
                        new_cache["v_scale"] = sc
                    else:
                        new_cache["k"] = kv["k"].astype(jnp.dtype(cfg.dtype))
                        new_cache["v"] = kv["v"].astype(jnp.dtype(cfg.dtype))
        else:
            if mode == "decode":
                y, sc = ssm_decode(lp["ssm"], h, {"state": lc["state"], "conv": lc["conv"]}, cfg)
                if active is not None:
                    # inactive slots must not advance: unlike KV (whose
                    # parked write is harmless), the SSM recurrence would
                    # fold the dummy token into the state irreversibly
                    sc = {
                        "state": jnp.where(active[:, None, None, None],
                                           sc["state"], lc["state"]),
                        "conv": jnp.where(active[:, None, None],
                                          sc["conv"], lc["conv"]),
                    }
                new_cache.update(sc)
            elif mode == "chunk":
                y, sc = ssm_prefill_chunk(
                    lp["ssm"], h, {"state": lc["state"], "conv": lc["conv"]},
                    pos, n_valid, cfg, binding,
                )
                new_cache.update(sc)
            elif mode == "prefill":
                y, sc = ssm_apply(lp["ssm"], h, cfg, binding, return_state=True)
                new_cache["state"] = sc["state"]
                new_cache["conv"] = sc["conv"]
            else:
                y = ssm_apply(lp["ssm"], h, cfg, binding)
        x = x + y

        if cfg.is_enc_dec:
            h = L.norm_apply(lp["cross_norm"], x, cfg, binding)
            if mode == "decode":
                y, _ = L.attention_decode(
                    lp["cross_attn"], h, {"k": lc["ck"], "v": lc["cv"]}, pos, cfg,
                    binding, use_rope=False, cross=True, pctx=self.pctx,
                    real_group=rg,
                )
                new_cache["ck"] = lc["ck"]
                new_cache["cv"] = lc["cv"]
            else:
                y, ckv = L.attention_apply(
                    lp["cross_attn"], h, cfg, binding, causal=False,
                    kv_source=enc_out, use_rope=False, pctx=self.pctx,
                    real_group=rg,
                )
                if mode == "prefill":
                    new_cache["ck"] = ckv["k"].astype(jnp.dtype(cfg.dtype))
                    new_cache["cv"] = ckv["v"].astype(jnp.dtype(cfg.dtype))
            x = x + y

        if cfg.d_ff or cfg.num_experts:
            if "moe" in lp or "mlp" in lp:
                h = L.norm_apply(lp["post_norm"], x, cfg, binding)
                if "moe" in lp:
                    y, layer_aux = moe_apply(
                        lp["moe"], h, cfg, self.pctx, binding,
                        oracle=self.moe_oracle, with_aux=(mode == "train"),
                        token_chunks=self.moe_token_chunks,
                        unroll=self.scan_unroll,
                    )
                    aux = aux + layer_aux
                else:
                    y = L.mlp_apply(lp["mlp"], h, cfg, binding)
                x = x + y
        x = self.pctx.constrain_residual(x)
        return x, (new_cache if mode in ("prefill", "decode", "chunk") else None), aux

    # ------------------------------------------------------------------ #
    # decoder stack
    # ------------------------------------------------------------------ #
    def _decoder(self, params, x, mode, cache=None, pos=None, enc_out=None,
                 positions=None, n_valid=None, active=None, block_tables=None,
                 window=None):
        cfg = self.cfg
        p = self.period
        unroll = self.num_blocks if self.scan_unroll else 1
        aux0 = jnp.zeros((), jnp.float32)
        if cfg.residual_fp32:
            x = x.astype(jnp.float32)

        if mode == "decode" and self.scan_unroll:
            # measurement mode: the xs->ys formulation — XLA cost analysis
            # charges dynamic_update_slice ~2x the FULL buffer (measured),
            # which would inflate the carry path's memory term ~30x; the
            # slab-wise ys traffic is the honest per-step cost.
            def dec_ys(carry, xs):
                x, aux = carry
                bp, bc = xs
                ncs: Tree = {}
                for j in range(p):
                    x, nc, aux = self._layer(
                        j, bp[f"p{j}"], x, mode, bc[f"p{j}"], pos, enc_out,
                        positions, aux
                    )
                    ncs[f"p{j}"] = nc
                return (x, aux), ncs

            (x, aux), new_cache = jax.lax.scan(
                dec_ys, (x, aux0), (params["decoder"], cache), unroll=unroll,
            )
            return x, new_cache, aux

        if mode in ("decode", "chunk"):
            # deployment mode: cache rides in the CARRY and is updated in
            # place with dynamic_update_slice — XLA keeps while-loop
            # carries aliased, so decode never materializes a second full
            # KV cache (the xs->ys formulation cannot alias across the
            # loop boundary; measured +5.4 GB temp on qwen2-72b decode_32k).
            # Chunked prefill reuses the same formulation: C tokens instead
            # of 1, same in-place cache discipline.
            def dec_block(carry, bp):
                x, aux, cache_st, i = carry
                new_cache = cache_st
                for j in range(p):
                    lc = jax.tree.map(
                        lambda c: jax.lax.dynamic_index_in_dim(
                            c, i, axis=0, keepdims=False
                        ),
                        new_cache[f"p{j}"],
                    )
                    x, nc, aux = self._layer(
                        j, bp[f"p{j}"], x, mode, lc, pos, enc_out, positions, aux,
                        n_valid=n_valid, active=active, block_tables=block_tables,
                        window=window,
                    )
                    new_cache = dict(new_cache)
                    new_cache[f"p{j}"] = jax.tree.map(
                        lambda buf, upd: jax.lax.dynamic_update_slice_in_dim(
                            buf, upd[None].astype(buf.dtype), i, axis=0
                        ),
                        new_cache[f"p{j}"],
                        nc,
                    )
                return (x, aux, new_cache, i + 1), None

            (x, aux, new_cache, _), _ = jax.lax.scan(
                dec_block, (x, aux0, cache, jnp.int32(0)), params["decoder"],
                unroll=unroll,
            )
            return x, new_cache, aux

        def block_fn(carry, xs):
            x, aux = carry
            bp = xs
            ncs: Tree = {}
            for j in range(p):
                x, nc, aux = self._layer(
                    j, bp[f"p{j}"], x, mode, None, pos, enc_out, positions, aux
                )
                if nc is not None:
                    ncs[f"p{j}"] = nc
            return (x, aux), (ncs if ncs else None)

        if mode == "train" and cfg.remat != "none":
            policy = (
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                if cfg.remat == "dots"
                else jax.checkpoint_policies.nothing_saveable
            )
            block_fn = jax.checkpoint(block_fn, policy=policy)

        (x, aux), new_cache = jax.lax.scan(
            block_fn, (x, aux0), params["decoder"], unroll=unroll,
        )
        return x, new_cache, aux

    def _encoder(self, params, frames):
        cfg, binding = self.cfg, self.binding
        x = frames + L.sinusoidal_positions(frames.shape[1], cfg.d_model).astype(frames.dtype)

        def enc_fn(x, lp):
            h = L.norm_apply(lp["pre_norm"], x, cfg, binding)
            y, _ = L.attention_apply(
                lp["attn"], h, cfg, binding, causal=False, use_rope=False,
                pctx=self.pctx, real_group=(self.q_group, self.q_group_padded),
            )
            x = x + y
            h = L.norm_apply(lp["post_norm"], x, cfg, binding)
            x = x + L.mlp_apply(lp["mlp"], h, cfg, binding)
            return x, None

        if cfg.remat != "none":
            enc_fn = jax.checkpoint(enc_fn)
        x, _ = jax.lax.scan(
            enc_fn, x, params["encoder"]["layers"],
            unroll=cfg.encoder_layers if self.scan_unroll else 1,
        )
        return L.norm_apply(params["encoder"]["final_norm"], x, cfg, binding)

    # ------------------------------------------------------------------ #
    # embeddings + logits
    # ------------------------------------------------------------------ #
    def _embed(self, params, tokens, offset: jnp.ndarray | int = 0):
        tok = L.dequant_param(params["embed"]["tok"], jnp.dtype(self.cfg.dtype))
        x = jnp.take(tok, tokens, axis=0)
        if self.cfg.family == "audio":
            x = x + L.sinusoidal_positions(
                tokens.shape[1], self.cfg.d_model, offset
            ).astype(x.dtype)
        return x

    def _logits(self, params, x):
        if self.cfg.tie_embeddings:
            # tied head reuses the (vocab, d) embedding; its axis-0 scales
            # do not match quant_matmul's per-output-channel layout after
            # the transpose, so the tied path always densifies.
            w = L.dequant_param(params["embed"]["tok"], x.dtype).T
        else:
            w = params["lm_head"]["w"]
        if isinstance(w, dict) and "quant_matmul" in self.binding:
            b, s, d = x.shape
            logits = self.binding["quant_matmul"](
                x.reshape(b * s, d), w["q"], w["scale"]
            ).reshape(b, s, -1).astype(jnp.float32)
        else:
            logits = jnp.einsum(
                "bsd,dv->bsv", x, L.dequant_param(w, x.dtype)
            ).astype(jnp.float32)
        if self.padded_vocab != self.cfg.vocab_size:
            mask = jnp.arange(self.padded_vocab) < self.cfg.vocab_size
            logits = jnp.where(mask, logits, -1e9)
        if self.pctx.active and self.pctx.model_axis:
            from jax.sharding import PartitionSpec as P

            logits = self.pctx.constrain(
                logits, P(self.pctx.batch_axes or None, None, self.pctx.model_axis)
            )
        return logits

    def _assemble_inputs(self, params, batch):
        """Token/modality fusion -> (x, enc_out, text_offset)."""
        cfg = self.cfg
        enc_out = None
        if cfg.is_enc_dec:
            enc_out = self._encoder(params, batch["frames"])
            x = self._embed(params, batch["tokens"])
            offset = 0
        elif cfg.modality == "vision":
            tok = self._embed(params, batch["tokens"])
            x = jnp.concatenate([batch["patch_embeds"].astype(tok.dtype), tok], axis=1)
            offset = batch["patch_embeds"].shape[1]
        else:
            x = self._embed(params, batch["tokens"])
            offset = 0
        return x, enc_out, offset

    # ------------------------------------------------------------------ #
    # public entry points
    # ------------------------------------------------------------------ #
    def loss_fn(self, params, batch):
        cfg = self.cfg
        x, enc_out, offset = self._assemble_inputs(params, batch)
        positions = jnp.arange(x.shape[1])
        x, _, aux = self._decoder(params, x, "train", enc_out=enc_out,
                                  positions=positions)
        x = L.norm_apply(params["final_norm"], x, cfg, self.binding)
        if offset:
            x = x[:, offset:, :]
        labels = batch["labels"]
        nll_sum = self._chunked_nll(params, x, labels)
        loss = nll_sum / (labels.shape[0] * labels.shape[1])
        if cfg.num_experts:
            loss = loss + 0.01 * aux / max(cfg.num_layers, 1)
        return loss, {"loss": loss, "aux": aux}

    def _chunked_nll(self, params, x, labels):
        """Cross-entropy with sequence-chunked logits.

        Full fp32 logits are (B, S, V) — for moonshot's 163k vocab that is
        ~8 GB of live softmax buffers per device.  Chunking the sequence
        recomputes each chunk's logits in the backward (jax.checkpoint),
        holding only (B, S/c, V) alive: the standard large-vocab loss."""
        b, s, _ = x.shape
        chunks = self.loss_seq_chunks
        if chunks <= 1 or s % chunks:
            logits = self._logits(params, x)
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
            return nll.sum()

        xs = x.reshape(b, chunks, s // chunks, -1).swapaxes(0, 1)
        ls = labels.reshape(b, chunks, s // chunks).swapaxes(0, 1)

        @jax.checkpoint
        def chunk_nll(acc, inp):
            xc, lc = inp
            logits = self._logits(params, xc)
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(logp, lc[..., None], axis=-1)[..., 0]
            return acc + nll.sum(), None

        total, _ = jax.lax.scan(
            chunk_nll, jnp.zeros((), jnp.float32), (xs, ls),
            unroll=chunks if self.scan_unroll else 1,
        )
        return total

    def prefill(self, params, batch):
        cfg = self.cfg
        x, enc_out, _ = self._assemble_inputs(params, batch)
        positions = jnp.arange(x.shape[1])
        x, cache, _ = self._decoder(params, x, "prefill", enc_out=enc_out,
                                    positions=positions)
        x = L.norm_apply(params["final_norm"], x, cfg, self.binding)
        logits = self._logits(params, x[:, -1:, :])[:, 0]
        return logits, cache

    def prefill_into(self, params, tokens, cache, slot, pos, n_valid=None,
                     block_row=None, window=None):
        """Chunked prefill: advance ONE slot of a batched cache by C tokens.

        The compiled unit of prompt ingestion — a fixed-shape step the
        scheduler calls ceil(prompt_len / C) times per request, instead of
        O(prompt_len) whole-batch decode ticks.  Compiles once per chunk
        width C; slot / pos / n_valid are traced, so every request reuses
        the same executable.

        Args:
          tokens: (1, C) int32 — the chunk, right-padded to C.
          cache: batched cache from `init_cache(batch, max_len)`; only the
            `slot` row is read or written.
          slot: () int32 — batch row to fill.
          pos: () int32 — global position of tokens[:, 0] (0 for the first
            chunk; the caller must guarantee pos + C <= max_len, or the
            in-bounds-clamped cache write would corrupt neighbor slots).
          n_valid: () int32 — real tokens in this chunk (defaults to C);
            < C only for the prompt's final partial chunk.  At pos == 0
            stale slot state (KV garbage, SSM state, conv tail) is
            neutralized inside the step — slot reuse needs no reset pass.

        Returns (logits (1, vocab) for token n_valid-1, updated cache).
        The logits seed the request's first generated token: sampling from
        them replaces the decode tick the old prefill-by-decode loop burned
        re-feeding the last prompt token.

        With `block_row` (this slot's (nblocks,) int32 block-table row)
        the cache is paged (`init_paged_cache`): the k/v pools are shared
        by all slots, so they are passed to the decoder whole and written
        back whole — only the per-slot recurrent (SSM) leaves are sliced
        and scattered at `slot` as in the contiguous path.

        With `window` (() int32, traced) each chunk query attends only its
        trailing `window` keys (sliding-window attention) — pages wholly
        behind the window may already have been released by the scheduler.
        """
        cfg = self.cfg
        if cfg.is_enc_dec or cfg.modality == "vision":
            raise NotImplementedError("chunked prefill supports text decoders only")
        if n_valid is None:
            n_valid = tokens.shape[1]
        n_valid = jnp.asarray(n_valid, jnp.int32)
        slot = jnp.asarray(slot, jnp.int32)
        pos = jnp.asarray(pos, jnp.int32)
        paged = block_row is not None
        if paged:
            row = {
                pj: {
                    name: (buf if name in ("k", "v")
                           else jax.lax.dynamic_slice_in_dim(buf, slot, 1, axis=1))
                    for name, buf in entry.items()
                }
                for pj, entry in cache.items()
            }
        else:
            row = jax.tree.map(
                lambda buf: jax.lax.dynamic_slice_in_dim(buf, slot, 1, axis=1),
                cache,
            )
        x = self._embed(params, tokens)
        x, new_row, _ = self._decoder(params, x, "chunk", cache=row, pos=pos,
                                      n_valid=n_valid, block_tables=block_row,
                                      window=window)
        x = L.norm_apply(params["final_norm"], x, cfg, self.binding)
        last = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=1)
        logits = self._logits(params, last)[:, 0]
        if paged:
            cache = {
                pj: {
                    name: (upd.astype(cache[pj][name].dtype)
                           if name in ("k", "v")
                           else jax.lax.dynamic_update_slice_in_dim(
                               cache[pj][name], upd.astype(cache[pj][name].dtype),
                               slot, axis=1))
                    for name, upd in entry.items()
                }
                for pj, entry in new_row.items()
            }
        else:
            cache = jax.tree.map(
                lambda buf, upd: jax.lax.dynamic_update_slice_in_dim(
                    buf, upd.astype(buf.dtype), slot, axis=1
                ),
                cache, new_row,
            )
        return logits, cache

    def decode(self, params, token, cache, pos, active=None, block_tables=None,
               window=None):
        """token: (B, 1) int32; pos: () or (B,) int32 — per-slot positions
        under continuous batching; active: optional (B,) bool — rows whose
        recurrent (SSM) state may advance.  Inactive rows keep their state;
        their KV write lands wherever the scheduler parks pos (by
        convention max_len-1, a slot admission never lets live data reach;
        paged: table row all zeros, the write lands in the park page).
        block_tables: optional (B, nblocks) int32 — the cache is paged.
        window: optional () or (B,) int32 — sliding-window decode: only
        the trailing `window` cache slots are attended, so out-of-window
        pages may already have been released to other slots.
        """
        cfg = self.cfg
        x = self._embed(params, token, offset=pos)
        x, new_cache, _ = self._decoder(params, x, "decode", cache=cache, pos=pos,
                                        active=active, block_tables=block_tables,
                                        window=window)
        x = L.norm_apply(params["final_norm"], x, cfg, self.binding)
        logits = self._logits(params, x)[:, 0]
        return logits, new_cache

    # ------------------------------------------------------------------ #
    # input specs (ShapeDtypeStruct stand-ins for the dry-run)
    # ------------------------------------------------------------------ #
    def input_specs(self, shape: ShapeConfig) -> Tree:
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        sd = jax.ShapeDtypeStruct
        dt = jnp.dtype(cfg.dtype)
        if shape.kind == "train":
            if cfg.is_enc_dec:
                return {"frames": sd((b, s, cfg.d_model), dt),
                        "tokens": sd((b, s), i32), "labels": sd((b, s), i32)}
            if cfg.modality == "vision":
                p = cfg.n_patches
                return {"patch_embeds": sd((b, p, cfg.d_model), dt),
                        "tokens": sd((b, s - p), i32), "labels": sd((b, s - p), i32)}
            return {"tokens": sd((b, s), i32), "labels": sd((b, s), i32)}
        if shape.kind == "prefill":
            if cfg.is_enc_dec:
                return {"frames": sd((b, s, cfg.d_model), dt), "tokens": sd((b, s), i32)}
            if cfg.modality == "vision":
                p = cfg.n_patches
                return {"patch_embeds": sd((b, p, cfg.d_model), dt),
                        "tokens": sd((b, s - p), i32)}
            return {"tokens": sd((b, s), i32)}
        # decode: one new token against a cache of seq_len
        return {
            "token": sd((b, 1), i32),
            "cache": self.abstract_cache(b, s),
            "pos": sd((), i32),
        }


def build_model(cfg: ModelConfig, binding=None, pctx=None, **kw) -> Model:
    return Model(cfg, binding=binding, pctx=pctx, **kw)
