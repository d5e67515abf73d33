"""Flash attention (causal, GQA) — Pallas TPU kernel.

TPU adaptation of the flash algorithm: the grid is (batch, q_blocks,
kv_blocks) with the kv dimension minor — TPU grids execute the minor
dimension sequentially on a core, so the running softmax state (m, l,
acc) of every head lives in VMEM scratch and is carried across kv steps
without HBM traffic.

Every block spans the operands' full trailing (heads, head_dim) pair —
q/out tiles are (block_q, H, Dh), k/v tiles (block_k, KV, Dh) — because
Mosaic only accepts blocks whose last two dimensions are (8, 128)-aligned
or whole, and a single head (1, Dh) is neither.  The kernel body loops
over the KV heads statically and, inside, over the query heads of each
GQA group, so each k/v tile is fetched from HBM once per q-block and read
by all of its query heads; the per-head math is unchanged.  Block shapes
default to (128, ·): MXU-aligned and small enough that the tiles plus
scratch fit VMEM at head_dim 128 and 32 query heads.

Causal blocks strictly above the diagonal are skipped with pl.when — for
long sequences this halves the executed grid.  Two scalar rows ride in
SMEM (prefetched, per batch element): `kv_len` masks unwritten cache
slots and `q_start` dynamically re-anchors the causal diagonal.  Between
them the same kernel serves all three serving geometries:

  * prefill  — kv_len = Sk, q_start = Sk - Sq (the static diagonal);
  * decode   — Sq == 1 against a partially filled cache, per-batch
    kv_len = pos + 1 (continuous batching: every slot at its own
    position in one call);
  * chunked prefill — Sq == C chunk queries starting at global position
    `q_start` against the cache: query i attends keys <= q_start + i,
    keys past kv_len masked.

**Paged KV cache** (``block_tables`` + ``page_size``): k/v may instead be
page *pools* of shape (P, page, KV, Dh) addressed through a per-batch
block table (B, nblocks) — logical position p of row b lives in page
``block_tables[b, p // page]`` at offset ``p % page``.  The table rides
in the same SMEM meta as kv_len/q_start (rows 2.., transposed to
(nblocks, B)) and the k/v BlockSpec index maps resolve the physical page
per grid step, so the DMA itself performs the gather — the kernel body
is unchanged, masking stays in logical coordinates.  block_k is clamped
to divide the page (gcd) so no tile ever straddles a page boundary.
Unallocated table entries must still hold a valid page index (the
serving engine points them at a reserved park page): their DMAs are
issued even when the kv_len mask discards every lane.

**Sliding window** (``window``): with a traced per-batch window width W
a third scalar row joins the SMEM meta — the *window start*
``ws = kv_len - Sq - W + 1`` — and query i attends only keys in
``[ws + i, ...]`` on top of the causal/kv_len masks.  k-blocks wholly
below the q-block's minimum window start are skipped with the same
pl.when heuristic that already skips unwritten cache suffixes, so long-KV
decode executes O(W) kv steps instead of O(kv_len).  The formula anchors
queries to the end of the written prefix, which is exactly where all
three serving geometries put them (decode: the one query sits at
kv_len-1; chunk: queries at kv_len-C .. kv_len-1; prefill: kv_len = Sk,
q_start = Sk - Sq).  W >= kv_len degenerates to the ordinary masks —
bit-identical output, every block still run.  Out-of-window pages may be
reused (parked) by the serving engine: their scores are masked to -inf
before the softmax, so stale contents are inert.

**Quantized KV cache** (``k_scale``/``v_scale``): k/v (contiguous or
paged pools) may be stored int8 or fp8 with per-batch float32
dequantization scales.  The scales are a (2, B) fp32 operand held
whole in SMEM (row 0 k, row 1 v), beside the int32 scalar-prefetch
meta.  Tiles are
dequantized in VMEM after the DMA: the cache streams from HBM at one
byte per element, the softmax math stays fp32
(docs/quantization.md pins the per-format error envelopes).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.tuning.config import BlockConfig, default_config

__all__ = ["flash_attention"]

_DEFAULTS = default_config("attention")   # single source of truth for fallbacks

_NEG_INF = -1e30


def _flash_kernel(
    meta_ref,       # SMEM (2[+1], B) int32: row 0 kv_len, row 1 q_start,
                    # then window start (windowed only), then block tables
    *refs,          # [scale_ref: SMEM (2, B) fp32 k/v scales, quantized
                    # only], q (1, bq, H, dh), k (1, bk, KV, dh),
                    # v (1, bk, KV, dh), out (1, bq, H, dh), then scratch:
                    # m (bq, H), l (bq, H), acc (H, bq, dh)
    causal: bool,
    scale: float,
    block_q: int,
    block_k: int,
    kv_blocks: int,
    q_offset: int,      # sk - sq: static diagonal for the skip heuristic
    dyn_offset: bool,   # True when q_start is a traced value (chunk prefill)
    windowed: bool,     # True when meta carries a window-start row
    quantized: bool,    # True when a k/v scale operand precedes q
    group: int,         # query heads per KV head
):
    if quantized:
        scale_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    bi = pl.program_id(0)
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    kvl = meta_ref[0, bi]
    qs = meta_ref[1, bi]
    ws = meta_ref[2, bi] if windowed else None
    if quantized:
        ksc = scale_ref[0, bi]
        vsc = scale_ref[1, bi]
    n_kv = k_ref.shape[2]

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    # Skip blocks that cannot contribute: past the written cache prefix,
    # (static diagonal only) strictly above the causal diagonal, or
    # (windowed) wholly before the q-block's earliest window start.
    run = (ik * block_k) < kvl
    if causal and not dyn_offset:
        run = run & ((ik * block_k) <= (iq * block_q + q_offset + block_q - 1))
    if windowed:
        # the q-block's first query (local row iq*bq) has the smallest
        # window start; a k-block whose last key is below it is dead for
        # every query in the tile — this traced skip is what turns a
        # long-KV decode into O(window) executed kv steps
        run = run & ((ik * block_k + block_k - 1) >= (iq * block_q + ws))

    @pl.when(run)
    def _body():
        mask = k_pos < kvl
        if causal:
            mask = mask & (k_pos <= q_pos + qs)
        if windowed:
            # sliding window: query q_pos attends keys >= its own window
            # start ws + q_pos (the mirror image of the causal bound)
            mask = mask & (k_pos >= q_pos + ws)
        # rows past kv_len may be out-of-bounds tile padding (garbage, NaN
        # in interpret mode); p is 0 there but 0 * NaN = NaN, so zero v too
        k_row = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_k, 1), 0)
        # static head loops: Mosaic indexes the second-minor (head) axis of
        # a tile only at static offsets
        for kvh in range(n_kv):
            k = k_ref[0, :, kvh, :].astype(jnp.float32)
            v = v_ref[0, :, kvh, :].astype(jnp.float32)
            if quantized:
                # dequantize the 1-byte cache tiles in VMEM: k/v stream from
                # HBM at a quarter of the fp32 bytes, math stays fp32
                k = k * ksc
                v = v * vsc
            v = jnp.where(k_row < kvl, v, 0.0)
            for h in range(kvh * group, (kvh + 1) * group):
                q = q_ref[0, :, h, :].astype(jnp.float32) * scale
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # (bq, bk)
                s = jnp.where(mask, s, _NEG_INF)
                m_prev = m_ref[:, h:h + 1]
                m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.exp(s - m_new)
                l_ref[:, h:h + 1] = (l_ref[:, h:h + 1] * alpha
                                     + p.sum(axis=-1, keepdims=True))
                acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                    p, v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                m_ref[:, h:h + 1] = m_new

    @pl.when(ik == kv_blocks - 1)
    def _finish():
        for h in range(o_ref.shape[2]):
            denom = jnp.maximum(l_ref[:, h:h + 1], 1e-30)
            o_ref[0, :, h, :] = (acc_ref[h] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "block_q", "block_k", "config",
                     "interpret", "page_size"),
)
def flash_attention(
    q: jnp.ndarray,                  # (B, Sq, H, Dh)
    k: jnp.ndarray,                  # (B, Sk, KV, Dh) | paged (P, page, KV, Dh)
    v: jnp.ndarray,
    kv_len: jnp.ndarray | None = None,   # () or (B,) int32; None -> Sk
    q_start: jnp.ndarray | None = None,  # () or (B,) int32; None -> Sk - Sq
    *,
    window: jnp.ndarray | None = None,   # () or (B,) int32 width W; None -> full
    k_scale: jnp.ndarray | None = None,  # () or (B,) fp32; k is quantized (int8/fp8)
    v_scale: jnp.ndarray | None = None,  # () or (B,) fp32; v is quantized
    causal: bool = True,
    scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    config: BlockConfig | None = None,
    interpret: bool = False,
    block_tables: jnp.ndarray | None = None,  # (B, nblocks) int32 page ids
    page_size: int | None = None,             # tokens per page; None -> k.shape[1]
) -> jnp.ndarray:
    cfg = config if config is not None else _DEFAULTS
    if block_q is None:
        block_q = cfg.get("block_q", _DEFAULTS["block_q"])
    if block_k is None:
        block_k = cfg.get("block_k", _DEFAULTS["block_k"])
    b, sq, h, dh = q.shape
    paged = block_tables is not None
    if paged:
        page = k.shape[1] if page_size is None else page_size
        assert k.shape[1] == page, f"pool page {k.shape[1]} != page_size {page}"
        nblocks = block_tables.shape[1]
        sk = nblocks * page                  # logical KV extent
    else:
        sk = k.shape[1]
    kv = k.shape[2]
    assert h % kv == 0, f"GQA requires H % KV == 0, got {h} % {kv}"
    group = h // kv
    scale = dh ** -0.5 if scale is None else scale
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    if paged:
        # a k/v tile must never straddle a page boundary: the index map
        # resolves ONE physical page per grid step
        block_k = math.gcd(min(block_k, page), page)
    q_blocks = pl.cdiv(sq, block_q)
    kv_blocks = pl.cdiv(sk, block_k)
    dyn_offset = q_start is not None
    kv_len = jnp.broadcast_to(
        jnp.asarray(sk if kv_len is None else kv_len, jnp.int32), (b,)
    )
    q_start = jnp.broadcast_to(
        jnp.asarray(sk - sq if q_start is None else q_start, jnp.int32), (b,)
    )
    windowed = window is not None
    quantized = k_scale is not None
    if quantized != (v_scale is not None):
        raise ValueError("quantized KV needs both k_scale and v_scale")
    rows = [kv_len, q_start]
    if windowed:
        # per-batch window start of the FIRST query: local query i's
        # window opens at ws + i.  Queries are anchored to the end of the
        # written prefix in every geometry (decode/chunk/prefill), so the
        # base is kv_len - sq.
        w = jnp.broadcast_to(jnp.asarray(window, jnp.int32), (b,))
        rows.append(kv_len - sq - w + 1)
    meta = jnp.stack(rows)                       # (2 [+1], B) in SMEM
    tbl_row = len(rows)                          # first block-table meta row
    if paged:
        # block-table rows ride below the scalar rows: meta[tbl_row+j, bi]
        # is the physical page of row bi's j-th logical block
        meta = jnp.concatenate(
            [meta, block_tables.astype(jnp.int32).T], axis=0
        )                                        # (tbl_row + nblocks, B)

    kernel = functools.partial(
        _flash_kernel,
        causal=causal,
        scale=scale,
        block_q=block_q,
        block_k=block_k,
        kv_blocks=kv_blocks,
        q_offset=sk - sq,
        dyn_offset=dyn_offset,
        windowed=windowed,
        quantized=quantized,
        group=group,
    )
    if paged:
        bpp = page // block_k                    # k-tiles per page

        def kv_spec():
            return pl.BlockSpec(
                (1, block_k, kv, dh),
                # logical k-block ik lives in page meta[tbl_row + ik // bpp,
                # bi], tile ik % bpp within it — the DMA performs the gather
                lambda bi, iq, ik, m: (m[tbl_row + ik // bpp, bi],
                                       ik % bpp, 0, 0),
            )
    else:
        def kv_spec():
            return pl.BlockSpec(
                (1, block_k, kv, dh), lambda bi, iq, ik, m: (bi, ik, 0, 0)
            )
    q_spec = pl.BlockSpec((1, block_q, h, dh), lambda bi, iq, ik, m: (bi, iq, 0, 0))
    in_specs = [q_spec, kv_spec(), kv_spec()]
    operands = [q, k, v]
    if quantized:
        # the fp32 dequantization scales sit whole in SMEM, one column
        # per batch row: row 0 k, row 1 v
        in_specs.insert(0, pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.insert(0, jnp.stack([
            jnp.broadcast_to(jnp.asarray(s, jnp.float32), (b,))
            for s in (k_scale, v_scale)
        ]))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, q_blocks, kv_blocks),
            in_specs=in_specs,
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((block_q, h), jnp.float32),
                pltpu.VMEM((block_q, h), jnp.float32),
                pltpu.VMEM((h, block_q, dh), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, sq, h, dh), q.dtype),
        interpret=interpret,
    )(meta, *operands)
    return out
