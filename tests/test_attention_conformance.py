"""Attention conformance grid: ref vs pallas over dtype x shape x layout.

Modeled on the xformers memory-efficient-attention test matrix: one
parametrized grid sweeps every attention entry point (`flash_attention`
prefill, `chunk_attention`, `decode_attention`) over

  * dtype (fp32 / bf16, per-dtype tolerances),
  * seq/kv geometry — including ragged Sq < Sk, non-multiple-of-block
    tails, and GQA group widths,
  * causal diagonals and dynamic q_start offsets,
  * kv_len padding masks (unwritten cache slots),
  * contiguous vs paged layout (page pools + shuffled block tables,
    poisoned park page),
  * sliding windows W in {page, 2*page, >= kv_len} for the windowed
    variants, with W >= kv_len pinned bit-identical to full attention,
  * layer-stacked pools (L = 3, the layer a scan carries read at index 0
    or 2, poison in the others) pinned bit-identical to the per-layer
    pool, for the Pallas kernel and the reference alike,

against a single fp32 masked-softmax oracle.  Every geometry is also
round-tripped through the tuner synthesizer (`bucket_shapes` ->
`args_from_shapes`), pinning that autotune/dispatch/bundles can rebuild
a workload for every shape the serving paths emit — paged ones included.
"""

import itertools
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.platform import POD_SIM
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_attention_ref import (
    attention_ref,
    chunk_attention_ref,
    decode_attention_ref,
    windowed_attention_ref,
)
from repro.kernels.ops import _NATIVES_INTERPRET, _REFS, tuners
from repro.tuning import bucket_shapes
from repro.tuning.config import BlockConfig

TOLS = {"float32": 2e-5, "bfloat16": 2e-2}
DTYPES = tuple(TOLS)
POISON = 50.0     # park-page fill: loud if it ever leaks into an output


def _seed(*parts) -> int:
    """Fold a grid cell's identifying parts (fixture name, geometry,
    dtype, ...) into a stable 31-bit PRNG seed.  Every fixture in this
    file derives its randomness from its own cell id ONLY — never from a
    shared or ad-hoc key — so the repro recipe for any failure is simply
    `pytest "tests/test_attention_conformance.py::<failing id>"`: the
    single test regenerates bit-identical tensors regardless of which
    other cells ran (or didn't) in the same process."""
    return zlib.crc32(":".join(map(str, parts)).encode()) & 0x7FFFFFFF


def _mk(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.dtype(dtype))


def _oracle(q, k, v, kv_len=None, q_start=None, causal=True):
    """fp32 masked-softmax oracle of the flash kernel's exact semantics:
    query i (global position q_start + i) sees keys j with j < kv_len
    and, when causal, j <= q_start + i."""
    b, sq, h, dh = q.shape
    sk, kv = k.shape[1], k.shape[2]
    group = h // kv
    scale = dh ** -0.5
    kv_len = jnp.broadcast_to(
        jnp.asarray(sk if kv_len is None else kv_len, jnp.int32), (b,))
    q_start = jnp.broadcast_to(
        jnp.asarray(sk - sq if q_start is None else q_start, jnp.int32), (b,))
    qg = (q.reshape(b, sq, kv, group, dh) * scale).astype(jnp.float32)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k.astype(jnp.float32))
    ki = jnp.arange(sk)
    mask = ki[None, :] < kv_len[:, None]                       # (B, Sk)
    mask = mask[:, None, :]                                    # (B, 1, Sk)
    if causal:
        qi = jnp.arange(sq)[None, :, None] + q_start[:, None, None]
        mask = mask & (ki[None, None, :] <= qi)                # (B, Sq, Sk)
    else:
        mask = jnp.broadcast_to(mask, (b, sq, sk))
    s = jnp.where(mask[:, None, None], s, -jnp.inf)
    p = jnp.exp(s - s.max(axis=-1, keepdims=True))
    p = p / p.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32))
    return out.reshape(b, sq, h, dh).astype(q.dtype)


def _paged_layout(k, v, page, seed):
    """Scatter a contiguous (B, S, KV, Dh) cache into page pools through a
    SHUFFLED permutation block table, so a kernel that ignores the table
    (or mixes up rows) cannot pass by accident.  Page 0 is the reserved
    park page, poisoned with a loud constant.  `seed` must come from
    `_seed(...)` over the calling cell's id parts (see its docstring)."""
    b, s = k.shape[:2]
    assert s % page == 0
    n = s // page
    npages = 1 + b * n
    perm = np.random.default_rng(seed).permutation(np.arange(1, npages))
    bt = jnp.asarray(perm.reshape(b, n), jnp.int32)
    pool_shape = (npages, page) + k.shape[2:]
    pool_k = jnp.full(pool_shape, POISON, k.dtype)
    pool_v = jnp.full(pool_shape, POISON, v.dtype)
    kb = k.reshape(b, n, page, *k.shape[2:]).reshape(b * n, page, *k.shape[2:])
    vb = v.reshape(b, n, page, *v.shape[2:]).reshape(b * n, page, *v.shape[2:])
    pool_k = pool_k.at[bt.reshape(-1)].set(kb)
    pool_v = pool_v.at[bt.reshape(-1)].set(vb)
    return pool_k, pool_v, bt


STACK_LAYERS = 3
STACKED = ("stacked0", "stacked2")    # layouts: the pool at layer 0 / 2 of 3


def _stack(pool, layer):
    """The (L, P, page, KV, Dh) stack a layer scan carries, holding `pool`
    at `layer` and poison in every other layer: a read of the wrong layer
    is loud."""
    layers = [pool if i == layer else jnp.full_like(pool, POISON)
              for i in range(STACK_LAYERS)]
    return jnp.stack(layers)


def _assert_stacked_identical(op, layout, args):
    """`op` on the layer-stacked pools (layer index as the trailing arg)
    must equal `op` on the per-layer pools bit for bit, for the Pallas
    kernel (interpret) and for the reference adapter.  `args` are the
    per-layer call's: q, pool_k, pool_v, pos, block table[, window,
    k_scale, v_scale]."""
    layer = int(layout[len("stacked"):])
    q, pool_k, pool_v, *rest = args
    tail = rest + [None] * (5 - len(rest))
    for impl in (_NATIVES_INTERPRET[op], _REFS[op]):
        want = impl(*args)
        got = impl(q, _stack(pool_k, layer), _stack(pool_v, layer), *tail,
                   jnp.asarray(layer, jnp.int32))
        assert np.array_equal(np.asarray(got), np.asarray(want))


def _close(got, want, dtype, scale=1):
    tol = scale * TOLS[dtype]
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=tol, rtol=tol)


# ---------------------------------------------------------------------------
# flash (prefill): geometry x causal x kv_len padding x layout
# ---------------------------------------------------------------------------

# (b, sq, sk, h, kv, dh) — ragged Sq < Sk, tails off the 8-wide blocks,
# GQA groups, and page-divisible extents for the paged variants
FLASH_GEOMS = [
    (1, 8, 8, 2, 2, 8),        # square, block-exact
    (2, 7, 19, 2, 1, 8),       # ragged + non-multiple-of-block tails
    (1, 30, 30, 2, 2, 8),      # multi-block with tail
    (1, 5, 40, 4, 2, 16),      # short queries vs long cache, GQA group 2
]


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("pad", [0, 3])
@pytest.mark.parametrize("geom", FLASH_GEOMS, ids=lambda g: "x".join(map(str, g)))
def test_flash_grid(geom, pad, causal, dtype):
    b, sq, sk, h, kv, dh = geom
    ks = jax.random.split(jax.random.PRNGKey(_seed("flash", geom, dtype)), 3)
    q = _mk(ks[0], (b, sq, h, dh), dtype)
    k = _mk(ks[1], (b, sk, kv, dh), dtype)
    v = _mk(ks[2], (b, sk, kv, dh), dtype)
    kv_len = None if pad == 0 else jnp.asarray(sk - pad, jnp.int32)
    out = flash_attention(q, k, v, kv_len=kv_len, causal=causal,
                          block_q=8, block_k=8, interpret=True)
    want = _oracle(q, k, v, kv_len=kv_len, causal=causal)
    _close(out, want, dtype, scale=5)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("geom", [(1, 8, 8, 2, 2, 8), (1, 5, 40, 4, 2, 16)],
                         ids=lambda g: "x".join(map(str, g)))
def test_flash_paged_matches_contiguous(geom, dtype):
    """Paged flash through a shuffled permutation table must equal the
    contiguous kernel bit-for-bit-ish — same math, different DMA route."""
    b, sq, sk, h, kv, dh = geom
    page = 8
    ks = jax.random.split(jax.random.PRNGKey(_seed("flash-paged", geom, dtype)), 3)
    q = _mk(ks[0], (b, sq, h, dh), dtype)
    k = _mk(ks[1], (b, sk, kv, dh), dtype)
    v = _mk(ks[2], (b, sk, kv, dh), dtype)
    kv_len = jnp.asarray(sk - 2, jnp.int32)
    cont = flash_attention(q, k, v, kv_len=kv_len, causal=True,
                           block_q=8, block_k=8, interpret=True)
    pool_k, pool_v, bt = _paged_layout(k, v, page, _seed("flash-paged", geom, dtype, "pool"))
    paged = flash_attention(q, pool_k, pool_v, kv_len=kv_len, causal=True,
                            block_q=8, block_k=8, interpret=True,
                            block_tables=bt, page_size=page)
    _close(paged, cont, dtype)


@pytest.mark.parametrize("case", ["no-table", "single-pool-with-layer"])
def test_flash_layer_needs_stacked_paged_pools(case):
    """A layer index reads a layer-stacked paged pool only: with contiguous
    k/v, or beside a single pool (already read as a stack of one), the
    call is refused rather than read at the wrong offset."""
    ks = jax.random.split(jax.random.PRNGKey(_seed("flash-layer", case)), 3)
    q = _mk(ks[0], (1, 8, 2, 8), jnp.float32)
    k = _mk(ks[1], (1, 16, 2, 8), jnp.float32)
    v = _mk(ks[2], (1, 16, 2, 8), jnp.float32)
    layer = jnp.asarray(0, jnp.int32)
    if case == "no-table":
        args, paged = (q, k, v), {}
    else:
        pool_k, pool_v, bt = _paged_layout(k, v, 8, _seed("flash-layer", "pool"))
        args, paged = (q, pool_k, pool_v), {"block_tables": bt, "page_size": 8}
    with pytest.raises(ValueError, match="layer needs"):
        flash_attention(*args, interpret=True, layer=layer, **paged)


# ---------------------------------------------------------------------------
# decode_attention: pos offsets x padding x layout
# ---------------------------------------------------------------------------

# (b, smax, h, kv, dh, pos) — scalar and per-row vector positions,
# non-power-of-two extents, first/last-slot edges
DECODE_GEOMS = [
    (2, 32, 2, 2, 8, (5, 17)),
    (1, 24, 2, 1, 8, 10),
    (3, 48, 4, 2, 16, (0, 47, 20)),
]


def _decode_args(geom, dtype, tag="decode"):
    b, smax, h, kv, dh, pos = geom
    ks = jax.random.split(jax.random.PRNGKey(_seed(tag, geom, dtype)), 3)
    q = _mk(ks[0], (b, 1, h, dh), dtype)
    k = _mk(ks[1], (b, smax, kv, dh), dtype)
    v = _mk(ks[2], (b, smax, kv, dh), dtype)
    posv = jnp.asarray(pos, jnp.int32)
    return q, k, v, posv


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("layout", ["contiguous", "paged", *STACKED])
@pytest.mark.parametrize("geom", DECODE_GEOMS, ids=lambda g: f"smax{g[1]}b{g[0]}")
def test_decode_grid(geom, layout, dtype):
    q, k, v, pos = _decode_args(geom, dtype)
    want = decode_attention_ref(q, k, v, pos)   # pinned against _oracle below
    if layout in STACKED:
        pool_k, pool_v, bt = _paged_layout(k, v, 8, _seed("decode", geom, dtype, "pool"))
        _assert_stacked_identical("decode_attention", layout,
                                  (q, pool_k, pool_v, pos, bt))
        return
    if layout == "paged":
        page = 8
        pool_k, pool_v, bt = _paged_layout(k, v, page, _seed("decode", geom, dtype, "pool"))
        out = _NATIVES_INTERPRET["decode_attention"](q, pool_k, pool_v, pos, bt)
        ref = decode_attention_ref(q, pool_k, pool_v, pos, bt)
        _close(ref, want, dtype)                # ref gather == logical cache
    else:
        out = _NATIVES_INTERPRET["decode_attention"](q, k, v, pos)
    _close(out, want, dtype, scale=5)


def test_decode_ref_matches_oracle():
    """The decode ref itself is pinned to the flash oracle (kv_len=pos+1,
    non-causal) so the grid above is anchored to one ground truth."""
    q, k, v, pos = _decode_args(DECODE_GEOMS[0], "float32")
    want = _oracle(q, k, v, kv_len=pos + 1, causal=False)
    _close(decode_attention_ref(q, k, v, pos), want, "float32")


# ---------------------------------------------------------------------------
# chunk_attention: q_start offsets x tails x layout
# ---------------------------------------------------------------------------

# (c, smax, h, kv, dh, pos) — chunk at the window start, mid-cache, and
# at a non-multiple-of-block offset; B == 1 (the serving prefill shape)
CHUNK_GEOMS = [
    (8, 32, 2, 2, 8, 8),
    (16, 48, 2, 1, 8, 16),
    (8, 24, 4, 2, 16, 0),
]


def _chunk_args(geom, dtype, tag="chunk"):
    c, smax, h, kv, dh, pos = geom
    ks = jax.random.split(jax.random.PRNGKey(_seed(tag, geom, dtype)), 3)
    q = _mk(ks[0], (1, c, h, dh), dtype)
    k = _mk(ks[1], (1, smax, kv, dh), dtype)
    v = _mk(ks[2], (1, smax, kv, dh), dtype)
    return q, k, v, pos


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("layout", ["contiguous", "paged", *STACKED])
@pytest.mark.parametrize("geom", CHUNK_GEOMS, ids=lambda g: f"c{g[0]}pos{g[5]}")
def test_chunk_grid(geom, layout, dtype):
    q, k, v, pos = _chunk_args(geom, dtype)
    want = chunk_attention_ref(q, k, v, pos)
    if layout in STACKED:
        pool_k, pool_v, bt = _paged_layout(k, v, geom[0], _seed("chunk", geom, dtype, "pool"))
        _assert_stacked_identical("chunk_attention", layout,
                                  (q, pool_k, pool_v, pos, bt))
        return
    if layout == "paged":
        page = geom[0]                          # serving invariant: page == C
        pool_k, pool_v, bt = _paged_layout(k, v, page, _seed("chunk", geom, dtype, "pool"))
        out = _NATIVES_INTERPRET["chunk_attention"](q, pool_k, pool_v, pos, bt)
        _close(chunk_attention_ref(q, pool_k, pool_v, pos, bt), want, dtype)
    else:
        out = _NATIVES_INTERPRET["chunk_attention"](q, k, v, pos)
    _close(out, want, dtype, scale=5)


def test_chunk_ref_matches_oracle():
    """chunk_attention == flash with the diagonal re-anchored at pos and
    kv_len = pos + C."""
    q, k, v, pos = _chunk_args(CHUNK_GEOMS[0], "float32")
    want = _oracle(q, k, v, kv_len=pos + q.shape[1], q_start=pos, causal=True)
    _close(chunk_attention_ref(q, k, v, pos), want, "float32")


def test_paged_park_page_is_inert():
    """Zero (park) block-table entries past the written prefix must not
    leak the park page's poison into the output: the kv_len mask discards
    those lanes even though their DMAs are issued."""
    q, k, v, pos = _decode_args((2, 32, 2, 2, 8, (5, 9)), "float32", tag="park")
    pool_k, pool_v, bt = _paged_layout(k, v, 8, _seed("park", "pool"))
    bt = bt.at[:, 2:].set(0)                    # park everything past page 1
    out = _NATIVES_INTERPRET["decode_attention"](q, pool_k, pool_v, pos, bt)
    want = decode_attention_ref(q, k, v, pos)   # pos < 16: logical prefix only
    assert np.all(np.isfinite(np.asarray(out)))
    _close(out, want, "float32", scale=5)


# ---------------------------------------------------------------------------
# tuner synthesizer round-trip: every grid geometry must be rebuildable
# ---------------------------------------------------------------------------

def _no_scalars(shapes: str) -> str:
    """pos is traced in recorded traffic ('scalar'/1-d part) but a python
    int in synthesized args (invisible to bucket_shapes) — compare the
    array parts only."""
    return ",".join(p for p in shapes.split(",")
                    if p and p != "scalar" and "x" in p)


def _roundtrip(op, args, expect_feasible=True):
    t = tuners()[op]
    shapes, dtype = bucket_shapes(args)
    synth = t.args_from_shapes(POD_SIM, shapes, dtype)
    assert synth is not None, f"{op}: no synth for bucket {shapes}"
    shapes2, dtype2 = bucket_shapes(synth)
    assert _no_scalars(shapes2) == _no_scalars(shapes), (shapes2, shapes)
    assert dtype2 == dtype
    feasible = [
        cfg for cfg in (
            BlockConfig.make(**dict(zip(t.space, vals)))
            for vals in itertools.product(*t.space.values()))
        if t.feasible(cfg, POD_SIM, synth)
    ]
    if expect_feasible:
        assert feasible, f"{op}: no feasible config for bucket {shapes}"


def _stacked_args(q, pool_k, pool_v, pos, bt, w=None):
    """A layer-stacked call's positional args: the window/scale slots
    held by None, the traced layer index last."""
    return (q, _stack(pool_k, 2), _stack(pool_v, 2), pos, bt, w, None, None,
            jnp.asarray(2, jnp.int32))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("layout", ["contiguous", "paged", "stacked"])
@pytest.mark.parametrize("geom", DECODE_GEOMS, ids=lambda g: f"smax{g[1]}b{g[0]}")
def test_decode_synth_roundtrip(geom, layout, dtype):
    q, k, v, pos = _decode_args(geom, dtype)
    if layout == "stacked":
        pool_k, pool_v, bt = _paged_layout(
            jnp.tile(k, (1, -(-32 // k.shape[1]), 1, 1))[:, :32],
            jnp.tile(v, (1, -(-32 // v.shape[1]), 1, 1))[:, :32], 16,
            _seed("decode-rt", geom, dtype, "pool"))
        _roundtrip("decode_attention", _stacked_args(q, pool_k, pool_v, pos, bt))
    elif layout == "paged":
        page = 16                               # >= the space's smallest bk
        pool_k, pool_v, bt = _paged_layout(
            jnp.tile(k, (1, -(-32 // k.shape[1]), 1, 1))[:, :32],
            jnp.tile(v, (1, -(-32 // v.shape[1]), 1, 1))[:, :32], page,
            _seed("decode-rt", geom, dtype, "pool"))
        _roundtrip("decode_attention", (q, pool_k, pool_v, pos, bt))
    else:
        _roundtrip("decode_attention", (q, k, v, pos))


# ---------------------------------------------------------------------------
# windowed (sliding-window causal) variants: dtype x geometry x layout x W
#
# Window column legend — W in {page, 2*page, full}:
#   * page:  W == page size: the sharpest cut, most KV pages skipped;
#   * 2page: window straddles a page boundary mid-page;
#   * full:  W >= kv_len: must be BIT-IDENTICAL to the unwindowed kernel
#            (same mask, same skip set, same float ops).
# ---------------------------------------------------------------------------

WINDOWS = ("page", "2page", "full")


def _win(wtag, page, full):
    """Resolve a window column tag to a concrete W (int32 scalar)."""
    w = {"page": page, "2page": 2 * page, "full": full}[wtag]
    return jnp.asarray(w, jnp.int32)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("wtag", WINDOWS)
@pytest.mark.parametrize("geom", FLASH_GEOMS, ids=lambda g: "x".join(map(str, g)))
def test_windowed_flash_grid(geom, wtag, dtype):
    b, sq, sk, h, kv, dh = geom
    ks = jax.random.split(jax.random.PRNGKey(_seed("wflash", geom, dtype)), 3)
    q = _mk(ks[0], (b, sq, h, dh), dtype)
    k = _mk(ks[1], (b, sk, kv, dh), dtype)
    v = _mk(ks[2], (b, sk, kv, dh), dtype)
    w = _win(wtag, 8, sk)
    out = flash_attention(q, k, v, window=w, causal=True,
                          block_q=8, block_k=8, interpret=True)
    want = windowed_attention_ref(q, k, v, w)
    _close(out, want, dtype, scale=5)
    if wtag == "full":
        full = flash_attention(q, k, v, causal=True,
                               block_q=8, block_k=8, interpret=True)
        assert np.array_equal(np.asarray(out), np.asarray(full))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("wtag", WINDOWS)
@pytest.mark.parametrize("geom", [(1, 8, 8, 2, 2, 8), (1, 5, 40, 4, 2, 16)],
                         ids=lambda g: "x".join(map(str, g)))
def test_windowed_flash_paged_matches_contiguous(geom, wtag, dtype):
    """Paged + windowed flash: the window-start meta row shifts the block
    table to SMEM row 3+, so this pins the shifted index maps against the
    contiguous windowed kernel."""
    b, sq, sk, h, kv, dh = geom
    page = 8
    ks = jax.random.split(jax.random.PRNGKey(_seed("wflash-paged", geom, dtype)), 3)
    q = _mk(ks[0], (b, sq, h, dh), dtype)
    k = _mk(ks[1], (b, sk, kv, dh), dtype)
    v = _mk(ks[2], (b, sk, kv, dh), dtype)
    w = _win(wtag, page, sk)
    cont = flash_attention(q, k, v, window=w, causal=True,
                           block_q=8, block_k=8, interpret=True)
    pool_k, pool_v, bt = _paged_layout(
        k, v, page, _seed("wflash-paged", geom, dtype, "pool"))
    paged = flash_attention(q, pool_k, pool_v, window=w, causal=True,
                            block_q=8, block_k=8, interpret=True,
                            block_tables=bt, page_size=page)
    _close(paged, cont, dtype)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("layout", ["contiguous", "paged", *STACKED])
@pytest.mark.parametrize("wtag", WINDOWS)
@pytest.mark.parametrize("geom", DECODE_GEOMS, ids=lambda g: f"smax{g[1]}b{g[0]}")
def test_windowed_decode_grid(geom, layout, wtag, dtype):
    q, k, v, pos = _decode_args(geom, dtype, tag="wdecode")
    smax = geom[1]
    w = _win(wtag, 8, smax)                     # full: W >= pos+1 for all rows
    want = decode_attention_ref(q, k, v, pos, None, w)
    if layout in STACKED:
        pool_k, pool_v, bt = _paged_layout(
            k, v, 8, _seed("wdecode", geom, dtype, "pool"))
        _assert_stacked_identical("decode_attention", layout,
                                  (q, pool_k, pool_v, pos, bt, w))
        return
    if layout == "paged":
        pool_k, pool_v, bt = _paged_layout(
            k, v, 8, _seed("wdecode", geom, dtype, "pool"))
        out = _NATIVES_INTERPRET["decode_attention"](q, pool_k, pool_v, pos, bt, w)
        full = _NATIVES_INTERPRET["decode_attention"](q, pool_k, pool_v, pos, bt)
        ref = decode_attention_ref(q, pool_k, pool_v, pos, bt, w)
        _close(ref, want, dtype)                # ref gather == logical cache
    else:
        out = _NATIVES_INTERPRET["decode_attention"](q, k, v, pos, None, w)
        full = _NATIVES_INTERPRET["decode_attention"](q, k, v, pos)
    _close(out, want, dtype, scale=5)
    if wtag == "full":
        assert np.array_equal(np.asarray(out), np.asarray(full))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("layout", ["contiguous", "paged", *STACKED])
@pytest.mark.parametrize("wtag", WINDOWS)
@pytest.mark.parametrize("geom", CHUNK_GEOMS, ids=lambda g: f"c{g[0]}pos{g[5]}")
def test_windowed_chunk_grid(geom, layout, wtag, dtype):
    q, k, v, pos = _chunk_args(geom, dtype, tag="wchunk")
    c, smax = geom[0], geom[1]
    w = _win(wtag, c, smax)                     # full: W >= pos+C for all geoms
    want = chunk_attention_ref(q, k, v, pos, None, w)
    if layout in STACKED:
        pool_k, pool_v, bt = _paged_layout(
            k, v, c, _seed("wchunk", geom, dtype, "pool"))
        _assert_stacked_identical("chunk_attention", layout,
                                  (q, pool_k, pool_v, pos, bt, w))
        return
    if layout == "paged":
        page = c                                # serving invariant: page == C
        pool_k, pool_v, bt = _paged_layout(
            k, v, page, _seed("wchunk", geom, dtype, "pool"))
        out = _NATIVES_INTERPRET["chunk_attention"](q, pool_k, pool_v, pos, bt, w)
        full = _NATIVES_INTERPRET["chunk_attention"](q, pool_k, pool_v, pos, bt)
        _close(chunk_attention_ref(q, pool_k, pool_v, pos, bt, w), want, dtype)
    else:
        out = _NATIVES_INTERPRET["chunk_attention"](q, k, v, pos, None, w)
        full = _NATIVES_INTERPRET["chunk_attention"](q, k, v, pos)
    _close(out, want, dtype, scale=5)
    if wtag == "full":
        assert np.array_equal(np.asarray(out), np.asarray(full))


def test_windowed_ref_anchors():
    """Two sharp pins on the windowed oracle itself: W >= Sk reproduces the
    causal oracle exactly, and W == 1 collapses the softmax onto each
    query's own key (output == v at the query positions when group == 1)."""
    b, sq, sk, h, kv, dh = 2, 8, 8, 2, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(_seed("wref", "anchors")), 3)
    q = _mk(ks[0], (b, sq, h, dh), "float32")
    k = _mk(ks[1], (b, sk, kv, dh), "float32")
    v = _mk(ks[2], (b, sk, kv, dh), "float32")
    wide = windowed_attention_ref(q, k, v, jnp.asarray(sk, jnp.int32))
    _close(wide, attention_ref(q, k, v, causal=True), "float32")
    one = windowed_attention_ref(q, k, v, jnp.asarray(1, jnp.int32))
    want = jnp.repeat(v, h // kv, axis=2)       # each query sees only key i
    _close(one, want, "float32")


def test_windowed_dead_pages_are_inert():
    """Pages wholly below the window start may be PARKed (remapped to the
    poisoned page 0) by the scheduler's sliding-window recycler — the
    kernel must never read through them: the window mask (and the skipped
    grid steps) make their contents unobservable."""
    geom = (2, 32, 2, 2, 8, (17, 20))
    q, k, v, pos = _decode_args(geom, "float32", tag="wdead")
    w = jnp.asarray(8, jnp.int32)
    pool_k, pool_v, bt = _paged_layout(k, v, 8, _seed("wdead", "pool"))
    # window starts at pos-7 (>= 10 for both rows): page 0 (keys 0..7) is
    # wholly out-of-window for every row -> park it, as the scheduler would
    bt = bt.at[:, 0].set(0)
    out = _NATIVES_INTERPRET["decode_attention"](q, pool_k, pool_v, pos, bt, w)
    want = decode_attention_ref(q, k, v, pos, None, w)
    assert np.all(np.isfinite(np.asarray(out)))
    _close(out, want, "float32", scale=5)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("geom", FLASH_GEOMS, ids=lambda g: "x".join(map(str, g)))
def test_windowed_synth_roundtrip(geom, dtype):
    b, sq, sk, h, kv, dh = geom
    ks = jax.random.split(jax.random.PRNGKey(_seed("wsynth", geom, dtype)), 3)
    q = _mk(ks[0], (b, sq, h, dh), dtype)
    k = _mk(ks[1], (b, sk, kv, dh), dtype)
    v = _mk(ks[2], (b, sk, kv, dh), dtype)
    # the space's smallest block_q is 16: shorter query extents synthesize
    # fine but legitimately have no feasible tuning config
    _roundtrip("windowed_attention", (q, k, v, jnp.asarray(8, jnp.int32)),
               expect_feasible=sq >= 16)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("layout", ["contiguous", "paged"])
@pytest.mark.parametrize("geom", DECODE_GEOMS, ids=lambda g: f"smax{g[1]}b{g[0]}")
def test_windowed_decode_synth_roundtrip(geom, layout, dtype):
    q, k, v, pos = _decode_args(geom, dtype, tag="wdecode-rt")
    w = jnp.asarray(16, jnp.int32)
    if layout == "paged":
        page = 16                               # >= the space's smallest bk
        pool_k, pool_v, bt = _paged_layout(
            jnp.tile(k, (1, -(-32 // k.shape[1]), 1, 1))[:, :32],
            jnp.tile(v, (1, -(-32 // v.shape[1]), 1, 1))[:, :32], page,
            _seed("wdecode-rt", geom, dtype, "pool"))
        _roundtrip("decode_attention", (q, pool_k, pool_v, pos, bt, w))
    else:
        _roundtrip("decode_attention", (q, k, v, pos, None, w))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("layout", ["contiguous", "paged", "stacked"])
@pytest.mark.parametrize("geom", CHUNK_GEOMS, ids=lambda g: f"c{g[0]}pos{g[5]}")
def test_windowed_chunk_synth_roundtrip(geom, layout, dtype):
    q, k, v, pos = _chunk_args(geom, dtype, tag="wchunk-rt")
    w = jnp.asarray(16, jnp.int32)
    ok = geom[0] >= 16                          # see test_chunk_synth_roundtrip
    if layout == "stacked":
        page = max(geom[0], 16)
        s = -(-k.shape[1] // page) * page
        pool_k, pool_v, bt = _paged_layout(
            jnp.pad(k, ((0, 0), (0, s - k.shape[1]), (0, 0), (0, 0))),
            jnp.pad(v, ((0, 0), (0, s - v.shape[1]), (0, 0), (0, 0))), page,
            _seed("wchunk-rt", geom, dtype, "pool"))
        _roundtrip("chunk_attention", _stacked_args(q, pool_k, pool_v, pos, bt, w),
                   expect_feasible=ok)
    elif layout == "paged":
        page = max(geom[0], 16)
        s = -(-k.shape[1] // page) * page
        pool_k, pool_v, bt = _paged_layout(
            jnp.pad(k, ((0, 0), (0, s - k.shape[1]), (0, 0), (0, 0))),
            jnp.pad(v, ((0, 0), (0, s - v.shape[1]), (0, 0), (0, 0))), page,
            _seed("wchunk-rt", geom, dtype, "pool"))
        _roundtrip("chunk_attention", (q, pool_k, pool_v, pos, bt, w),
                   expect_feasible=ok)
    else:
        _roundtrip("chunk_attention", (q, k, v, pos, None, w),
                   expect_feasible=ok)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("layout", ["contiguous", "paged"])
@pytest.mark.parametrize("geom", CHUNK_GEOMS, ids=lambda g: f"c{g[0]}pos{g[5]}")
def test_chunk_synth_roundtrip(geom, layout, dtype):
    q, k, v, pos = _chunk_args(geom, dtype)
    # the chunk space's smallest block_q is 16: c=8 buckets synthesize
    # fine but legitimately have no feasible tuning config
    ok = geom[0] >= 16
    if layout == "paged":
        page = max(geom[0], 16)
        s = -(-k.shape[1] // page) * page
        pool_k, pool_v, bt = _paged_layout(
            jnp.pad(k, ((0, 0), (0, s - k.shape[1]), (0, 0), (0, 0))),
            jnp.pad(v, ((0, 0), (0, s - v.shape[1]), (0, 0), (0, 0))), page,
            _seed("chunk-rt", geom, dtype, "pool"))
        _roundtrip("chunk_attention", (q, pool_k, pool_v, pos, bt),
                   expect_feasible=ok)
    else:
        _roundtrip("chunk_attention", (q, k, v, pos), expect_feasible=ok)
