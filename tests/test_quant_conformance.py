"""Quantization conformance grid: int8/fp8 kernels vs the fp32 oracle.

Extends the attention-conformance grid (same `_seed` cell-id recipe, same
paged-layout harness) to the quantized serving path:

  * `quant_matmul` — fp32 activations against per-output-channel int8/fp8
    weights, pinned two ways per cell: tight against the quantized jnp
    ref (same codes, same math) and inside a per-format error ENVELOPE
    against the full-precision fp32 matmul;
  * quantized-KV `decode_attention` / `chunk_attention` — int8/fp8 cache
    pools with per-row fp32 scales riding the kernel meta, over ragged
    geometry x contiguous/paged x windowed/full, each cell pinned tight
    against the quantized ref and enveloped against the fp32 oracle
    computed on the ORIGINAL (pre-quantization) cache;
  * bit-identity pins: W >= kv_len quantized-windowed == quantized-full,
    paged == contiguous on identical codes, layer-stacked pools (L = 3,
    read at layer 0 or 2) == the per-layer pool, kernel and reference;
  * tuner synthesizer round-trips for the composite "float32+int8" /
    "float32+fp8" buckets, so autotune can rebuild every quantized
    geometry the serving paths emit.

The per-format envelopes double as documentation: they are the measured
worst-case dequantization error (~3x headroom) for normal-distributed
data, quoted in docs/quantization.md — a kernel change that silently
degrades quantized accuracy fails here before it ships.
"""

import itertools
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.platform import POD_SIM
from repro.kernels.flash_attention_ref import (
    chunk_attention_ref,
    decode_attention_ref,
)
from repro.kernels.ops import _NATIVES_INTERPRET, _REFS, tuners
from repro.kernels.quant import (
    FP8_MAX,
    INT8_MAX,
    FORMATS,
    dequantize,
    quantize_per_channel,
    storage_dtype,
)
from repro.kernels.quant_matmul_ref import quant_matmul_ref
from repro.tuning import bucket_shapes
from repro.tuning.config import BlockConfig

TOL = 2e-5        # fp32 interpret-mode tolerance (kernel vs quantized ref)
POISON = 50.0     # park-page fill: loud if it ever leaks into an output

# Per-format error envelopes vs the fp32 oracle, for normal-distributed
# inputs at the grid's sizes.  Measured worst cases: attention int8
# ~0.02-0.05, fp8 ~0.05-0.1; matmul (D=64 contraction) int8 ~0.1, fp8
# ~0.5.  The envelopes carry ~3x headroom — loose enough to be stable,
# tight enough that a broken dequant (wrong scale, wrong axis, missing
# clip) blows straight through them.
ATTN_ENVELOPE = {"int8": 0.12, "fp8": 0.30}
QMM_ENVELOPE = {"int8": 0.35, "fp8": 1.50}


def _seed(*parts) -> int:
    """Cell-id -> stable 31-bit seed (see test_attention_conformance)."""
    return zlib.crc32(":".join(map(str, parts)).encode()) & 0x7FFFFFFF


def _mk(key, shape, dtype="float32"):
    return jax.random.normal(key, shape, jnp.dtype(dtype))


def _close(got, want, scale=1):
    tol = scale * TOL
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=tol, rtol=tol)


def _envelope(got, want, fmt, table):
    err = float(np.max(np.abs(
        np.asarray(got, np.float32) - np.asarray(want, np.float32))))
    assert err <= table[fmt], (
        f"{fmt} max-abs error {err:.4f} exceeds the {table[fmt]} envelope")
    return err


def _quant_cache(x, fmt):
    """Quantize a (B, S, KV, Dh) fp32 cache per batch row — the same
    symmetric amax scaling `layers._quant_update` applies on cache write,
    with the (B,) fp32 scale the serving path threads as a cache leaf."""
    m = INT8_MAX if fmt == "int8" else FP8_MAX
    amax = jnp.maximum(jnp.max(jnp.abs(x), axis=(1, 2, 3)), 1e-6)
    s = (amax / m).astype(jnp.float32)
    y = x.astype(jnp.float32) / s.reshape(-1, 1, 1, 1)
    if fmt == "int8":
        q = jnp.clip(jnp.round(y), -INT8_MAX, INT8_MAX).astype(jnp.int8)
    else:
        q = jnp.clip(y, -FP8_MAX, FP8_MAX).astype(storage_dtype(fmt))
    return q, s


def _paged_layout(k, v, page, seed):
    """Shuffled-permutation page pools with a poisoned park page 0 (see
    test_attention_conformance._paged_layout) — here the pools inherit
    the QUANTIZED storage dtype, so the kernels' int8/fp8 page DMAs and
    in-VMEM dequant are what is under test."""
    b, s = k.shape[:2]
    assert s % page == 0
    n = s // page
    npages = 1 + b * n
    perm = np.random.default_rng(seed).permutation(np.arange(1, npages))
    bt = jnp.asarray(perm.reshape(b, n), jnp.int32)
    pool_shape = (npages, page) + k.shape[2:]
    pool_k = jnp.full(pool_shape, POISON, k.dtype)
    pool_v = jnp.full(pool_shape, POISON, v.dtype)
    kb = k.reshape(b * n, page, *k.shape[2:])
    vb = v.reshape(b * n, page, *v.shape[2:])
    pool_k = pool_k.at[bt.reshape(-1)].set(kb)
    pool_v = pool_v.at[bt.reshape(-1)].set(vb)
    return pool_k, pool_v, bt


STACK_LAYERS = 3
STACKED = ("stacked0", "stacked2")    # layouts: the pool at layer 0 / 2 of 3


def _stack(pool, layer):
    """(L, P, page, KV, Dh) stack holding the quantized `pool` at `layer`
    and poison codes elsewhere (see test_attention_conformance._stack)."""
    return jnp.stack([pool if i == layer else jnp.full_like(pool, POISON)
                      for i in range(STACK_LAYERS)])


def _assert_stacked_identical(op, layout, args):
    """The layer-stacked call (layer index trailing) == the per-layer call
    bit for bit, Pallas kernel (interpret) and reference adapter alike.
    `args`: q, pool_k, pool_v, pos, table, window, k_scale, v_scale."""
    layer = int(layout[len("stacked"):])
    q, pool_k, pool_v, *rest = args
    for impl in (_NATIVES_INTERPRET[op], _REFS[op]):
        want = impl(*args)
        got = impl(q, _stack(pool_k, layer), _stack(pool_v, layer), *rest,
                   jnp.asarray(layer, jnp.int32))
        assert np.array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# quant_matmul: ragged geometry x format, kernel == ref, ref ~ fp32
# ---------------------------------------------------------------------------

# (t, d, f) — token extents off the 8-wide tiles, rectangular weights
QMM_GEOMS = [
    (8, 32, 32),       # tile-exact
    (60, 64, 64),      # multi-tile with tail rows
    (7, 48, 32),       # sub-tile token count
    (16, 32, 64),      # wide output, the decode microbatch shape
]


def _qmm_args(geom, fmt):
    t, d, f = geom
    ks = jax.random.split(jax.random.PRNGKey(_seed("qmm", geom, fmt)), 2)
    x = _mk(ks[0], (t, d))
    w = _mk(ks[1], (d, f))
    qw, scale = quantize_per_channel(w, axis=-2, fmt=fmt)
    return x, w, qw, scale


@pytest.mark.parametrize("fmt", FORMATS)
@pytest.mark.parametrize("geom", QMM_GEOMS, ids=lambda g: "x".join(map(str, g)))
def test_quant_matmul_grid(geom, fmt):
    x, w, qw, scale = _qmm_args(geom, fmt)
    out = _NATIVES_INTERPRET["quant_matmul"](x, qw, scale)
    _close(out, quant_matmul_ref(x, qw, scale), scale=5)
    _envelope(out, x @ w, fmt, QMM_ENVELOPE)


@pytest.mark.parametrize("fmt", FORMATS)
@pytest.mark.parametrize("geom", QMM_GEOMS, ids=lambda g: "x".join(map(str, g)))
def test_quant_matmul_equals_dequantized_einsum(geom, fmt):
    """The kernel's fused dequant must equal materialize-then-matmul on
    the same codes — the storage-form weights are semantics-free layout."""
    x, _, qw, scale = _qmm_args(geom, fmt)
    out = _NATIVES_INTERPRET["quant_matmul"](x, qw, scale)
    dense = x @ dequantize(qw, scale, axis=-2, dtype=jnp.float32)
    _close(out, dense, scale=5)


@pytest.mark.parametrize("fmt", FORMATS)
@pytest.mark.parametrize("geom", QMM_GEOMS, ids=lambda g: "x".join(map(str, g)))
def test_quant_matmul_synth_roundtrip(geom, fmt):
    x, _, qw, scale = _qmm_args(geom, fmt)
    _roundtrip("quant_matmul", (x, qw, scale))


# ---------------------------------------------------------------------------
# decode_attention: geometry x layout x window x format
# ---------------------------------------------------------------------------

# (b, smax, h, kv, dh, pos) — reused from the attention grid: vector and
# scalar positions, GQA groups, first/last-slot edges
DECODE_GEOMS = [
    (2, 32, 2, 2, 8, (5, 17)),
    (1, 24, 2, 1, 8, 10),
    (3, 48, 4, 2, 16, (0, 47, 20)),
]

WINDOWS = ("win", "full")


def _decode_args(geom, fmt, tag="qdecode"):
    b, smax, h, kv, dh, pos = geom
    ks = jax.random.split(jax.random.PRNGKey(_seed(tag, geom, fmt)), 3)
    q = _mk(ks[0], (b, 1, h, dh))
    k = _mk(ks[1], (b, smax, kv, dh))
    v = _mk(ks[2], (b, smax, kv, dh))
    qk, k_scale = _quant_cache(k, fmt)
    qv, v_scale = _quant_cache(v, fmt)
    return q, k, v, qk, qv, k_scale, v_scale, jnp.asarray(pos, jnp.int32)


@pytest.mark.parametrize("fmt", FORMATS)
@pytest.mark.parametrize("wtag", WINDOWS)
@pytest.mark.parametrize("layout", ["contiguous", "paged", *STACKED])
@pytest.mark.parametrize("geom", DECODE_GEOMS, ids=lambda g: f"smax{g[1]}b{g[0]}")
def test_quant_decode_grid(geom, layout, wtag, fmt):
    q, k, v, qk, qv, ks_, vs_, pos = _decode_args(geom, fmt)
    smax = geom[1]
    w = jnp.asarray(8 if wtag == "win" else smax, jnp.int32)
    want = decode_attention_ref(q, k, v, pos, None, w)   # fp32 oracle
    if layout in STACKED:
        pool_k, pool_v, bt = _paged_layout(
            qk, qv, 8, _seed("qdecode", geom, fmt, "pool"))
        _assert_stacked_identical("decode_attention", layout,
                                  (q, pool_k, pool_v, pos, bt, w, ks_, vs_))
        return
    if layout == "paged":
        pool_k, pool_v, bt = _paged_layout(
            qk, qv, 8, _seed("qdecode", geom, fmt, "pool"))
        out = _NATIVES_INTERPRET["decode_attention"](
            q, pool_k, pool_v, pos, bt, w, ks_, vs_)
        qref = decode_attention_ref(q, pool_k, pool_v, pos, bt, w, ks_, vs_)
        full = _NATIVES_INTERPRET["decode_attention"](
            q, pool_k, pool_v, pos, bt, None, ks_, vs_)
    else:
        out = _NATIVES_INTERPRET["decode_attention"](
            q, qk, qv, pos, None, w, ks_, vs_)
        qref = decode_attention_ref(q, qk, qv, pos, None, w, ks_, vs_)
        full = _NATIVES_INTERPRET["decode_attention"](
            q, qk, qv, pos, None, None, ks_, vs_)
    _close(out, qref, scale=5)                  # kernel == quantized ref
    _envelope(out, want, fmt, ATTN_ENVELOPE)    # quantization error bound
    if wtag == "full":                          # W >= smax: same skip set,
        assert np.array_equal(np.asarray(out), np.asarray(full))


@pytest.mark.parametrize("fmt", FORMATS)
@pytest.mark.parametrize("geom", DECODE_GEOMS, ids=lambda g: f"smax{g[1]}b{g[0]}")
def test_quant_decode_paged_matches_contiguous(geom, fmt):
    """Identical codes through the paged DMA route and the contiguous
    route must agree to fp32 interpret tolerance — the block table only
    changes the gather, never the dequant math."""
    q, _, _, qk, qv, ks_, vs_, pos = _decode_args(geom, fmt, tag="qd-layout")
    cont = _NATIVES_INTERPRET["decode_attention"](
        q, qk, qv, pos, None, None, ks_, vs_)
    pool_k, pool_v, bt = _paged_layout(
        qk, qv, 8, _seed("qd-layout", geom, fmt, "pool"))
    paged = _NATIVES_INTERPRET["decode_attention"](
        q, pool_k, pool_v, pos, bt, None, ks_, vs_)
    _close(paged, cont, scale=5)


def test_quant_decode_park_page_is_inert():
    """Parked (poisoned) pages past the written prefix must stay
    unobservable in the quantized path too: POISON codes dequantize to a
    loud 50*scale, so any mask slip shows up immediately."""
    geom = (2, 32, 2, 2, 8, (5, 9))
    q, k, v, qk, qv, ks_, vs_, pos = _decode_args(geom, "int8", tag="qpark")
    pool_k, pool_v, bt = _paged_layout(qk, qv, 8, _seed("qpark", "pool"))
    bt = bt.at[:, 2:].set(0)                    # park everything past page 1
    out = _NATIVES_INTERPRET["decode_attention"](
        q, pool_k, pool_v, pos, bt, None, ks_, vs_)
    want = decode_attention_ref(q, k, v, pos)   # pos < 16: prefix only
    assert np.all(np.isfinite(np.asarray(out)))
    _envelope(out, want, "int8", ATTN_ENVELOPE)


@pytest.mark.parametrize("fmt", FORMATS)
def test_quant_decode_scalar_scale_broadcasts(fmt):
    """A () scale must mean the same thing as the equal-valued (B,)
    vector — both ride the kernel meta, one broadcast earlier."""
    geom = (2, 32, 2, 2, 8, (5, 17))
    q, _, _, qk, qv, _, _, pos = _decode_args(geom, fmt, tag="qscalar")
    s = jnp.asarray(0.03, jnp.float32)
    vec = jnp.full((2,), 0.03, jnp.float32)
    a = _NATIVES_INTERPRET["decode_attention"](
        q, qk, qv, pos, None, None, s, s)
    b = _NATIVES_INTERPRET["decode_attention"](
        q, qk, qv, pos, None, None, vec, vec)
    _close(a, b, scale=5)


# ---------------------------------------------------------------------------
# chunk_attention: geometry x layout x window x format
# ---------------------------------------------------------------------------

# (c, smax, h, kv, dh, pos) — chunk at the window start, mid-cache, zero
CHUNK_GEOMS = [
    (8, 32, 2, 2, 8, 8),
    (16, 48, 2, 1, 8, 16),
    (8, 24, 4, 2, 16, 0),
]


def _chunk_args(geom, fmt, tag="qchunk"):
    c, smax, h, kv, dh, pos = geom
    ks = jax.random.split(jax.random.PRNGKey(_seed(tag, geom, fmt)), 3)
    q = _mk(ks[0], (1, c, h, dh))
    k = _mk(ks[1], (1, smax, kv, dh))
    v = _mk(ks[2], (1, smax, kv, dh))
    qk, k_scale = _quant_cache(k, fmt)
    qv, v_scale = _quant_cache(v, fmt)
    return q, k, v, qk, qv, k_scale, v_scale, pos


@pytest.mark.parametrize("fmt", FORMATS)
@pytest.mark.parametrize("wtag", WINDOWS)
@pytest.mark.parametrize("layout", ["contiguous", "paged", *STACKED])
@pytest.mark.parametrize("geom", CHUNK_GEOMS, ids=lambda g: f"c{g[0]}pos{g[5]}")
def test_quant_chunk_grid(geom, layout, wtag, fmt):
    q, k, v, qk, qv, ks_, vs_, pos = _chunk_args(geom, fmt)
    c, smax = geom[0], geom[1]
    w = jnp.asarray(c if wtag == "win" else smax, jnp.int32)
    want = chunk_attention_ref(q, k, v, pos, None, w)    # fp32 oracle
    if layout in STACKED:
        pool_k, pool_v, bt = _paged_layout(
            qk, qv, c, _seed("qchunk", geom, fmt, "pool"))
        _assert_stacked_identical("chunk_attention", layout,
                                  (q, pool_k, pool_v, pos, bt, w, ks_, vs_))
        return
    if layout == "paged":
        page = c                                # serving invariant: page == C
        pool_k, pool_v, bt = _paged_layout(
            qk, qv, page, _seed("qchunk", geom, fmt, "pool"))
        out = _NATIVES_INTERPRET["chunk_attention"](
            q, pool_k, pool_v, pos, bt, w, ks_, vs_)
        qref = chunk_attention_ref(q, pool_k, pool_v, pos, bt, w, ks_, vs_)
        full = _NATIVES_INTERPRET["chunk_attention"](
            q, pool_k, pool_v, pos, bt, None, ks_, vs_)
    else:
        out = _NATIVES_INTERPRET["chunk_attention"](
            q, qk, qv, pos, None, w, ks_, vs_)
        qref = chunk_attention_ref(q, qk, qv, pos, None, w, ks_, vs_)
        full = _NATIVES_INTERPRET["chunk_attention"](
            q, qk, qv, pos, None, None, ks_, vs_)
    _close(out, qref, scale=5)
    _envelope(out, want, fmt, ATTN_ENVELOPE)
    if wtag == "full":
        assert np.array_equal(np.asarray(out), np.asarray(full))


# ---------------------------------------------------------------------------
# tuner synthesizer round-trip: quantized composite buckets rebuildable
# ---------------------------------------------------------------------------

def _no_scalars(shapes: str) -> str:
    return ",".join(p for p in shapes.split(",")
                    if p and p != "scalar" and "x" in p)


def _roundtrip(op, args, expect_feasible=True):
    t = tuners()[op]
    shapes, dtype = bucket_shapes(args)
    # composite buckets carry the STORAGE dtype suffix, not the format tag
    storage_names = {str(jnp.dtype(storage_dtype(f))) for f in FORMATS}
    assert "+" not in str(dtype) or str(dtype).split("+")[1] in storage_names
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


@pytest.mark.parametrize("fmt", FORMATS)
@pytest.mark.parametrize("layout", ["contiguous", "paged", "stacked"])
@pytest.mark.parametrize("geom", DECODE_GEOMS, ids=lambda g: f"smax{g[1]}b{g[0]}")
def test_quant_decode_synth_roundtrip(geom, layout, fmt):
    q, _, _, qk, qv, ks_, vs_, pos = _decode_args(geom, fmt, tag="qd-rt")
    if layout == "stacked":
        s = -(-qk.shape[1] // 16) * 16
        pad = ((0, 0), (0, s - qk.shape[1]), (0, 0), (0, 0))
        pool_k, pool_v, bt = _paged_layout(
            jnp.pad(qk, pad), jnp.pad(qv, pad), 16,
            _seed("qd-rt", geom, fmt, "pool"))
        _roundtrip("decode_attention",
                   (q, _stack(pool_k, 2), _stack(pool_v, 2), pos, bt, None,
                    ks_, vs_, jnp.asarray(2, jnp.int32)))
    elif layout == "paged":
        page = 16                               # >= the space's smallest bk
        s = -(-qk.shape[1] // page) * page
        pad = ((0, 0), (0, s - qk.shape[1]), (0, 0), (0, 0))
        pool_k, pool_v, bt = _paged_layout(
            jnp.pad(qk, pad), jnp.pad(qv, pad), page,
            _seed("qd-rt", geom, fmt, "pool"))
        _roundtrip("decode_attention",
                   (q, pool_k, pool_v, pos, bt, None, ks_, vs_))
    else:
        _roundtrip("decode_attention",
                   (q, qk, qv, pos, None, None, ks_, vs_))


@pytest.mark.parametrize("fmt", FORMATS)
@pytest.mark.parametrize("layout", ["contiguous", "paged"])
@pytest.mark.parametrize("geom", CHUNK_GEOMS, ids=lambda g: f"c{g[0]}pos{g[5]}")
def test_quant_chunk_synth_roundtrip(geom, layout, fmt):
    q, _, _, qk, qv, ks_, vs_, pos = _chunk_args(geom, fmt, tag="qc-rt")
    w = jnp.asarray(16, jnp.int32)
    ok = geom[0] >= 16                          # smallest chunk block_q is 16
    if layout == "paged":
        page = max(geom[0], 16)
        s = -(-qk.shape[1] // page) * page
        pad = ((0, 0), (0, s - qk.shape[1]), (0, 0), (0, 0))
        pool_k, pool_v, bt = _paged_layout(
            jnp.pad(qk, pad), jnp.pad(qv, pad), page,
            _seed("qc-rt", geom, fmt, "pool"))
        _roundtrip("chunk_attention",
                   (q, pool_k, pool_v, pos, bt, w, ks_, vs_),
                   expect_feasible=ok)
    else:
        _roundtrip("chunk_attention", (q, qk, qv, pos, None, w, ks_, vs_),
                   expect_feasible=ok)
