"""Op declarations + registration: the site's "library inventory".

Declares the ABI for every swappable logical op, registers the portable
reference implementation (what the Bundle ships) and the Pallas TPU
implementation (what the site bind-mounts in, gated on the
``pallas_kernels`` platform feature — absent on CPU hosts, so deployment
there keeps the references, exactly like Shifter on a system without the
vendor stack).
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from repro.core.abi import AbiString
from repro.core.registry import ImplKind, OpImpl, OpRegistry, global_registry
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_attention_ref import (
    attention_ref,
    chunk_attention_ref,
    decode_attention_ref,
    windowed_attention_ref,
)
from repro.kernels.moe_gmm import moe_gmm
from repro.kernels.moe_gmm_ref import moe_gmm_ref
from repro.kernels.quant_matmul import quant_matmul
from repro.kernels.quant_matmul_ref import quant_matmul_ref
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.rmsnorm_ref import rmsnorm_ref
from repro.kernels.ssd_scan import ssd_scan
from repro.kernels.ssd_scan_ref import ssd_scan_ref
from repro.tuning import OpTuner

__all__ = ["ABIS", "OP_NAMES", "register_all", "default_binding", "tuners"]

# Canonical signatures: the structural part of the ABI string.  Changing a
# signature (or the semantic major version) makes old native kernels
# un-swappable — the registry will refuse, like Shifter on a libtool
# mismatch.
_SIGS = {
    "rmsnorm": {
        "args": ["x:[*,d]", "weight:[d]"],
        "kwargs": ["eps:float"],
        "semantics": "y = x/rms(x)*w, fp32 accumulation",
    },
    "attention": {
        "args": ["q:[b,sq,h,dh]", "k:[b,sk,kv,dh]", "v:[b,sk,kv,dh]"],
        "kwargs": ["causal:bool", "scale:float?"],
        "semantics": "softmax(qk^T*scale+causal_mask)v, GQA h%kv==0, fp32 softmax",
    },
    "decode_attention": {
        "args": ["q:[b,1,h,dh]", "k_cache:[b,smax,kv,dh]", "v_cache:[b,smax,kv,dh]", "pos:i32"],
        "kwargs": ["scale:float?"],
        "semantics": "single-token attention, cache slots > pos masked",
    },
    "windowed_attention": {
        "args": ["q:[b,sq,h,dh]", "k:[b,sk,kv,dh]", "v:[b,sk,kv,dh]", "window:i32"],
        "kwargs": ["scale:float?"],
        "semantics": ("sliding-window causal: query i attends keys in "
                      "(i-window, i], GQA h%kv==0, fp32 softmax"),
    },
    "chunk_attention": {
        "args": ["q:[b,c,h,dh]", "k_cache:[b,smax,kv,dh]", "v_cache:[b,smax,kv,dh]", "pos:i32"],
        "kwargs": ["scale:float?"],
        "semantics": "chunked prefill: query i attends cache keys <= pos+i",
    },
    "ssd_scan": {
        "args": ["x:[b,s,h,p]", "dt:[b,s,h]", "A:[h]", "B:[b,s,g,n]", "C:[b,s,g,n]"],
        "kwargs": ["chunk:int"],
        "semantics": "mamba2 SSD; returns (y, final_state[b,h,n,p] fp32)",
    },
    "moe_gmm": {
        "args": ["x:[t,d] sorted-by-expert", "w:[e,d,f]", "group_sizes:[e]"],
        "kwargs": [],
        # NB: this text feeds the signature digest, which must stay stable
        # across compatible revisions (a digest change strands every bundle
        # persisted under the old string) — behavioral refinements are
        # recorded as _ABI_MINORS bumps, not edits here.  Since minor 2 the
        # reference is dropless at decode scale (<=1k rows); above that it
        # remains the capacity-truncated baseline.
        "semantics": ("per-group matmul, groups partition rows of x; "
                      "capacity-truncated baseline, dropless native"),
    },
    "quant_matmul": {
        "args": ["x:[t,d]", "qw:[d,f] int8|fp8", "scale:[f] f32"],
        "kwargs": [],
        "semantics": ("y = x @ (qw * scale[None,:]) per output channel, "
                      "fp32 accumulation, output in x's dtype"),
    },
}

# Minor revisions: compatible extensions of a kernel (libtool "revision").
# A bump here leaves old bundles deployable (provider minor >= required
# minor) but expires the op's tuning-cache entries — they were measured
# on the previous kernel revision (see tuning/expiry.py).
#   moe_gmm 1: grew the k-loop contraction (block_k knob, D > 8k feasible)
#   moe_gmm 2: reference is dropless below _EXACT_ROWS_MAX rows (the
#              geometry-dependent capacity drop broke prefill/decode
#              consistency — docs/kernels.md)
#   decode_attention 1: pos may be (B,) as well as scalar — continuous
#              batching decodes every slot at its own position in one
#              call (the kernel grew per-batch kv_len rows in SMEM)
#   decode_attention 2 / chunk_attention 1: optional trailing
#              block_tables arg — k/v may be page pools (P, page, KV, Dh)
#              gathered through a per-batch block table; the kernel grew
#              per-batch block-index rows in the same SMEM meta
#              (docs/kernels.md "block-gather meta ABI")
#   decode_attention 3 / chunk_attention 2: optional trailing window arg
#              (traced () or (B,) i32) — sliding-window attention: keys
#              at logical positions <= pos - window (decode) /
#              <= pos + i - window (chunk) are masked, and whole
#              out-of-window k-blocks are skipped; the kernel grew a
#              per-batch window-start row in the same SMEM meta
#              (docs/kernels.md "window meta ABI")
#   decode_attention 4 / chunk_attention 3: optional trailing
#              k_scale/v_scale args (traced () or (B,) f32) — k/v caches
#              may be int8/fp8 quantized pools, dequantized in-kernel
#              after the VMEM upcast; the scales are a (2, B) fp32
#              SMEM operand beside the int32 meta
#              (docs/quantization.md "scale meta ABI")
#   decode_attention 5 / chunk_attention 4: optional trailing layer arg
#              (traced () i32) — paged k/v may be the layer-stacked pools
#              (L, P, page, KV, Dh) a layer scan carries; the kernel grew
#              a layer row in the same SMEM meta and reads the layer's
#              pages from the stack, so no per-layer pool is sliced out
#              (docs/kernels.md "Layer-stacked pools: the layer row")
_ABI_MINORS = {"moe_gmm": 2, "decode_attention": 5, "chunk_attention": 4}

ABIS: dict[str, AbiString] = {
    name: AbiString.make(name, sig, major=1, minor=_ABI_MINORS.get(name, 0))
    for name, sig in _SIGS.items()
}
OP_NAMES: tuple[str, ...] = tuple(sorted(ABIS))


# -- native call-convention adapters ----------------------------------------
def _native_attention(q, k, v, *, causal=True, scale=None, config=None,
                      interpret=False):
    return flash_attention(q, k, v, causal=causal, scale=scale, config=config,
                           interpret=interpret)


def _native_windowed_attention(q, k, v, window, *, scale=None, config=None,
                               interpret=False):
    # sliding-window causal prefill: the full-attention geometry plus a
    # traced window width — the wrapper adds the window-start meta row
    return flash_attention(q, k, v, window=window, causal=True, scale=scale,
                           config=config, interpret=interpret)


def _ref_windowed_attention(q, k, v, window, *, scale=None):
    return windowed_attention_ref(q, k, v, window, scale=scale)


def _native_decode_attention(q, k_cache, v_cache, pos, block_tables=None,
                             window=None, k_scale=None, v_scale=None,
                             layer=None, *, scale=None, config=None,
                             interpret=False):
    # decode = flash with Sq=1 over the written prefix of the cache; with
    # block_tables the caches are page pools and the kernel's index maps
    # gather pages (page size = the pool's page dim); with window only
    # the trailing `window` slots are attended (out-of-window pages may
    # already be parked); with k_scale/v_scale the pools are int8/fp8
    # and dequantized in-kernel after the VMEM upcast; with layer the
    # pools are layer-stacked and the kernel reads that layer's pages
    page = k_cache.shape[-3] if block_tables is not None else None
    return flash_attention(
        q, k_cache, v_cache, kv_len=pos + 1, causal=False, scale=scale,
        window=window, k_scale=k_scale, v_scale=v_scale, config=config,
        interpret=interpret, block_tables=block_tables, page_size=page,
        layer=layer,
    )


def _ref_decode_attention(q, k_cache, v_cache, pos, block_tables=None,
                          window=None, k_scale=None, v_scale=None,
                          layer=None, *, scale=None):
    if layer is not None:
        k_cache, v_cache = k_cache[layer], v_cache[layer]
    return decode_attention_ref(q, k_cache, v_cache, pos, block_tables,
                                window, k_scale, v_scale, scale=scale)


def _native_chunk_attention(q, k_cache, v_cache, pos, block_tables=None,
                            window=None, k_scale=None, v_scale=None,
                            layer=None, *, scale=None, config=None,
                            interpret=False):
    # chunked prefill = flash with the causal diagonal re-anchored at pos:
    # query i (global position pos+i) sees cache keys <= pos+i, and the
    # kv_len mask hides slots past the chunk's own freshly written tail.
    page = k_cache.shape[-3] if block_tables is not None else None
    return flash_attention(
        q, k_cache, v_cache, kv_len=pos + q.shape[1], q_start=pos,
        causal=True, scale=scale, window=window, k_scale=k_scale,
        v_scale=v_scale, config=config, interpret=interpret,
        block_tables=block_tables, page_size=page, layer=layer,
    )


def _ref_chunk_attention(q, k_cache, v_cache, pos, block_tables=None,
                         window=None, k_scale=None, v_scale=None,
                         layer=None, *, scale=None):
    if layer is not None:
        k_cache, v_cache = k_cache[layer], v_cache[layer]
    return chunk_attention_ref(q, k_cache, v_cache, pos, block_tables,
                               window, k_scale, v_scale, scale=scale)


def _ref_attention(q, k, v, *, causal=True, scale=None):
    # chunked (flash-in-jnp) automatically above 2k keys: same math, O(S)
    # live memory — the portable reference stays deployable at 32k.
    chunk = 1024 if k.shape[1] > 2048 else None
    return attention_ref(q, k, v, causal=causal, scale=scale, chunk_kv=chunk)


_REFS = {
    "rmsnorm": rmsnorm_ref,
    "attention": _ref_attention,
    "windowed_attention": _ref_windowed_attention,
    "decode_attention": _ref_decode_attention,
    "chunk_attention": _ref_chunk_attention,
    "ssd_scan": ssd_scan_ref,
    "moe_gmm": moe_gmm_ref,
    "quant_matmul": quant_matmul_ref,
}

_NATIVES = {
    "rmsnorm": functools.partial(rmsnorm, interpret=False),
    "attention": _native_attention,
    "windowed_attention": _native_windowed_attention,
    "decode_attention": _native_decode_attention,
    "chunk_attention": _native_chunk_attention,
    "ssd_scan": functools.partial(ssd_scan, interpret=False),
    "moe_gmm": functools.partial(moe_gmm, interpret=False),
    "quant_matmul": functools.partial(quant_matmul, interpret=False),
}

# interpret-mode variants: the Pallas kernel body executed by the HLO
# interpreter — numerically the real kernel, bindable on CPU simulation
# hosts (platform feature "pallas_interpret").
_NATIVES_INTERPRET = {
    "rmsnorm": functools.partial(rmsnorm, interpret=True),
    "attention": functools.partial(_native_attention, interpret=True),
    "windowed_attention": functools.partial(_native_windowed_attention,
                                            interpret=True),
    "decode_attention": functools.partial(_native_decode_attention, interpret=True),
    "chunk_attention": functools.partial(_native_chunk_attention, interpret=True),
    "ssd_scan": functools.partial(ssd_scan, interpret=True),
    "moe_gmm": functools.partial(moe_gmm, interpret=True),
    "quant_matmul": functools.partial(quant_matmul, interpret=True),
}

# -- autotuner hooks ---------------------------------------------------------
# Per-op config spaces + canonical workloads the TuningContext measures at
# bind time.  Example shapes are platform-scaled: small on cpu-host
# hardware (interpret mode runs the kernel body through the HLO
# interpreter — correctness-exact, orders of magnitude slower), full-size
# on real accelerators.  Feasibility pruning rejects candidates whose
# VMEM working set overflows or whose blocks don't fit the workload
# before anything is compiled.

_VMEM_BUDGET = 12 * 2**20   # bytes/core usable for kernel tiles (16M - headroom)


def _is_cpu(platform) -> bool:
    return platform.hardware.name == "cpu-host"


# Abstract workloads (ShapeDtypeStructs) are the single source of the
# example geometry: cache keys are derived from them without allocating
# anything; the _example_* materializers fill them in only when a search
# actually runs.

def _spec_rmsnorm(platform):
    rows, d = (128, 256) if _is_cpu(platform) else (8192, 4096)
    return (jax.ShapeDtypeStruct((rows, d), jnp.float32),
            jax.ShapeDtypeStruct((d,), jnp.float32))


def _example_rmsnorm(platform):
    sx, sw = _spec_rmsnorm(platform)
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    return (jax.random.normal(k1, sx.shape, sx.dtype),
            jax.random.normal(k2, sw.shape, sw.dtype))


def _feasible_rmsnorm(cfg, platform, args):
    # the kernel flattens leading dims to rows and clamps block_rows, so
    # profiled rank-3 activations (B, S, D) are tunable too; keep at least
    # the smallest space value alive for sub-tile row counts
    shape = args[0].shape
    rows, d = math.prod(shape[:-1]), shape[-1]
    br = cfg["block_rows"]
    return (br <= max(rows, 8)
            and (3 * min(br, rows) * d + d) * 4 <= _VMEM_BUDGET)


def _spec_attention(platform):
    b, s, h, kv, dh = (1, 64, 2, 2, 64) if _is_cpu(platform) else (4, 2048, 16, 4, 128)
    return (jax.ShapeDtypeStruct((b, s, h, dh), jnp.float32),
            jax.ShapeDtypeStruct((b, s, kv, dh), jnp.float32),
            jax.ShapeDtypeStruct((b, s, kv, dh), jnp.float32))


def _example_attention(platform):
    specs = _spec_attention(platform)
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    return tuple(jax.random.normal(k, s.shape, s.dtype)
                 for k, s in zip(ks, specs))


def _feasible_attention(cfg, platform, args):
    sq, dh = args[0].shape[1], args[0].shape[3]
    sk = args[1].shape[1]
    bq, bk = cfg["block_q"], cfg["block_k"]
    vmem = (2 * bq * dh + 2 * bk * dh + bq * bk + 2 * bq) * 4
    return bq <= sq and bk <= sk and vmem <= _VMEM_BUDGET


def _spec_windowed(platform):
    # the full-attention geometry plus a traced window width; the canonical
    # window is Sk // 4 — small enough that the skip heuristic matters,
    # large enough to span several k-blocks
    q, k, v = _spec_attention(platform)
    return (q, k, v, jax.ShapeDtypeStruct((), jnp.int32))


def _example_windowed(platform):
    q, k, v, _ = _spec_windowed(platform)
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    w = jnp.asarray(max(1, k.shape[1] // 4), jnp.int32)
    return (jax.random.normal(ks[0], q.shape, q.dtype),
            jax.random.normal(ks[1], k.shape, k.dtype),
            jax.random.normal(ks[2], v.shape, v.dtype),
            w)


def _feasible_windowed(cfg, platform, args):
    # identical working set to full attention: the window narrows which
    # k-blocks run, not their shapes
    return _feasible_attention(cfg, platform, args)


def _spec_decode(platform):
    b, smax, h, kv, dh = (1, 64, 2, 2, 64) if _is_cpu(platform) else (8, 4096, 16, 4, 128)
    return (jax.ShapeDtypeStruct((b, 1, h, dh), jnp.float32),
            jax.ShapeDtypeStruct((b, smax, kv, dh), jnp.float32),
            jax.ShapeDtypeStruct((b, smax, kv, dh), jnp.float32),
            smax // 2)


def _example_decode(platform):
    sq, sk, sv, pos = _spec_decode(platform)
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    return (jax.random.normal(ks[0], sq.shape, sq.dtype),
            jax.random.normal(ks[1], sk.shape, sk.dtype),
            jax.random.normal(ks[2], sv.shape, sv.dtype),
            pos)


def _paged_geom(args):
    """(page, logical_smax) when args carry a block table, else None.

    The bucket validator rebuilds scalar parts as python ints, so an
    array-ness check (has .shape, rank 2) is the paged discriminator —
    a contiguous call's 5th arg is the scalar pos / absent."""
    if len(args) >= 5:
        shp = getattr(args[4], "shape", None)
        if shp is not None and len(shp) == 2:
            page = args[1].shape[-3]        # (P | L, P), page, KV, Dh
            return page, shp[1] * page
    return None


def _feasible_decode(cfg, platform, args):
    smax, dh = args[1].shape[1], args[1].shape[-1]
    bk = cfg["block_k"]
    paged = _paged_geom(args)
    if paged is not None:
        page, smax = paged
        # block_k > page would be gcd-clamped to the page size inside the
        # kernel — reject so distinct configs never alias one measurement
        if bk > page:
            return False
    return bk <= smax and (2 * dh + 2 * bk * dh + bk + 2) * 4 <= _VMEM_BUDGET


def _spec_chunk(platform):
    # C-token chunk mid-way through a max_len cache — the serving
    # prefill geometry (chunk C minor to batch, cache at full Smax)
    b, c, smax, h, kv, dh = (1, 16, 64, 2, 2, 64) if _is_cpu(platform) \
        else (1, 256, 4096, 16, 4, 128)
    return (jax.ShapeDtypeStruct((b, c, h, dh), jnp.float32),
            jax.ShapeDtypeStruct((b, smax, kv, dh), jnp.float32),
            jax.ShapeDtypeStruct((b, smax, kv, dh), jnp.float32),
            smax // 2)


def _example_chunk(platform):
    sq, sk, sv, pos = _spec_chunk(platform)
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    return (jax.random.normal(ks[0], sq.shape, sq.dtype),
            jax.random.normal(ks[1], sk.shape, sk.dtype),
            jax.random.normal(ks[2], sv.shape, sv.dtype),
            pos)


def _feasible_chunk(cfg, platform, args):
    c, dh = args[0].shape[1], args[0].shape[3]
    smax = args[1].shape[1]
    bq, bk = cfg["block_q"], cfg["block_k"]
    paged = _paged_geom(args)
    if paged is not None:
        page, smax = paged
        if bk > page:
            return False
    vmem = (2 * bq * dh + 2 * bk * dh + bq * bk + 2 * bq) * 4
    return bq <= c and bk <= smax and vmem <= _VMEM_BUDGET


def _spec_ssd(platform):
    b, s, h, p, g, n = (1, 64, 2, 16, 1, 16) if _is_cpu(platform) else (2, 2048, 8, 64, 1, 64)
    return (jax.ShapeDtypeStruct((b, s, h, p), jnp.float32),
            jax.ShapeDtypeStruct((b, s, h), jnp.float32),
            jax.ShapeDtypeStruct((h,), jnp.float32),
            jax.ShapeDtypeStruct((b, s, g, n), jnp.float32),
            jax.ShapeDtypeStruct((b, s, g, n), jnp.float32))


def _example_ssd(platform):
    sx, sdt, sa, sb, sc = _spec_ssd(platform)
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    return (jax.random.normal(ks[0], sx.shape, sx.dtype) * 0.3,
            jax.nn.softplus(jax.random.normal(ks[1], sdt.shape, sdt.dtype)),
            -jnp.exp(jax.random.normal(ks[2], sa.shape, sa.dtype) * 0.3),
            jax.random.normal(ks[3], sb.shape, sb.dtype) * 0.3,
            jax.random.normal(ks[4], sc.shape, sc.dtype) * 0.3)


def _feasible_ssd(cfg, platform, args):
    s, p = args[0].shape[1], args[0].shape[3]
    n = args[3].shape[3]
    q = cfg["chunk"]
    vmem = (q * p + 2 * q * n + q * q + n * p) * 4
    return q <= s and s % q == 0 and vmem <= _VMEM_BUDGET


def _spec_moe(platform):
    t, d, e, f = (128, 64, 4, 64) if _is_cpu(platform) else (8192, 2048, 8, 2048)
    return (jax.ShapeDtypeStruct((t, d), jnp.float32),
            jax.ShapeDtypeStruct((e, d, f), jnp.float32),
            jax.ShapeDtypeStruct((e,), jnp.int32))


def _example_moe(platform):
    sx, sw, sg = _spec_moe(platform)
    ks = jax.random.split(jax.random.PRNGKey(4), 2)
    t, e = sx.shape[0], sg.shape[0]
    return (jax.random.normal(ks[0], sx.shape, sx.dtype),
            jax.random.normal(ks[1], sw.shape, sw.dtype),
            jnp.full((e,), t // e, sg.dtype))


def _feasible_moe(cfg, platform, args):
    t, d = args[0].shape
    f = args[1].shape[2]
    bm, bn, bk = cfg["block_m"], cfg["block_n"], cfg["block_k"]
    # the kernel degrades block_k to gcd(block_k, d), so narrow experts
    # (d below the space minimum of 64) stay searchable — keep at least
    # the smallest bk value alive and budget VMEM at the effective size
    bk_eff = math.gcd(min(bk, d), d)
    # x tile + w tile + fp32 accumulator scratch + out tile; D itself no
    # longer appears — the k-loop makes VMEM independent of expert width.
    # bm mirrors the kernel's clamp to max(t, 8): tiny-token geometries
    # keep the smallest tile searchable instead of pruning everything
    vmem = (bm * bk_eff + bk_eff * bn + 2 * bm * bn) * 4
    return (bm <= max(t, 8) and bn <= f and bk <= max(d, 64)
            and vmem <= _VMEM_BUDGET)


def _spec_quant_matmul(platform):
    # the serving-matmul geometry: a decode/chunk activation against a
    # per-channel int8 weight (fp8 buckets reuse the same tuned entries
    # modulo the dtype suffix on the bucket key)
    t, d, f = (64, 64, 64) if _is_cpu(platform) else (256, 4096, 4096)
    return (jax.ShapeDtypeStruct((t, d), jnp.float32),
            jax.ShapeDtypeStruct((d, f), jnp.int8),
            jax.ShapeDtypeStruct((f,), jnp.float32))


def _example_quant_matmul(platform):
    sx, sw, ss = _spec_quant_matmul(platform)
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    return (jax.random.normal(ks[0], sx.shape, sx.dtype),
            jax.random.randint(ks[1], sw.shape, -127, 128,
                               jnp.int32).astype(sw.dtype),
            jax.random.uniform(ks[2], ss.shape, ss.dtype, 0.001, 0.02))


def _feasible_quant_matmul(cfg, platform, args):
    t, d = args[0].shape
    f = args[1].shape[1]
    bm, bn = cfg["block_m"], cfg["block_n"]
    qbytes = jnp.dtype(args[1].dtype).itemsize
    # fp32 x tile + 1-byte weight tile + fp32 scale slice + fp32 out tile;
    # the full D contraction stays resident like rmsnorm's row
    vmem = bm * d * 4 + d * bn * qbytes + bn * 4 + bm * bn * 4
    return bm <= max(t, 8) and bn <= f and vmem <= _VMEM_BUDGET


_TUNERS: dict[str, OpTuner] = {
    "rmsnorm": OpTuner(
        op="rmsnorm",
        space={"block_rows": (8, 16, 32, 64, 128, 256, 512)},
        example_args=_example_rmsnorm, feasible=_feasible_rmsnorm,
        example_specs=_spec_rmsnorm,
    ),
    "attention": OpTuner(
        op="attention",
        space={"block_q": (16, 32, 64, 128, 256),
               "block_k": (16, 32, 64, 128, 256)},
        example_args=_example_attention, feasible=_feasible_attention,
        example_specs=_spec_attention,
    ),
    "windowed_attention": OpTuner(
        op="windowed_attention",
        # same space as attention, but the sweet spot differs: block_k
        # larger than the window wastes the skip, so the tuner usually
        # lands on smaller k-tiles than full attention does
        space={"block_q": (16, 32, 64, 128, 256),
               "block_k": (16, 32, 64, 128, 256)},
        example_args=_example_windowed, feasible=_feasible_windowed,
        example_specs=_spec_windowed,
    ),
    "decode_attention": OpTuner(
        op="decode_attention",
        space={"block_k": (16, 32, 64, 128, 256, 512)},
        example_args=_example_decode, feasible=_feasible_decode,
        example_specs=_spec_decode,
    ),
    "chunk_attention": OpTuner(
        op="chunk_attention",
        space={"block_q": (16, 32, 64, 128, 256),
               "block_k": (16, 32, 64, 128, 256)},
        example_args=_example_chunk, feasible=_feasible_chunk,
        example_specs=_spec_chunk,
    ),
    "ssd_scan": OpTuner(
        op="ssd_scan",
        space={"chunk": (8, 16, 32, 64, 128, 256)},
        example_args=_example_ssd, feasible=_feasible_ssd,
        example_specs=_spec_ssd,
    ),
    "moe_gmm": OpTuner(
        op="moe_gmm",
        space={"block_m": (8, 16, 32, 64, 128, 256),
               "block_n": (8, 16, 32, 64, 128, 256),
               "block_k": (64, 128, 256, 512, 1024, 2048)},
        example_args=_example_moe, feasible=_feasible_moe,
        example_specs=_spec_moe,
    ),
    "quant_matmul": OpTuner(
        op="quant_matmul",
        space={"block_m": (8, 16, 32, 64, 128, 256),
               "block_n": (8, 16, 32, 64, 128, 256)},
        example_args=_example_quant_matmul, feasible=_feasible_quant_matmul,
        example_specs=_spec_quant_matmul,
    ),
}


# -- profile-geometry synthesizers -------------------------------------------
# repro.tuning.warm (and a profile-aware TuningContext) replays *recorded*
# shape buckets, not the canonical examples above.  Each synthesizer turns
# one recorded (shapes, dtype) bucket back into concrete workload args; a
# bucket whose structure does not match the op's signature returns None
# and the caller skips it (a foreign or corrupted profile entry must not
# abort warming).

def _parse_bucket(shapes: str) -> list[tuple[int, ...]] | None:
    try:
        return [
            () if part == "scalar" else tuple(int(n) for n in part.split("x"))
            for part in shapes.split(",") if part
        ]
    except ValueError:
        return None


def _normal(key, shape, dtype):
    dt = jnp.dtype(dtype)
    if dt == jnp.int8:
        # quantized code points span the symmetric clip range
        return jax.random.randint(key, shape, -127, 128, jnp.int32).astype(dt)
    if jnp.issubdtype(dt, jnp.integer):
        return jax.random.randint(key, shape, 0, 8, dt)
    if dt.itemsize == 1:
        # fp8 storage: sample in fp32, snap to the fp8 grid
        return jax.random.normal(key, shape).astype(dt)
    return jax.random.normal(key, shape, dt)


def _split_dtype(dtype: str) -> tuple[str, str | None]:
    """Split a composite bucket dtype "float32+int8" into (base, quant).

    The "+<storage dtype>" suffix is how quantized-KV calls bucket
    separately from full-precision ones (repro.tuning.bucket_shapes);
    plain buckets return (dtype, None)."""
    base, _, quant = str(dtype).partition("+")
    return base, (quant or None)


def _synth_rmsnorm(platform, shapes, dtype):
    parts = _parse_bucket(shapes)
    if not parts or len(parts) != 2 or len(parts[0]) < 1 or len(parts[1]) != 1:
        return None
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    return (_normal(k1, parts[0], dtype), _normal(k2, parts[1], dtype))


def _synth_attention(platform, shapes, dtype):
    parts = _parse_bucket(shapes)
    if not parts or len(parts) != 3 or any(len(p) != 4 for p in parts):
        return None
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    return tuple(_normal(k, p, dtype) for k, p in zip(ks, parts))


def _attn_cache_parts(shapes, quantized=False):
    """Normalize a decode/chunk attention bucket to its array parts.

    Returns ``(parts, windowed)`` where parts is [q, k_cache, v_cache]
    (contiguous) or [q, pool_k, pool_v, block_table] (paged; the pools
    are 5-d when layer-stacked).  pos
    carries no geometry — recorded as a "scalar" part (traced 0-d), a
    1-d (B,) vector (continuous batching), or absent (python int) —
    drop it whichever way it appears.  The block table is always 2-d, so
    rank disambiguates; a trailing rank-0 part *after* pos/table is the
    traced sliding-window width (ABI decode/1:3, chunk/1:2) — this is
    how "window rides the bucket key": windowed calls bucket separately
    from full-attention calls and warm to their own tuned entries.

    ``quantized`` (the caller reads it off the bucket dtype's "+int8"/
    "+float8*" suffix — the authoritative signal, since a scale part is
    shaped exactly like a traced pos) strips the trailing k/v dequant
    scale pair (ABI decode/1:4, chunk/1:3) before the tail parse.  A
    layer-stacked pool (5-d k/v) always comes with the traced layer
    index (ABI decode/1:5, chunk/1:4) as the very last part."""
    parts = _parse_bucket(shapes)
    if (not parts or len(parts) < 3 or len(parts[0]) != 4
            or len(parts[1]) not in (4, 5) or len(parts[2]) != len(parts[1])):
        return None
    stacked = len(parts[1]) == 5
    tail = parts[3:]
    if stacked:
        if not tail or tail[-1] != ():
            return None                  # stacked pools without a layer
        tail = tail[:-1]
    if quantized:
        if len(tail) < 2 or any(len(p) > 1 for p in tail[-2:]):
            return None                  # scale pair missing/misshapen
        tail = tail[:-2]
    if tail and len(tail[0]) <= 1:       # traced pos: () or (B,)
        tail = tail[1:]
    table = None
    if tail and len(tail[0]) == 2:       # paged block table
        table = tail[0]
        tail = tail[1:]
    windowed = bool(tail) and len(tail[0]) <= 1
    if windowed:
        tail = tail[1:]
    if tail or (stacked and table is None):   # unrecognized residue
        return None
    return parts[:3] + ([table] if table is not None else []), windowed


def _synth_window(logical: int):
    """Representative traced window for a resynthesized windowed bucket:
    a quarter of the logical extent, so the measurement exercises the
    out-of-window block skip (the value itself never reaches the bucket
    key — only its 0-d "scalar" shape does)."""
    return jnp.asarray(max(1, logical // 4), jnp.int32)


def _synth_tail(parts, windowed, quantized):
    """Optional trailing (window, k_scale, v_scale, layer) args for a
    resynthesized attention bucket, in adapter positional order.  The
    scale values are representative dequant magnitudes — like the window
    width they never reach the bucket key, only their 0-d shapes do; a
    layer-stacked pool reads its last layer."""
    tail = ()
    logical = (parts[3][1] * parts[1][-3]) if len(parts) == 4 else parts[1][1]
    stacked = len(parts[1]) == 5
    if windowed:
        tail += (_synth_window(logical),)
    if quantized:
        if not windowed:
            tail += (None,)              # hold the window slot
        sc = jnp.asarray(0.02, jnp.float32)
        tail += (sc, sc)
    if stacked:
        tail += (None,) * (3 - len(tail))   # hold the window/scale slots
        tail += (jnp.asarray(parts[1][0] - 1, jnp.int32),)
    return tail


def _synth_decode(platform, shapes, dtype):
    base, quant = _split_dtype(dtype)
    quantized = quant is not None
    norm = _attn_cache_parts(shapes, quantized=quantized)
    if norm is None:
        return None
    parts, windowed = norm
    kv_dt = quant if quantized else base
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q = _normal(ks[0], parts[0], base)
    k = _normal(ks[1], parts[1], kv_dt)
    v = _normal(ks[2], parts[2], kv_dt)
    if len(parts) == 4:
        npages, page = parts[1][-4], parts[1][-3]
        b, nblocks = parts[3]
        bt = jax.random.randint(ks[3], (b, nblocks), 0, max(npages, 1),
                                jnp.int32)
        logical = nblocks * page
        args = (q, k, v, logical // 2, bt)
    else:
        logical = parts[1][1]
        args = (q, k, v, logical // 2, None)
    tail = _synth_tail(parts, windowed, quantized)
    if tail:
        return args + tail
    return args[:4] if args[4] is None else args


def _synth_chunk(platform, shapes, dtype):
    # same bucket structure as decode: q/k_cache/v_cache (+ optional
    # trailing "scalar" for a traced pos, + block table when paged,
    # + trailing "scalar" window when windowed, + trailing scale pair
    # when quantized); resynthesize pos mid-cache
    base, quant = _split_dtype(dtype)
    quantized = quant is not None
    norm = _attn_cache_parts(shapes, quantized=quantized)
    if norm is None:
        return None
    parts, windowed = norm
    kv_dt = quant if quantized else base
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    q = _normal(ks[0], parts[0], base)
    k = _normal(ks[1], parts[1], kv_dt)
    v = _normal(ks[2], parts[2], kv_dt)
    c = parts[0][1]
    if len(parts) == 4:
        npages, page = parts[1][-4], parts[1][-3]
        b, nblocks = parts[3]
        logical = nblocks * page
        if logical < c:
            return None      # chunk cannot fit the logical window
        bt = jax.random.randint(ks[3], (b, nblocks), 0, max(npages, 1),
                                jnp.int32)
        pos = max(0, min(logical - c, logical // 2))
        args = (q, k, v, pos, bt)
    else:
        logical = parts[1][1]
        args = (q, k, v, logical // 2, None)
    tail = _synth_tail(parts, windowed, quantized)
    if tail:
        return args + tail
    return args[:4] if args[4] is None else args


def _synth_windowed(platform, shapes, dtype):
    parts = _parse_bucket(shapes)
    if (not parts or len(parts) != 4 or any(len(p) != 4 for p in parts[:3])
            or parts[3] != ()):
        return None
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q, k, v = (_normal(kk, p, dtype) for kk, p in zip(ks, parts[:3]))
    return (q, k, v, _synth_window(parts[1][1]))


def _synth_ssd(platform, shapes, dtype):
    parts = _parse_bucket(shapes)
    if (not parts or len(parts) != 5 or len(parts[0]) != 4
            or len(parts[1]) != 3 or len(parts[2]) != 1
            or len(parts[3]) != 4 or len(parts[4]) != 4):
        return None
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    dt = jnp.dtype(dtype)
    return (jax.random.normal(ks[0], parts[0], dt) * 0.3,
            jax.nn.softplus(jax.random.normal(ks[1], parts[1], dt)),
            -jnp.exp(jax.random.normal(ks[2], parts[2], dt) * 0.3),
            jax.random.normal(ks[3], parts[3], dt) * 0.3,
            jax.random.normal(ks[4], parts[4], dt) * 0.3)


def _synth_moe(platform, shapes, dtype):
    parts = _parse_bucket(shapes)
    if (not parts or len(parts) != 3 or len(parts[0]) != 2
            or len(parts[1]) != 3 or len(parts[2]) != 1):
        return None
    (t, _), (e, d, f), _ = parts
    ks = jax.random.split(jax.random.PRNGKey(4), 2)
    # distribute all t rows (t//e per expert would be all-zeros when
    # e > t, measuring an empty workload)
    base, rem = divmod(t, max(e, 1))
    gs = jnp.array([base + (i < rem) for i in range(e)], jnp.int32)
    return (_normal(ks[0], (t, d), dtype),
            _normal(ks[1], (e, d, f), dtype),
            gs)


def _synth_quant_matmul(platform, shapes, dtype):
    parts = _parse_bucket(shapes)
    if (not parts or len(parts) != 3 or len(parts[0]) != 2
            or len(parts[1]) != 2 or len(parts[2]) != 1
            or parts[0][1] != parts[1][0] or parts[1][1] != parts[2][0]):
        return None
    base, quant = _split_dtype(dtype)
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    return (_normal(ks[0], parts[0], base),
            _normal(ks[1], parts[1], quant if quant is not None else base),
            jax.random.uniform(ks[2], parts[2], jnp.float32, 0.001, 0.02))


_SYNTHS = {
    "rmsnorm": _synth_rmsnorm,
    "attention": _synth_attention,
    "windowed_attention": _synth_windowed,
    "decode_attention": _synth_decode,
    "chunk_attention": _synth_chunk,
    "ssd_scan": _synth_ssd,
    "moe_gmm": _synth_moe,
    "quant_matmul": _synth_quant_matmul,
}

for _name, _synth in _SYNTHS.items():
    _TUNERS[_name] = dataclasses.replace(_TUNERS[_name], args_from_shapes=_synth)


def tuners() -> dict[str, OpTuner]:
    """The per-op tuner hooks (shared by the TPU and interpret impls)."""
    return dict(_TUNERS)


_registered: set[int] = set()


def register_all(registry: OpRegistry | None = None) -> OpRegistry:
    """Populate a registry with every op (idempotent per registry)."""
    reg = registry if registry is not None else global_registry
    if id(reg) in _registered and reg is global_registry:
        return reg
    for name in OP_NAMES:
        reg.declare(ABIS[name])
        reg.register(
            OpImpl(abi=ABIS[name], kind=ImplKind.REFERENCE, fn=_REFS[name],
                   provider="jnp-ref")
        )
        reg.register(
            OpImpl(abi=ABIS[name], kind=ImplKind.NATIVE, fn=_NATIVES[name],
                   requires_feature="pallas_kernels",
                   requires_device_kind="tpu", provider="pallas-tpu",
                   tuner=_TUNERS.get(name))
        )
        reg.register(
            OpImpl(abi=ABIS[name], kind=ImplKind.NATIVE,
                   fn=_NATIVES_INTERPRET[name],
                   requires_feature="pallas_interpret",
                   provider="pallas-interpret", tuner=_TUNERS.get(name))
        )
    _registered.add(id(reg))
    return reg


def default_binding():
    """Reference-only binding for code running outside a Runtime (smoke
    tests, oracles).  Uses the real registry path with swap disabled."""
    from repro.core.platform import LAPTOP

    reg = register_all()
    return reg.bind(OP_NAMES, LAPTOP, native=False, freeze=False)


def measurement_binding():
    """Dry-run cost binding: identical math to the references, but with
    every internal lax.scan UNROLLED — XLA's cost_analysis counts a while
    body once regardless of trip count, so rolled chunk loops (chunked
    attention, SSD inter-chunk scan) silently undercount FLOPs/bytes."""

    def attention_u(q, k, v, *, causal=True, scale=None):
        chunk = 1024 if k.shape[1] > 2048 else None
        return attention_ref(q, k, v, causal=causal, scale=scale,
                             chunk_kv=chunk, unroll=True)

    def ssd_u(x, dt, A, Bm, Cm, *, chunk=128):
        return ssd_scan_ref(x, dt, A, Bm, Cm, chunk=chunk, unroll=True)

    table = dict(default_binding())
    table["attention"] = attention_u
    table["ssd_scan"] = ssd_u
    return table
