"""Compile rehearsal: the native kernels and one whole decode step, built
by the TPU compiler for a described (not attached) v5e chip.

Interpret mode never checks Mosaic's tiling rules, so these compiles are
what keeps a kernel that passes every CPU test from being refused on the
chip.  The geometries are the served path's at published widths:
granite-3-8b (H=32, KV=8, Dh=128, D=4096) and mamba2-780m (48 SSD heads
of 64, state 128), with the paged pools `chip_smoke.py` serves from.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, so each pytest worker must
collect these tests without touching it.
"""

from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.kernels import ops
from repro.models.model import Model

NAT = ops._NATIVES
BF, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32
H, KV, DH = 32, 8, 128            # granite-3-8b attention
SLOTS, MAX_LEN, PAGE = 8, 2048, 128
SMOKE_LAYERS = 20                  # chip_smoke.py's phase-A depth cut
HBM_BYTES = 16e9                   # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(sharding, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _pool(page):
    pages = 1 + SLOTS * (MAX_LEN // page)
    return (pages, page, KV, DH), (SLOTS, MAX_LEN // page)


_POOL128, _TBL128 = _pool(PAGE)
_POOL16, _TBL16 = _pool(16)

# (name, fn, operand shapes) — each one native op at one served geometry
KERNEL_CASES = [
    ("rmsnorm-granite", lambda x, w: NAT["rmsnorm"](x, w, eps=1e-6),
     [((SLOTS, PAGE, 4096), BF), ((4096,), BF)]),
    ("rmsnorm-mamba2", lambda x, w: NAT["rmsnorm"](x, w, eps=1e-6),
     [((1, PAGE, 1536), BF), ((1536,), BF)]),
    ("attention-prefill", lambda q, k, v: NAT["attention"](q, k, v, causal=True),
     [((1, 512, H, DH), BF), ((1, 512, KV, DH), BF), ((1, 512, KV, DH), BF)]),
    ("decode-contiguous", lambda q, k, v, p: NAT["decode_attention"](q, k, v, p),
     [((SLOTS, 1, H, DH), BF), ((SLOTS, MAX_LEN, KV, DH), BF),
      ((SLOTS, MAX_LEN, KV, DH), BF), ((SLOTS,), I32)]),
    ("decode-paged", lambda q, k, v, p, t: NAT["decode_attention"](q, k, v, p, t),
     [((SLOTS, 1, H, DH), BF), (_POOL128, BF), (_POOL128, BF), ((SLOTS,), I32),
      (_TBL128, I32)]),
    ("chunk-paged-128", lambda q, k, v, p, t: NAT["chunk_attention"](q, k, v, p, t),
     [((1, PAGE, H, DH), BF), (_POOL128, BF), (_POOL128, BF), ((), I32),
      ((1, _TBL128[1]), I32)]),
    ("chunk-paged-16", lambda q, k, v, p, t: NAT["chunk_attention"](q, k, v, p, t),
     [((1, 16, H, DH), BF), (_POOL16, BF), (_POOL16, BF), ((), I32),
      ((1, _TBL16[1]), I32)]),
    ("decode-windowed",
     lambda q, k, v, p, t, w: NAT["decode_attention"](q, k, v, p, t, w),
     [((SLOTS, 1, H, DH), BF), (_POOL128, BF), (_POOL128, BF), ((SLOTS,), I32),
      (_TBL128, I32), ((), I32)]),
    ("decode-int8",
     lambda q, k, v, p, t, ks, vs: NAT["decode_attention"](q, k, v, p, t, None,
                                                           ks, vs),
     [((SLOTS, 1, H, DH), BF), (_POOL128, jnp.int8), (_POOL128, jnp.int8),
      ((SLOTS,), I32), (_TBL128, I32), ((SLOTS,), F32), ((SLOTS,), F32)]),
    ("ssd_scan-mamba2",
     lambda x, dt, a, b, c: NAT["ssd_scan"](x, dt, a, b, c, chunk=128),
     [((1, PAGE, 48, 64), BF), ((1, PAGE, 48), F32), ((48,), F32),
      ((1, PAGE, 1, 128), BF), ((1, PAGE, 1, 128), BF)]),
    ("moe_gmm", lambda x, w, g: NAT["moe_gmm"](x, w, g),
     [((1024, 4096), BF), ((8, 4096, 6400), BF), ((8,), I32)]),
    ("quant_matmul-int8", lambda x, w, s: NAT["quant_matmul"](x, w, s),
     [((SLOTS, 4096), BF), ((4096, 12800), jnp.int8), ((12800,), F32)]),
]


@pytest.mark.parametrize("name,fn,shapes", KERNEL_CASES,
                         ids=[c[0] for c in KERNEL_CASES])
def test_native_kernel_compiles(one_chip, name, fn, shapes):
    _compile(one_chip, fn, *shapes)


def test_granite_decode_step_fits_one_chip(one_chip):
    """The phase-A model of chip_smoke.py — granite-3-8b at published
    widths, cut to 20 layers — compiles one whole paged decode step with
    every op native, and its weights, pools and fresh output pool fit
    one chip's HBM (the pool is not donated, so it counts twice)."""
    cfg = dataclasses.replace(get_config("granite-3-8b"), num_layers=SMOKE_LAYERS)
    model = Model(cfg, binding=dict(NAT))
    nb = MAX_LEN // PAGE

    def placed(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
            tree)

    params = placed(model.abstract_params())
    cache = placed(model.abstract_paged_cache(1 + SLOTS * nb, PAGE, SLOTS))
    small = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in
             [((SLOTS, 1), I32), ((SLOTS,), I32), ((SLOTS,), jnp.bool_),
              ((SLOTS, nb), I32)]]
    compiled = jax.jit(model.decode).lower(
        params, small[0], cache, small[1], small[2], small[3]).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, total


def _computations(hlo: str) -> dict[str, list[str]]:
    """The optimized HLO's computations by name, each as its instruction
    lines; the entry computation is keyed "ENTRY"."""
    comps, name = {}, None
    for line in hlo.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            name = "ENTRY" if line.startswith("ENTRY") else line.split()[0]
            comps[name] = []
        elif name is not None and line.startswith("  "):
            comps[name].append(line.strip())
    return comps


# (step, slots, pages, max_len): the two granite cells' paged steps
PAGED_STEPS = [("decode", 16, 193, 1536), ("prefill_into", 8, 233, 3712)]


@pytest.mark.parametrize("step,slots,pages,max_len", PAGED_STEPS,
                         ids=[s[0] for s in PAGED_STEPS])
def test_granite_paged_step_keeps_pools_whole(one_chip, step, slots, pages,
                                              max_len):
    """The layer scan hands the attention kernels the stacked k/v pools
    and a layer index: the compiled step holds no single layer's pool
    (no slice of it out of the stack, no write-back), and the stack is
    never copied inside the loop (the entry copies of the undonated
    argument are another matter)."""
    cfg = dataclasses.replace(get_config("granite-3-8b"), num_layers=SMOKE_LAYERS)
    model = Model(cfg, binding=dict(NAT))
    nb = max_len // PAGE

    def placed(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
            tree)

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = placed(model.abstract_params())
    cache = placed(model.abstract_paged_cache(pages, PAGE, slots))
    if step == "decode":
        args = (sd((slots, 1), I32), cache, sd((slots,), I32),
                sd((slots,), jnp.bool_), sd((slots, nb), I32))
    else:
        args = (sd((1, PAGE), I32), cache, sd((), I32), sd((), I32),
                sd((), I32), sd((nb,), I32))
    hlo = jax.jit(getattr(model, step)).lower(params, *args).compile().as_text()
    layer_pool = f"bf16[{pages},{PAGE},{KV},{DH}]"
    stack = f"bf16[{SMOKE_LAYERS},{pages},{PAGE},{KV},{DH}]"
    comps = _computations(hlo)
    assert "ENTRY" in comps and len(comps) > 1
    for name, lines in comps.items():
        for line in lines:
            result = line.split(" = ", 1)[-1]
            assert not result.startswith(layer_pool), (name, line[:200])
            if name != "ENTRY":
                assert not (result.startswith(stack) and " copy(" in result), \
                    (name, line[:200])
