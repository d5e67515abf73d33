"""Autotuner subsystem: BlockConfig, search pruning, cache persistence,
cache-key stability, registry/Runtime integration (hits, misses, fallbacks)."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.core.abi import AbiString
from repro.core.bundle import Bundle
from repro.core.platform import POD_SIM, Platform
from repro.core.registry import ImplKind, OpImpl, OpRegistry
from repro.core.runtime import Runtime
from repro.kernels.ops import ABIS, register_all
from repro.tuning import (
    SCHEMA_VERSION,
    BlockConfig,
    CacheKey,
    OpTuner,
    TuningCache,
    TuningContext,
    default_config,
    enumerate_space,
    resolve_cache_path,
    search,
)

# ---------------------------------------------------------------- config --


def test_block_config_roundtrip_and_hash():
    cfg = BlockConfig.make(block_q=128, block_k=64)
    assert cfg["block_q"] == 128 and cfg.get("missing", 7) == 7
    assert BlockConfig.from_dict(cfg.to_dict()) == cfg
    assert hash(BlockConfig.make(block_k=64, block_q=128)) == hash(cfg)
    assert "block_k=64" in str(cfg)


def test_block_config_rejects_junk():
    with pytest.raises(ValueError):
        BlockConfig.make(block_rows=0)
    with pytest.raises(ValueError):
        BlockConfig.from_dict({"": 4})


def test_default_config_platform_override():
    assert default_config("rmsnorm")["block_rows"] == 256
    assert default_config("rmsnorm", POD_SIM)["block_rows"] == 64
    assert default_config("rmsnorm", "pod-sim") == default_config("rmsnorm", POD_SIM)
    assert default_config("unknown_op") == BlockConfig()


# ---------------------------------------------------------------- search --


def test_enumerate_space_cartesian():
    configs = enumerate_space({"a": (1, 2), "b": (3, 4, 5)})
    assert len(configs) == 6
    assert BlockConfig.make(a=2, b=4) in configs


def test_search_prunes_and_picks_fastest():
    import time

    def run_with(cfg):
        time.sleep(0.005 * cfg["a"])   # 10 ms apart: above scheduler jitter

    result = search(run_with, {"a": (1, 3, 8)},
                    feasible=lambda c: c["a"] < 8, iters=1, warmup=0)
    assert result.pruned == 1
    assert result.best == BlockConfig.make(a=1)
    assert len(result.measurements) == 2


def test_search_survives_failing_candidates():
    def run_with(cfg):
        if cfg["a"] != 2:
            raise RuntimeError("boom")
        return 0

    result = search(run_with, {"a": (1, 2, 3)}, iters=1, warmup=0)
    assert result.failed == 2
    assert result.best == BlockConfig.make(a=2)


# ----------------------------------------------------------------- cache --


def _key(shapes="128x256", abi="rmsnorm/1:0/abcdefabcdef"):
    return CacheKey(abi=abi, platform="pod-sim/cpu-host/cpu",
                    shapes=shapes, dtype="float32")


def test_cache_round_trip_persistence(tmp_path):
    path = tmp_path / "deep" / "tuning.json"
    cache = TuningCache(path)
    cache.put(_key(), BlockConfig.make(block_rows=64), metrics={"best_us": 12.5})
    assert cache.dirty
    cache.save()
    assert not cache.dirty

    reloaded = TuningCache.load(path)
    assert len(reloaded) == 1
    assert reloaded.get(_key()) == BlockConfig.make(block_rows=64)
    assert reloaded.metrics(_key())["best_us"] == 12.5
    assert reloaded.get(_key(shapes="512x512")) is None


def test_cache_corrupted_file_falls_back_empty(tmp_path):
    path = tmp_path / "tuning.json"
    path.write_text("{ this is not json")
    cache = TuningCache.load(path)
    assert len(cache) == 0
    assert cache.get(_key()) is None
    cache.put(_key(), BlockConfig.make(block_rows=8))
    cache.save()                       # corrupted file is recoverable in place
    assert TuningCache.load(path).get(_key()) == BlockConfig.make(block_rows=8)


def test_cache_stale_schema_ignored(tmp_path):
    path = tmp_path / "tuning.json"
    path.write_text(json.dumps({
        "schema": SCHEMA_VERSION + 1,
        "entries": {_key().encode(): {"config": {"block_rows": 4}}},
    }))
    assert TuningCache.load(path).get(_key()) is None


def test_cache_save_merges_concurrent_writers(tmp_path):
    """Two deployments tuning different ops against one site cache must
    both keep their winners (save is read-merge-replace, not clobber)."""
    path = tmp_path / "tuning.json"
    a = TuningCache(path)
    b = TuningCache(path)
    a.put(_key(abi="op_a/1:0/aaaaaaaaaaaa"), BlockConfig.make(block=2))
    b.put(_key(abi="op_b/1:0/bbbbbbbbbbbb"), BlockConfig.make(block=4))
    a.save()
    b.save()
    merged = TuningCache.load(path)
    assert merged.get(_key(abi="op_a/1:0/aaaaaaaaaaaa")) == BlockConfig.make(block=2)
    assert merged.get(_key(abi="op_b/1:0/bbbbbbbbbbbb")) == BlockConfig.make(block=4)


def test_cache_malformed_entry_dropped(tmp_path):
    path = tmp_path / "tuning.json"
    path.write_text(json.dumps({
        "schema": SCHEMA_VERSION,
        "entries": {
            "good": {"config": {"block_rows": 4}},
            "bad": {"config": {"block_rows": "huge"}},
        },
    }))
    assert len(TuningCache.load(path)) == 1


def test_cache_path_env_override(tmp_path):
    assert resolve_cache_path({"REPRO_TUNING_CACHE": str(tmp_path / "c.json")}) \
        == tmp_path / "c.json"
    assert resolve_cache_path({}).name == "tuning.json"


def test_cache_key_stable_across_processes():
    """The key derivation must be deterministic process-to-process, or the
    site cache would never hit after a restart."""
    snippet = (
        "from repro.kernels.ops import ABIS, tuners\n"
        "from repro.core.platform import POD_SIM\n"
        "from repro.tuning import CacheKey\n"
        "t = tuners()['rmsnorm']\n"
        "key = t.cache_key(str(ABIS['rmsnorm']), POD_SIM,"
        " t.example_args(POD_SIM))\n"
        "print(key.encode())\n"
    )
    repo = Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    env["PYTHONPATH"] = str(repo / "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", snippet], capture_output=True, text=True,
        env=env, check=True,
    )
    from repro.kernels.ops import tuners

    t = tuners()["rmsnorm"]
    local = t.cache_key(str(ABIS["rmsnorm"]), POD_SIM, t.example_args(POD_SIM))
    assert out.stdout.strip() == local.encode()


# ------------------------------------------------- registry integration --

FAKE_SIM = Platform(
    name="fake-sim",
    hardware=POD_SIM.hardware,
    mesh_shape=(1,),
    mesh_axes=("data",),
    native_features=frozenset({"pallas_interpret"}),
)


def _tunable_registry():
    reg = OpRegistry()
    abi = AbiString.make("scale", {"args": ["x"]})
    reg.register(OpImpl(abi=abi, kind=ImplKind.REFERENCE,
                        fn=lambda x: x, provider="ref"))
    tuner = OpTuner(
        op="scale",
        space={"block": (2, 4, 8)},
        example_args=lambda platform: (1.5,),
        feasible=lambda cfg, platform, args: cfg["block"] <= 4,
        iters=1, warmup=0,
    )
    reg.register(OpImpl(
        abi=abi, kind=ImplKind.NATIVE,
        fn=lambda x, config=None: x * config["block"],
        requires_feature="pallas_interpret", provider="fake-native", tuner=tuner,
    ))
    return reg, abi


def test_bind_records_searched_then_hit(tmp_path):
    reg, _ = _tunable_registry()
    cache = TuningCache(tmp_path / "tuning.json")

    ctx = TuningContext(cache, FAKE_SIM)
    binding = reg.bind(["scale"], FAKE_SIM, native=True, freeze=False, tuning=ctx)
    r = binding.reports[0]
    assert r.swapped and r.tuning == "cache-miss-searched"
    assert r.config in ("block=2", "block=4")        # pruned space only
    assert "tune: cache-miss-searched" in binding.describe()
    # the injected config actually drives the bound callable
    assert binding["scale"](1.0) in (2.0, 4.0)

    # the resolved config is exposed for call sites that pass explicit tiles
    assert binding.tuned_config("scale") is not None
    assert f"block={binding.tuned_config('scale')['block']}" == r.config

    ctx2 = TuningContext(cache, FAKE_SIM)
    binding2 = reg.bind(["scale"], FAKE_SIM, native=True, freeze=False, tuning=ctx2)
    assert binding2.reports[0].tuning == "cache-hit"
    assert binding2.reports[0].config == r.config


def test_untuned_binding_exposes_no_config():
    reg, _ = _tunable_registry()
    binding = reg.bind(["scale"], FAKE_SIM, native=True, freeze=False)
    assert binding.tuned_config("scale") is None
    assert binding.tuned_config("never_declared") is None


def test_bind_unselected_op_falls_back_to_default(tmp_path):
    reg, _ = _tunable_registry()
    ctx = TuningContext(TuningCache(tmp_path / "t.json"), FAKE_SIM,
                        ops={"some_other_op"})
    binding = reg.bind(["scale"], FAKE_SIM, native=True, freeze=False, tuning=ctx)
    assert binding.reports[0].tuning == "cache-miss-default"


def test_bind_reference_impl_reports_no_tuning(tmp_path):
    reg, _ = _tunable_registry()
    ctx = TuningContext(TuningCache(tmp_path / "t.json"), FAKE_SIM)
    binding = reg.bind(["scale"], FAKE_SIM, native=False, freeze=False, tuning=ctx)
    assert binding.reports[0].tuning == "" and binding.reports[0].config == ""


def test_search_failure_falls_back_to_default(tmp_path):
    reg = OpRegistry()
    abi = AbiString.make("boom", {"args": ["x"]})
    reg.register(OpImpl(abi=abi, kind=ImplKind.REFERENCE, fn=lambda x: x))
    tuner = OpTuner(op="boom", space={"block": (2,)},
                    example_args=lambda platform: (1.0,),
                    feasible=lambda cfg, platform, args: False,  # prunes all
                    iters=1, warmup=0)
    reg.register(OpImpl(abi=abi, kind=ImplKind.NATIVE,
                        fn=lambda x, config=None: x,
                        requires_feature="pallas_interpret", tuner=tuner))
    cache = TuningCache(tmp_path / "t.json")
    ctx = TuningContext(cache, FAKE_SIM)
    binding = reg.bind(["boom"], FAKE_SIM, native=True, freeze=False, tuning=ctx)
    assert binding.reports[0].tuning == "search-failed-default"
    # the fallback is persisted: the failed search is paid once, not per deploy
    ctx.flush()
    ctx2 = TuningContext(TuningCache.load(cache.path), FAKE_SIM)
    binding2 = reg.bind(["boom"], FAKE_SIM, native=True, freeze=False, tuning=ctx2)
    assert binding2.reports[0].tuning == "cache-hit"


def test_cache_key_from_specs_matches_materialized_args():
    """Keys derived from abstract ShapeDtypeStructs must equal keys from
    the materialized arrays, or warm-cache deploys would never hit."""
    from repro.kernels.ops import tuners

    for op, t in tuners().items():
        assert t.example_specs is not None, op
        k_spec = t.cache_key("x/1:0/" + "0" * 12, POD_SIM, t.workload_spec(POD_SIM))
        k_args = t.cache_key("x/1:0/" + "0" * 12, POD_SIM, t.example_args(POD_SIM))
        assert k_spec == k_args, op


def test_ssd_scan_tuned_chunk_degrades_to_divisor():
    """A cached chunk that doesn't divide the live sequence must fall back
    to a common divisor instead of tripping the kernel assert."""
    import jax

    from repro.kernels.ssd_scan import ssd_scan
    from repro.kernels.ssd_scan_ref import ssd_scan_ref

    b, s, h, p, g, n = 1, 24, 2, 8, 1, 8
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    import jax.numpy as jnp

    x = jax.random.normal(ks[0], (b, s, h, p)) * 0.3
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    Bm = jax.random.normal(ks[3], (b, s, g, n)) * 0.3
    Cm = jax.random.normal(ks[4], (b, s, g, n)) * 0.3
    y, st = ssd_scan(x, dt, A, Bm, Cm,
                     config=BlockConfig.make(chunk=16),  # 24 % 16 != 0 -> gcd 8
                     interpret=True)
    yr, sr = ssd_scan_ref(x, dt, A, Bm, Cm, chunk=8)
    import numpy as np

    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=2e-5, rtol=2e-5)


# ------------------------------------------------- runtime end-to-end --


def _rmsnorm_bundle():
    return Bundle(name="tune-demo", tag="t", model_config={}, recipe={},
                  required_ops={"rmsnorm": str(ABIS["rmsnorm"])}, env={})


def test_runtime_autotune_demo_pod_sim(tmp_path):
    """The acceptance demo: tuning rmsnorm in interpret mode on pod-sim
    writes a cache entry; a second Runtime deployment binds with a cache
    hit recorded in the SwapReport."""
    cache_path = tmp_path / "site" / "tuning.json"
    host_env = {"REPRO_PLATFORM": "pod-sim",
                "REPRO_TUNING_CACHE": str(cache_path)}

    rt = Runtime(registry=register_all(OpRegistry()), host_env=host_env)
    c1 = rt.deploy(_rmsnorm_bundle(), native_ops=True, autotune=True,
                   autotune_ops=["rmsnorm"])
    r1 = next(r for r in c1.binding.reports if r.op == "rmsnorm")
    assert r1.swapped and r1.bound == "pallas-interpret"
    assert r1.tuning == "cache-miss-searched" and r1.config
    assert cache_path.is_file()
    assert c1.autotune and "autotune: on" in c1.describe()
    assert c1.env["REPRO_TUNING_CACHE"] == str(cache_path)  # allowlisted
    rt.cleanup()

    rt2 = Runtime(registry=register_all(OpRegistry()), host_env=host_env)
    c2 = rt2.deploy(_rmsnorm_bundle(), native_ops=True, autotune=True)
    r2 = next(r for r in c2.binding.reports if r.op == "rmsnorm")
    assert r2.tuning == "cache-hit" and r2.config == r1.config
    rt2.cleanup()


def test_runtime_autotune_off_leaves_reports_untouched():
    rt = Runtime(registry=register_all(OpRegistry()),
                 host_env={"REPRO_PLATFORM": "pod-sim"})
    c = rt.deploy(_rmsnorm_bundle(), native_ops=True, autotune=False)
    assert all(r.tuning == "" for r in c.binding.reports)
    assert not c.autotune
    rt.cleanup()
