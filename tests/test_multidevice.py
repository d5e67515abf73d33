"""Multi-device behaviour (8 forced host devices, subprocess-isolated:
device count locks at backend init, so each scenario runs in its own
python)."""

import subprocess
import sys
import textwrap

import pytest

from conftest import subprocess_env


def _run(code: str, devices: int = 8, timeout: int = 600):
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        env=subprocess_env(devices),
        capture_output=True,
        text=True,
        timeout=timeout,
    )
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr[-3000:]}"
    return proc.stdout


def test_hierarchical_equals_flat_allreduce():
    """The vendor-collective swap changes the schedule, not the numbers
    (Tables III/IV: ratio == 1.0)."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed import flat_grad_allreduce, hierarchical_grad_allreduce

        mesh = jax.make_mesh((2, 4), ("pod", "data"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        grads = {"w": jnp.arange(32, dtype=jnp.float32).reshape(8, 4),
                 "b": jnp.ones((5,), jnp.float32)}

        def run(fn):
            return jax.jit(jax.shard_map(
                fn, mesh=mesh, in_specs=(P(),), out_specs=P(), check_vma=False,
            ))(grads)

        flat = run(lambda g: flat_grad_allreduce(g, data_axis="data", pod_axis="pod"))
        hier = run(lambda g: hierarchical_grad_allreduce(g, data_axis="data", pod_axis="pod"))
        for k in grads:
            np.testing.assert_allclose(np.asarray(flat[k]), np.asarray(hier[k]),
                                       atol=1e-6, rtol=1e-6)
        print("OK")
    """)
    assert "OK" in out


def test_compressed_dcn_allreduce_close_to_exact():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed import flat_grad_allreduce, hierarchical_grad_allreduce

        mesh = jax.make_mesh((2, 4), ("pod", "data"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        g = {"w": jnp.linspace(-1, 1, 64, dtype=jnp.float32).reshape(8, 8)}

        def run(fn):
            return jax.jit(jax.shard_map(
                fn, mesh=mesh, in_specs=(P(),), out_specs=P(), check_vma=False,
            ))(g)

        exact = run(lambda t: flat_grad_allreduce(t, data_axis="data", pod_axis="pod"))
        comp = run(lambda t: hierarchical_grad_allreduce(
            t, data_axis="data", pod_axis="pod", compress_dcn=True))
        err = float(jnp.abs(exact["w"] - comp["w"]).max())
        rng = float(jnp.abs(exact["w"]).max())
        assert err <= rng / 64, (err, rng)   # int8 quantization error bound
        print("OK", err)
    """)
    assert "OK" in out


def test_pipeline_matches_sequential():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed import pipeline_apply

        mesh = jax.make_mesh((4,), ("pipe",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        S, M, mb, d = 4, 6, 3, 8
        key = jax.random.PRNGKey(0)
        ws = jax.random.normal(key, (S, d, d)) * 0.3
        x = jax.random.normal(jax.random.PRNGKey(1), (M, mb, d))

        def stage(w, h):
            return jnp.tanh(h @ w)

        got = pipeline_apply(stage, ws, x, mesh, axis="pipe")

        ref = x
        for s in range(S):
            ref = jnp.tanh(ref @ ws[s])
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5, rtol=1e-5)
        print("OK")
    """)
    assert "OK" in out


def test_moe_shard_map_matches_local_path():
    """Expert-TP under a real (data x model) mesh == single-device gmm."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import ARCHS
        from repro.models import build_model, ParallelCtx
        from repro.models.moe import moe_apply

        cfg = ARCHS["phi3.5-moe-42b-a6.6b"].reduced()
        # data=1 so both paths see identical per-shard token counts (the
        # capacity cutoff C = cf*T/E depends on the local T; with data>1
        # the reference may drop different overflow rows than the
        # single-device run — documented capacity semantics, not a bug).
        mesh = jax.make_mesh((1, 8), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        pctx = ParallelCtx(mesh=mesh, batch_axes=("data",), model_axis="model")
        model = build_model(cfg, pctx=pctx)
        params = model.init(jax.random.PRNGKey(0))
        moe_params = jax.tree.map(lambda x: x[0], params["decoder"]["p0"]["moe"])

        x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, cfg.d_model)) * 0.3
        local_pctx = ParallelCtx()
        y_local, _ = moe_apply(moe_params, x, cfg, local_pctx, model.binding)
        y_mesh, _ = jax.jit(
            lambda p, h: moe_apply(p, h, cfg, pctx, model.binding)
        )(moe_params, x)
        np.testing.assert_allclose(np.asarray(y_local, np.float32),
                                   np.asarray(y_mesh, np.float32),
                                   atol=2e-4, rtol=2e-4)
        print("OK")
    """)
    assert "OK" in out


def test_small_mesh_train_step_compiles_and_runs():
    """A miniature of the production dry-run that actually EXECUTES: a
    reduced arch on a (2 data x 4 model) mesh, two real train steps."""
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.configs import ARCHS
        from repro.configs.base import ShapeConfig
        from repro.data import DataConfig, SyntheticStream
        from repro.launch.steps import DeployOptions, make_deployment
        from repro.optim import adamw_init

        cfg = ARCHS["qwen2.5-14b"].reduced()
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        shape = ShapeConfig("t", 32, 4, "train")
        dep = make_deployment(cfg, shape, mesh, options=DeployOptions(donate=False))
        params = jax.device_put(dep.model.init(jax.random.PRNGKey(0)), dep.param_sharding)
        opt = jax.device_put(adamw_init(params), dep.opt_sharding)
        stream = SyntheticStream(cfg, shape, DataConfig())
        l0 = None
        for step in range(2):
            batch = jax.device_put(stream.global_batch_at(step), dep.batch_sharding)
            params, opt, metrics = dep.train_step(params, opt, batch)
            assert bool(jnp.isfinite(metrics["loss"]))
            l0 = float(metrics["loss"])
        print("OK", l0)
    """)
    assert "OK" in out


def test_fleet_replica_state_on_its_own_device():
    """Four one-device deploys behind one fleet router: every replica
    builds its params and paged cache on the device it was deployed on,
    and KV handoffs land on the adopting replica's device."""
    out = _run("""
        import jax, numpy as np
        from repro.configs import get_config
        from repro.core import Runtime
        from repro.launch.serve import JaxEngine, Request
        from repro.launch.train import make_bundle
        from repro.serving import FleetScheduler, Replica

        cfg = get_config("qwen2.5-14b").reduced()
        bundle = make_bundle("qwen2.5-14b", reduced=True)
        devices = jax.devices()
        engines = {}

        def factory(role, host_id):
            rt = Runtime(host_env={})
            container = rt.deploy(bundle, devices=[devices[host_id]])
            eng = JaxEngine(cfg, container, slots=2, max_len=32, chunk=4,
                            paged=True)
            engines[host_id] = eng
            return Replica(host_id, role, eng)

        def placed(tree, dev):
            return all(x.devices() == {dev} for x in jax.tree.leaves(tree))

        fleet = FleetScheduler(factory, prefill=1, decode=3)
        for host_id, eng in engines.items():
            assert placed(eng.params, devices[host_id]), host_id
            assert placed(eng.cache, devices[host_id]), host_id
        rng = np.random.default_rng(3)
        reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, size=n)
                        .astype(np.int32), max_new=2)
                for i, n in enumerate([5, 7, 3])]
        for r in reqs:
            assert fleet.submit(r)
        fleet.run(max_ticks=200)
        assert all(r.done for r in reqs)
        assert fleet.handoffs == len(reqs)
        for host_id, eng in engines.items():
            assert placed(eng.cache, devices[host_id]), host_id
        assert sum(rep.scheduler.adopted for rep in fleet.decode_pool) == 3
        print("PLACED")
    """, devices=4)
    assert "PLACED" in out


def test_elastic_restore_8_to_4_devices():
    """Save on 8 devices, restore+reshard on 4 — the downscale path."""
    env8 = """
        import jax, jax.numpy as jnp
        from repro.checkpoint import save_checkpoint
        from repro.ft import rescale_plan
        plan = rescale_plan(8, model=4)
        mesh = plan.build_mesh()
        from jax.sharding import NamedSharding, PartitionSpec as P
        w = jax.device_put(jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
                           NamedSharding(mesh, P("data", "model")))
        save_checkpoint("{d}", 7, {{"w": w}})
        print("SAVED")
    """
    env4 = """
        import jax, jax.numpy as jnp, numpy as np
        from repro.checkpoint import restore_checkpoint
        from repro.ft import rescale_plan
        plan = rescale_plan(4, model=4)
        mesh = plan.build_mesh()
        from jax.sharding import NamedSharding, PartitionSpec as P
        sh = NamedSharding(mesh, P(None, "model"))
        tree, step = restore_checkpoint("{d}", {{"w": np.zeros((8, 8), np.float32)}},
                                        sharding_fn=lambda p, a: sh)
        assert step == 7
        np.testing.assert_array_equal(np.asarray(tree["w"]),
                                      np.arange(64, dtype=np.float32).reshape(8, 8))
        assert tree["w"].sharding == sh
        print("RESTORED")
    """
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        out1 = _run(env8.format(d=d), devices=8)
        assert "SAVED" in out1
        out2 = _run(env4.format(d=d), devices=4)
        assert "RESTORED" in out2
