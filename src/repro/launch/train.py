"""Training driver — the `shifter --image=<bundle> train` of this framework.

Runs the full paper workflow on whatever devices exist: pull the bundle
from the gateway cache, deploy it through the Runtime (op swap + mesh
injection), then run the fault-tolerant training loop:

  * deterministic data pipeline (restart replays from the checkpoint step)
  * async single-manifest checkpoints with atomic LATEST pointer
  * automatic restore (+ reshard, if the device count changed) on startup
  * straggler observation hooks (simulated timings on CPU)

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-14b \
      --steps 50 --reduced --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import AsyncCheckpointer, latest_step, restore_checkpoint
from repro.configs import SHAPES, get_config
from repro.configs.base import ModelConfig, ShapeConfig
from repro.core import Bundle, Runtime, global_registry
from repro.data import DataConfig, SyntheticStream
from repro.kernels.ops import OP_NAMES, register_all
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import DeployOptions, make_deployment
from repro.optim import OptState, adamw_init

__all__ = ["main", "train_loop", "make_bundle", "serving_config"]


def make_bundle(arch: str, *, reduced: bool = False,
                num_layers: int | None = None) -> Bundle:
    """The portable bundle for `arch`: its reduced CPU config, or the
    published widths, optionally cut to the first `num_layers` layers."""
    cfg = serving_config(arch, reduced=reduced, num_layers=num_layers)
    register_all()
    return Bundle(
        name=cfg.name,
        tag="latest",
        model_config=cfg.to_dict(),
        recipe={"optimizer": "adamw", "lr": 3e-4},
        required_ops={op: str(global_registry.decl(op).abi) for op in OP_NAMES},
        env={"REPRO_BUNDLE_KIND": "train"},
    )


def serving_config(arch: str, *, reduced: bool = False,
                   num_layers: int | None = None) -> ModelConfig:
    """`arch`'s registry config: reduced for the CPU, or at published
    widths and dtype with the stack cut to `num_layers` (None: whole)."""
    cfg = get_config(arch)
    if reduced:
        return cfg.reduced()
    if num_layers is not None:
        cfg = dataclasses.replace(cfg, num_layers=num_layers)
    return cfg


def train_loop(
    dep,
    stream: SyntheticStream,
    *,
    steps: int,
    ckpt_dir: Path | None,
    ckpt_every: int = 50,
    start_step: int = 0,
    params=None,
    opt_state=None,
    log_every: int = 10,
):
    model = dep.model
    if params is None:
        params = jax.device_put(
            model.init(jax.random.PRNGKey(0)), dep.param_sharding
        )
    if opt_state is None:
        opt_state = jax.device_put(adamw_init(params), dep.opt_sharding)

    ckpt = AsyncCheckpointer(ckpt_dir) if ckpt_dir else None
    losses = []
    t_start = time.time()
    for step in range(start_step, steps):
        batch = jax.device_put(stream.global_batch_at(step), dep.batch_sharding)
        params, opt_state, metrics = dep.train_step(params, opt_state, batch)
        losses.append(float(metrics["loss"]))
        if step % log_every == 0 or step == steps - 1:
            dt = time.time() - t_start
            print(f"step {step:5d} loss {losses[-1]:.4f} "
                  f"({dt / max(step - start_step + 1, 1):.2f}s/step)", flush=True)
        if ckpt and (step + 1) % ckpt_every == 0:
            ckpt.save(step + 1, {"params": params, "opt": opt_state})
    if ckpt:
        ckpt.save(steps, {"params": params, "opt": opt_state})
        ckpt.wait()
    return params, opt_state, losses


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--reduced", action="store_true",
                    help="train the smoke-scale config of the same family")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--native-ops", action="store_true")
    ap.add_argument("--data-mesh", type=int, default=0,
                    help="data-parallel ways (0 = all local devices)")
    ap.add_argument("--profile", action="store_true",
                    help="capture op geometries into REPRO_WORKLOAD_PROFILE "
                         "(feed repro.tuning.warm; or set REPRO_PROFILE=1)")
    ap.add_argument("--autotune", action="store_true",
                    help="resolve kernel configs from the site tuning cache "
                         "(or set REPRO_AUTOTUNE=1)")
    ap.add_argument("--max-tuned-entries", type=int, default=None, metavar="K",
                    help="per-op cap on the geometry-dispatch table; cold "
                         "cached buckets beyond it are LRU-evicted "
                         "(or set REPRO_TUNING_MAX_ENTRIES)")
    ap.add_argument("--tuning-bundle", default=None, metavar="PATH",
                    help="portable tuning bundle to import before binding "
                         "(python -m repro.tuning.bundle export; or set "
                         "REPRO_TUNING_BUNDLE)")
    args = ap.parse_args(argv)

    enable_compile_cache()
    bundle = make_bundle(args.arch, reduced=args.reduced)
    runtime = Runtime()
    mesh = make_host_mesh(data=args.data_mesh or None)
    container = runtime.deploy(bundle, native_ops=args.native_ops, mesh=mesh,
                               profile=True if args.profile else None,
                               autotune=True if args.autotune else None,
                               max_tuned_entries=args.max_tuned_entries,
                               tuning_bundle=args.tuning_bundle)
    print(container.describe())

    cfg = ModelConfig.from_dict(container.bundle.model_config)
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    dep = make_deployment(
        cfg, shape, container.mesh,
        options=DeployOptions(donate=True),
        binding=container.binding,
    )
    stream = SyntheticStream(cfg, shape, DataConfig(seed=0))

    start_step, params, opt_state = 0, None, None
    if args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
        model = dep.model
        skeleton = {
            "params": model.abstract_params(),
            "opt": OptState(
                m=jax.tree.map(
                    lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
                    model.abstract_params(),
                ),
                v=jax.tree.map(
                    lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
                    model.abstract_params(),
                ),
                count=jax.ShapeDtypeStruct((), jnp.int32),
            ),
        }
        skeleton = jax.tree.map(
            lambda s: np.zeros(s.shape, s.dtype), skeleton,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
        )
        restored, start_step = restore_checkpoint(args.ckpt_dir, skeleton)
        params = jax.device_put(restored["params"], dep.param_sharding)
        opt_state = jax.device_put(restored["opt"], dep.opt_sharding)
        print(f"restored checkpoint at step {start_step}")

    train_loop(
        dep, stream,
        steps=args.steps,
        ckpt_dir=Path(args.ckpt_dir) if args.ckpt_dir else None,
        ckpt_every=args.ckpt_every,
        start_step=start_step,
        params=params,
        opt_state=opt_state,
    )
    if container.workload is not None:
        print(f"captured {len(container.workload)} op geometries -> "
              f"{container.workload.path} (warm with: python -m repro.tuning.warm)")
    from repro.launch.serve import print_dispatch_stats

    print_dispatch_stats(container)
    runtime.cleanup()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
