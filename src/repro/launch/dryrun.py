import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: prove every (arch x shape x mesh) deployment is
coherent — lower + compile under the production mesh, record memory and
cost analysis and the collective schedule.

The lines above MUST precede any jax import: the 512 placeholder host
devices let jax.make_mesh build the production meshes on the CPU, and
the CPU pin keeps the dry run (and every child it spawns, which inherits
the environment) off any attached TPU.  Smoke tests and benchmarks do NOT import this module.

Usage:
  python -m repro.launch.dryrun --arch qwen2-72b --shape train_4k
  python -m repro.launch.dryrun --arch ... --shape ... --multi-pod
  python -m repro.launch.dryrun --all [--multi-pod] --out experiments/dryrun

--all orchestrates one subprocess per cell (fresh XLA memory per compile).
"""

import argparse
import dataclasses
import json
import subprocess
import sys
import time
from pathlib import Path

__all__ = ["run_cell", "main"]

_DEF_OUT = Path("experiments/dryrun")


def _lower_and_compile(dep, shape):
    t0 = time.time()
    if shape.kind == "train":
        params, opt = dep.abstract_state()
        lowered = dep.train_step.lower(params, opt, dep.abstract_batch())
    elif shape.kind == "prefill":
        params, _ = dep.abstract_state()
        lowered = dep.prefill_step.lower(params, dep.abstract_batch())
    else:
        params, _ = dep.abstract_state()
        b = dep.abstract_batch()
        lowered = dep.decode_step.lower(params, b["token"], b["cache"], b["pos"])
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    return lowered, compiled, t_lower, t_compile


def run_cell(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    seq_shard: bool = False,
    remat: str | None = None,
    rules: str = "baseline",
    label: str = "baseline",
    moe_chunks: int = 1,
    loss_chunks: int = 1,
    grad_accum: int = 1,
    head_padding: bool = True,
    cache_seq_shard: bool = True,
) -> dict:
    import dataclasses as dc

    from repro.configs import get_config, get_shape, shape_applicable
    from repro.launch import perf_variants
    from repro.launch.hlo_analysis import (
        collective_stats,
        cost_stats,
        memory_stats,
        roofline_terms,
    )
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import DeployOptions, make_deployment

    cfg = get_config(arch)
    shape = get_shape(shape_name)
    ok, why = shape_applicable(cfg, shape)
    result: dict = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "label": label,
        "kind": shape.kind,
    }
    if not ok:
        result["status"] = "skipped"
        result["reason"] = why
        return result

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    options = DeployOptions(
        remat=remat, seq_shard=seq_shard, rules=perf_variants.get_rules(rules),
        moe_token_chunks=moe_chunks, loss_seq_chunks=loss_chunks,
        grad_accum=grad_accum,
        head_padding=head_padding, cache_seq_shard=cache_seq_shard,
    )

    # -- 1. full-depth compile (scanned): the deployment PROOF + memory ----
    dep = make_deployment(cfg, shape, mesh, options=options)
    _, compiled, t_lower, t_compile = _lower_and_compile(dep, shape)
    mem = memory_stats(compiled)

    # -- 2. cost extrapolation: XLA's cost_analysis counts while bodies once,
    # so flops/bytes/collectives come from small UNROLLED depth-1/depth-2
    # models: total = c1 + (n_blocks - 1) * (c2 - c1) [+ encoder delta].
    from repro.models.model import build_model  # for period calculation

    period = build_model(cfg).period
    n_blocks = cfg.num_layers // period
    opts_u = dc.replace(options, scan_unroll=True)

    def cost_at(dec_blocks: int, enc_layers: int | None = None):
        kw = {"num_layers": period * dec_blocks}
        if cfg.encoder_layers:
            kw["encoder_layers"] = enc_layers if enc_layers is not None else 1
        cfg_k = dc.replace(cfg, **kw)
        dep_k = make_deployment(cfg_k, shape, mesh, options=opts_u)
        _, compiled_k, _, _ = _lower_and_compile(dep_k, shape)
        c = cost_stats(compiled_k)
        col = collective_stats(compiled_k.as_text())
        return {
            "flops": c.get("flops", 0.0),
            "bytes": c.get("bytes_accessed", 0.0),
            "coll_operand": float(col["total_bytes"]),
            "coll_wire": float(col["total_wire_bytes"]),
            "coll_counts": col["count_by_kind"],
            "coll_bytes_by_kind": col["bytes_by_kind"],
        }

    c1 = cost_at(1)
    c2 = cost_at(2)
    scale = n_blocks - 1

    def extrap(key):
        # linear in depth; clamped because XLA's collective combiner can be
        # mildly sublinear between depth-1 and depth-2 modules
        return max(c1[key] + scale * (c2[key] - c1[key]), max(c1[key], c2[key]))

    cost = {k: extrap(k) for k in ("flops", "bytes", "coll_operand", "coll_wire")}
    coll_counts = {
        k: c1["coll_counts"][k] + scale * (c2["coll_counts"][k] - c1["coll_counts"][k])
        for k in c1["coll_counts"]
    }
    coll_bytes_kind = {
        k: c1["coll_bytes_by_kind"][k]
        + scale * (c2["coll_bytes_by_kind"][k] - c1["coll_bytes_by_kind"][k])
        for k in c1["coll_bytes_by_kind"]
    }
    if cfg.encoder_layers > 1:
        c_enc2 = cost_at(1, enc_layers=2)
        enc_scale = cfg.encoder_layers - 1
        for k in cost:
            src = {"flops": "flops", "bytes": "bytes",
                   "coll_operand": "coll_operand", "coll_wire": "coll_wire"}[k]
            cost[k] += enc_scale * (c_enc2[src] - c1[src])

    # model-level FLOPs (assignment conventions); enc-dec processes S
    # encoder frames AND S decoder tokens -> 2x positions per cell
    total_p, active_p = cfg.param_count()
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    if cfg.is_enc_dec and shape.kind != "decode":
        tokens *= 2
    mult = 6 if shape.kind == "train" else 2
    model_flops = mult * active_p * tokens

    flops_dev = cost["flops"]
    terms = roofline_terms(flops_dev, cost["bytes"], cost["coll_operand"], chips)
    result.update(
        status="ok",
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        memory=mem,
        cost={
            "flops": flops_dev,
            "bytes_accessed": cost["bytes"],
            "collective_operand_bytes": cost["coll_operand"],
            "collective_wire_bytes": cost["coll_wire"],
        },
        collectives={
            "bytes_by_kind": coll_bytes_kind,
            "count_by_kind": coll_counts,
            "total_bytes": cost["coll_operand"],
            "total_wire_bytes": cost["coll_wire"],
        },
        chips=chips,
        period=period,
        n_blocks=n_blocks,
        params_total=total_p,
        params_active=active_p,
        model_flops_total=model_flops,
        model_flops_per_chip=model_flops / chips,
        useful_flops_ratio=(model_flops / chips) / flops_dev if flops_dev else None,
        roofline=terms.as_dict(),
    )
    return result


def _print_summary(r: dict) -> None:
    if r["status"] != "ok":
        print(f"[{r['arch']} x {r['shape']} @ {r['mesh']}] {r['status']}: "
              f"{r.get('reason', r.get('error', ''))}")
        return
    mem = r["memory"]
    print(
        f"[{r['arch']} x {r['shape']} @ {r['mesh']} ({r['label']})] OK "
        f"compile={r['compile_s']}s\n"
        f"  per-device bytes: args={mem.get('argument_size_in_bytes', 0)/1e9:.3f}G "
        f"temp={mem.get('temp_size_in_bytes', 0)/1e9:.3f}G "
        f"out={mem.get('output_size_in_bytes', 0)/1e9:.3f}G\n"
        f"  per-device flops={r['cost']['flops']:.3e} "
        f"hbm_bytes={r['cost']['bytes_accessed']:.3e} "
        f"coll_bytes={r['collectives']['total_bytes']:.3e}\n"
        f"  roofline: compute={r['roofline']['compute_s']*1e3:.2f}ms "
        f"memory={r['roofline']['memory_s']*1e3:.2f}ms "
        f"collective={r['roofline']['collective_s']*1e3:.2f}ms "
        f"-> {r['roofline']['dominant']}-bound\n"
        f"  useful_flops_ratio={r['useful_flops_ratio'] and round(r['useful_flops_ratio'], 3)}"
    )


def _cell_filename(arch: str, shape: str, multi_pod: bool, label: str) -> str:
    mesh = "multi" if multi_pod else "single"
    return f"{arch}__{shape}__{mesh}__{label}.json"


def _run_all(args) -> int:
    from repro.configs import ARCHS, SHAPES, shape_applicable

    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    failures = 0
    cells = [
        (a, s)
        for a in ARCHS
        for s in SHAPES
    ]
    for arch, shape in cells:
        fname = out / _cell_filename(arch, shape, args.multi_pod, args.label)
        if fname.exists() and not args.force:
            print(f"skip (cached): {fname.name}")
            continue
        cmd = [
            sys.executable, "-m", "repro.launch.dryrun",
            "--arch", arch, "--shape", shape,
            "--out", str(out), "--label", args.label,
        ]
        if args.multi_pod:
            cmd.append("--multi-pod")
        if args.seq_shard:
            cmd.append("--seq-shard")
        if args.remat:
            cmd += ["--remat", args.remat]
        if args.rules != "baseline":
            cmd += ["--rules", args.rules]
        print(f"=== {arch} x {shape} ({'multi' if args.multi_pod else 'single'}) ===",
              flush=True)
        proc = subprocess.run(cmd, timeout=args.timeout,
                              env={**os.environ, "JAX_PLATFORMS": "cpu"})
        if proc.returncode != 0:
            failures += 1
            fname.write_text(json.dumps({
                "arch": arch, "shape": shape,
                "mesh": "2x16x16" if args.multi_pod else "16x16",
                "label": args.label, "status": "error",
                "error": f"subprocess exited {proc.returncode}",
            }, indent=1))
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--seq-shard", action="store_true")
    ap.add_argument("--remat", default=None)
    ap.add_argument("--rules", default="baseline")
    ap.add_argument("--moe-chunks", type=int, default=1)
    ap.add_argument("--loss-chunks", type=int, default=1)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--no-head-pad", action="store_true")
    ap.add_argument("--legacy-cache", action="store_true")
    ap.add_argument("--label", default="baseline")
    ap.add_argument("--out", default=str(_DEF_OUT))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--timeout", type=int, default=3600)
    args = ap.parse_args(argv)

    if args.all:
        return _run_all(args)

    if not args.arch or not args.shape:
        ap.error("--arch and --shape required (or --all)")

    try:
        result = run_cell(
            args.arch, args.shape,
            multi_pod=args.multi_pod, seq_shard=args.seq_shard,
            remat=args.remat, rules=args.rules, label=args.label,
            moe_chunks=args.moe_chunks,
            loss_chunks=args.loss_chunks,
            grad_accum=args.grad_accum,
            head_padding=not args.no_head_pad,
            cache_seq_shard=not args.legacy_cache,
        )
    except Exception as e:  # record failures as data, they are bugs to fix
        import traceback

        result = {
            "arch": args.arch, "shape": args.shape,
            "mesh": "2x16x16" if args.multi_pod else "16x16",
            "label": args.label, "status": "error",
            "error": f"{type(e).__name__}: {e}",
            "traceback": traceback.format_exc()[-2000:],
        }
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    fname = out / _cell_filename(args.arch, args.shape, args.multi_pod, args.label)
    fname.write_text(json.dumps(result, indent=1))
    _print_summary(result)
    return 0 if result["status"] in ("ok", "skipped") else 1


if __name__ == "__main__":
    sys.exit(main())
