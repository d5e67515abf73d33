"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Prints, as the last line of standard output, one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device``, with ``--trace 1`` a ``breakdown``, and last
``checks``: each number compared for ``correct`` beside its limit, which
also close standard error.  Without a TPU, or with fewer chips than the
cell asks for, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_PROC = time.perf_counter()     # set-up is timed from here

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import check, harness  # noqa: E402
from bench.readers import Run  # noqa: E402

CHECK_NAME = "logit_gap_sd"


def metric_names(bench: dict, workload: str, trace: bool) -> list[str]:
    kind = "per_layer" if trace else "end_to_end"
    return [m["name"] for m in bench[kind]
            if workload in m.get("workloads", [workload])]


def read_metric(name: str, run: Run, root: Path = ROOT):
    return harness.plugin("metrics", name, root).read(run)


def enable_compile_cache() -> None:
    """JAX's persistent cache at the fixed path ``.jax_cache/`` inside
    the checkout, for every program.  A directory named in the
    environment is replaced, and the program, which takes its directory
    from JAX_COMPILATION_CACHE_DIR, is given the same one, so that two
    checkouts never share a cache."""
    import jax

    path = ROOT / ".jax_cache"
    path.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(path)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def execute(cell, seed: int, seconds: float, trace: bool, *, peaks=None,
            provider: str = "pallas-tpu", platform=None, program_config=None,
            hook=None, t_proc: float = T_PROC, log=sys.stderr) -> dict:
    """One run of `cell`: set-up, the window, the comparison, the line.
    Metrics are read only with `peaks` (a chip's); without them the run
    reports none."""
    bench = cell.driver.Driver(cell, provider=provider, platform=platform, t_proc=t_proc,
                               program_config=program_config)
    bench.set_weights(seed)
    bench.warm()
    rec = bench.window(seed, seconds, trace=trace, hook=hook)
    peak = bench.memory_peak()
    due = rec.due_in_window
    failed = sum(1 for r in due if not r.accepted or r.first is None)
    bench.free_program_state()
    picked = check.sample(due, seed, int(cell.deploy["check_requests"]))
    limit = float(cell.deploy["check_limit"])
    gap, tokens = None, 0               # no finished request: nothing served
    if picked:
        cmp = check.compare(cell, bench.weights, picked)
        gap, tokens = cmp["gap"], cmp["tokens"]
    correct = gap is not None and gap <= limit
    metrics = {}
    if peaks is not None:
        run = Run(rec, cell, peaks)
        spec = harness.load_benchmark(cell.root)
        units = {m["name"]: m["unit"] for m in spec["end_to_end"] + spec["per_layer"]}
        for name in metric_names(spec, cell.name, trace):
            value = read_metric(name, run, cell.root)
            if value is not None:
                metrics[name] = {"value": value, "unit": units[name]}
    dev = bench.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(__import__("jax").devices()), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": len(due), "failed": failed,
              "metrics": metrics, "device": device}
    if trace and rec.trace is not None:
        device["busy_s"] = rec.trace.busy_s
        device["window_s"] = rec.trace.window_s
        result["breakdown"] = {"device_ops": [list(x) for x in rec.trace.device_ops],
                               "idle_gaps": [list(x) for x in rec.trace.idle_gaps]}
    result["checks"] = {CHECK_NAME: {"value": gap, "limit": limit}}
    bench.close()
    gc.collect()
    print(f"[{cell.name}] seed {seed}: {len(due)} requests due in {seconds} s, "
          f"{failed} failed, generator at most {rec.late_s * 1e3:.1f} ms late; "
          f"setup {rec.setup_s:.1f} s (deploy {rec.deploy_s:.1f}, warm-up "
          f"{rec.warmup_s:.1f}); peak {peak} bytes", file=log, flush=True)
    print(f"[{cell.name}] compared {tokens} served tokens of {len(picked)} requests "
          f"with the reference", file=log, flush=True)
    print(f"check {CHECK_NAME} {gap!r} limit {limit!r} -> "
          f"{'correct' if correct else 'NOT correct'}", file=log, flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.load_cell(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return 2
    from bench.peaks import peaks_for

    peaks = peaks_for(devices[0].device_kind)
    enable_compile_cache()
    result = execute(cell, args.seed, args.seconds, bool(args.trace), peaks=peaks)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
