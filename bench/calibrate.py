"""Readings that set a cell's rate and its limit, on the chip, in one process.

    python3 bench/calibrate.py sweep   --workload W --rates 1,1.5,2 --seconds 30
    python3 bench/calibrate.py readings --workload W --seeds 1,2,3 --seconds 20

``sweep`` offers the cell's mix at each rate for a window of its own
(one deploy for all) and reports, per rate, the requests due and
completed in the window and the backlog (due, not finished) at its
middle and at its close: the knee is the highest rate whose completions
keep pace and whose backlog does not grow from the middle to the close.

``readings`` serves a short window at the cell's own load for each seed,
with that seed's weights, and reports the widest logit gap of the
program's served tokens and of the float8 control's at the same
positions (bench/check.py): the lower and upper readings a limit is set
between.  The benchmark's own runs never run this.

Each result is one JSON line on standard output.
"""

from __future__ import annotations

import argparse
import copy
import json
import sys
import time
from pathlib import Path

T_PROC = time.perf_counter()
ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import check, harness  # noqa: E402
from bench.run import enable_compile_cache  # noqa: E402


def backlog(rec, t: float) -> int:
    return sum(1 for r in rec.requests.values()
               if r.due <= t and not (r.req is not None and r.req.done
                                      and r.times and r.times[-1] <= t))


def sweep(bench, rates, seconds: float, seed: int) -> None:
    for rate in rates:
        mix = copy.deepcopy(bench.cell.mix)
        mix["arrivals"]["rate"] = rate
        rec = bench.window(seed, seconds, mix=mix, drain=False)
        due = rec.due_in_window
        done = sum(1 for r in due if r.req.done and r.times[-1] <= rec.t_end)
        tokens = sum(1 for r in rec.requests.values() for t in r.times if rec.in_window(t))
        print(json.dumps({"rate": rate, "due": len(due), "completed": done,
                          "backlog_mid": backlog(rec, rec.t0 + seconds / 2),
                          "backlog_end": backlog(rec, rec.t_end),
                          "tokens_per_s": tokens / seconds}), flush=True)
        bench.reset()


def readings(bench, seeds, seconds: float) -> None:
    cell = bench.cell
    for seed in seeds:
        bench.set_weights(seed)
        rec = bench.window(seed, seconds)
        picked = check.sample(rec.due_in_window, seed, int(cell.deploy["check_requests"]))
        t = time.perf_counter()
        out = check.compare(cell, bench.weights, picked, control=True)
        out.update(seed=seed, requests=len(picked), reference_s=time.perf_counter() - t)
        print(json.dumps(out), flush=True)
        bench.reset()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("mode", choices=("sweep", "readings"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seeds", default="1")
    args = ap.parse_args(argv)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    cell = harness.load_cell(args.workload)
    bench = cell.driver.Driver(cell, t_proc=T_PROC)
    seeds = [int(s) for s in args.seeds.split(",")]
    bench.set_weights(seeds[0])
    bench.warm()
    print(json.dumps({"deploy_s": bench.deploy_s, "warmup_s": bench.warmup_s,
                      "setup_s": time.perf_counter() - T_PROC}), flush=True)
    if args.mode == "sweep":
        sweep(bench, [float(r) for r in args.rates.split(",")], args.seconds, seeds[0])
    else:
        readings(bench, seeds, args.seconds)
    print(json.dumps({"memory_peak_bytes": bench.memory_peak()}), flush=True)
    bench.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
