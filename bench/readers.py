"""What the metric readers share: the run they read, and its arithmetic.

A reader is ``bench/metrics/<metric>.py`` with one function,
``read(run) -> float | None``; None means that the run held nothing to
read, and the harness leaves the metric out of the line.
"""

from __future__ import annotations

import dataclasses
import math
import statistics

from bench import work

__all__ = ["Run", "nearest_rank", "window_calls", "traced_calls", "roofline_share",
           "step_mfu"]


@dataclasses.dataclass
class Run:
    rec: object            # bench.harness.WindowRecord
    cell: object           # bench.harness.Cell
    peaks: object          # bench.peaks.Peaks

    @property
    def dims(self) -> dict:
        return self.cell.dims


def nearest_rank(values, q: float) -> float | None:
    v = sorted(values)
    if not v:
        return None
    return v[max(1, math.ceil(q * len(v))) - 1]


def window_calls(rec, calls):
    """Step calls that began and ended inside the measured window."""
    return [c for c in calls if rec.t0 <= c[0] and c[1] <= rec.t_end]


def traced_calls(rec, calls):
    """Step calls that began and ended inside the traced window."""
    if rec.traced is None:
        return []
    lo, hi = rec.traced
    return [c for c in calls if lo <= c[0] and c[1] <= hi]


def roofline_share(run, calls, module: str, kernel: str, per_call, layers: int):
    """Least time the traced calls' kernel work needs on this chip, over
    the kernel's device time inside `module`, in %.  None when the trace
    holds no such kernel, or when its events do not match the calls (one
    per layer per call)."""
    red = run.rec.trace
    if red is None or not calls or layers == 0:
        return None
    seconds, events = red.kernel.get((module, kernel), (0.0, 0))
    if events != len(calls) * layers * run.rec.chips or seconds <= 0:
        return None
    need = sum(work.roofline_time(per_call(c), run.peaks)[0] for c in calls) * layers
    return 100.0 * need / (seconds / run.rec.chips)


def step_mfu(run, calls, flops_of) -> float | None:
    """Model FLOPs of the window's calls over their summed host time,
    times chips and peak, in %."""
    calls = window_calls(run.rec, calls)
    if not calls:
        return None
    seconds = sum(c[1] - c[0] for c in calls)
    flops = sum(flops_of(c) for c in calls)
    return 100.0 * flops / (seconds * run.rec.chips * run.peaks.flops_bf16)


def median_ms(calls) -> float | None:
    return statistics.median((c[1] - c[0]) * 1e3 for c in calls) if calls else None
