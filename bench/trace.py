"""Reduce a profiler trace to the numbers the per-layer readers use.

What the trace of a TPU run holds (JAX 0.9, one v5e; read by hand
before this was written):

* a plane ``/device:TPU:<n>`` per chip, with a line ``XLA Modules``
  whose events are executions of jitted programs, named
  ``jit_<function>(<hash>)``, and a line ``XLA Ops`` whose events are
  HLO operations named by their HLO text, ``%<op>.<k> = <shape> ...``.
  Ops nest on that line: a ``while`` loop's event spans its body's ops.
  A Pallas kernel is a custom call named after its wrapper, so every
  flash-family kernel is ``%flash_attention.<k>`` and the SSD scan
  ``%ssd_scan.<k>``; which step ran it shows only in the enclosing
  module (``prefill_into`` or ``decode``).
* a plane ``/host:CPU`` whose ``python`` line holds the host spans that
  ``jax.profiler.TraceAnnotation`` wrote, on the same clock.

`load` keeps only those events, as plain tuples, so that the reduction
can be tested on a small recorded trace (``bench/data/``).
"""

from __future__ import annotations

import bisect
import dataclasses
import gzip
import json
import re
from collections import defaultdict
from pathlib import Path

__all__ = ["Trace", "load", "load_json", "save_json", "reduce", "Reduction",
           "op_base", "module_base", "HOST_PREFIX"]

HOST_PREFIX = "bench."          # host spans written by the benchmark
_OP = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)?(?:\s*=|$)")
_MODULE = re.compile(r"^(?:jit_)?([\w\-\.]+?)(?:\(.*)?$")


@dataclasses.dataclass
class Trace:
    # per chip: [(start_ns, end_ns, name)] of modules and of ops
    modules: dict[str, list[tuple[float, float, str]]]
    ops: dict[str, list[tuple[float, float, str]]]
    host: list[tuple[float, float, str]]          # benchmark spans


def op_base(name: str) -> str:
    """``%flash_attention.15 = bf16[...] custom-call(...)`` -> ``flash_attention``."""
    m = _OP.match(name.strip())
    return m.group(1) if m else name.split()[0]


def op_label(name: str) -> str:
    """The HLO instruction name with its number: ``fusion.97``."""
    head = name.strip().split(" = ", 1)[0]
    return head.lstrip("%")


def module_base(name: str) -> str:
    """``jit_prefill_into(18287758729998928016)`` -> ``prefill_into``."""
    m = _MODULE.match(name.strip())
    return m.group(1) if m else name


def load(xplane_path: str | Path) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(xplane_path))
    modules: dict = {}
    ops: dict = {}
    host: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules[plane.name] = [(e.start_ns, e.end_ns, e.name) for e in line.events]
                elif line.name == "XLA Ops":
                    ops[plane.name] = [(e.start_ns, e.end_ns, e.name) for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend((e.start_ns, e.end_ns, e.name) for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    return Trace(modules, ops, sorted(host))


def save_json(trace: Trace, path: Path) -> None:
    data = {"modules": trace.modules, "ops": trace.ops, "host": trace.host}
    with gzip.open(path, "wt") as f:
        json.dump(data, f)


def load_json(path: Path) -> Trace:
    with gzip.open(path, "rt") as f:
        data = json.load(f)
    tup = lambda evs: [tuple(e) for e in evs]
    return Trace({k: tup(v) for k, v in data["modules"].items()},
                 {k: tup(v) for k, v in data["ops"].items()}, tup(data["host"]))


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                                  # mean over chips
    # (module, kernel) -> [seconds, events], summed over chips
    kernel: dict[tuple[str, str], list]
    device_ops: list[tuple[str, float]]            # top by exclusive time
    idle_gaps: list[tuple[str, float]]             # idle seconds by host span

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def _union(intervals, lo, hi):
    """Merged, clipped intervals."""
    out = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _exclusive(events):
    """Exclusive time of each event on a line where events nest."""
    evs = sorted(events, key=lambda x: (x[0], -x[1]))
    excl = [e - s for s, e, _ in evs]
    stack: list[int] = []
    for i, (s, e, _) in enumerate(evs):
        while stack and evs[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            excl[stack[-1]] -= min(e, evs[stack[-1]][1]) - s
        stack.append(i)
    return [(evs[i][2], max(excl[i], 0.0)) for i in range(len(evs))]


def _enclosing(modules, t):
    """Name of the module execution that contains time t, or None."""
    starts = [m[0] for m in modules]
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and modules[i][0] <= t < modules[i][1]:
        return module_base(modules[i][2])
    return None


def _host_at(host, t):
    """Innermost benchmark span covering time t."""
    best = None
    for s, e, name in host:
        if s > t:
            break
        if s <= t < e and (best is None or s >= best[0]):
            best = (s, e, name)
    return best[2][len(HOST_PREFIX):] if best else "untraced host"


def reduce(trace: Trace, window: tuple[float, float], *, top: int = 10) -> Reduction:
    """Busy and idle time, kernel time by enclosing program, the top
    device ops and the idle gaps by what the host was doing, all within
    `window` (start_ns, end_ns on the trace's clock)."""
    lo, hi = window
    chips = sorted(trace.ops)
    if not chips:
        raise ValueError("the trace holds no device operations")
    busy = 0.0
    kernel: dict = defaultdict(lambda: [0.0, 0])
    excl: dict = defaultdict(float)
    gaps: dict = defaultdict(float)
    for chip in chips:
        ops = [o for o in trace.ops[chip] if o[1] > lo and o[0] < hi]
        mods = sorted(trace.modules.get(chip, []))
        merged = _union([(s, e) for s, e, _ in ops], lo, hi)
        busy += sum(e - s for s, e in merged)
        labelled = []
        for s, e, name in ops:
            mod = _enclosing(mods, s) or "?"
            kernel[(mod, op_base(name))][0] += (min(e, hi) - max(s, lo)) / 1e9
            kernel[(mod, op_base(name))][1] += 1
            labelled.append((s, e, f"{mod}:{op_label(name)}"))
        for label, t in _exclusive(labelled):
            excl[label] += t / 1e9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps[_host_at(trace.host, (s + e) / 2)] += (e - s) / 1e9
    n = len(chips)
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return Reduction(
        window_s=(hi - lo) / 1e9, busy_s=busy / n / 1e9, kernel=dict(kernel),
        device_ops=[(k, v / n) for k, v in rank(excl)],
        idle_gaps=[(k, v / n) for k, v in rank(gaps)])
