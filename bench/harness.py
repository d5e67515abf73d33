"""What every cell shares: finding its files by name, and the records
that a measured window leaves for the metric readers.

A cell is named in ``BENCHMARK.json``.  Everything that belongs to it is
found by name, as a file of its own:

* its configuration ``bench/configs/<config>.json``, whose ``family``
  names ``bench/models/<family>.py`` (the benchmark's weights, the plain
  reference and the model FLOPs);
* its traffic mix ``bench/traffic/<traffic>.json``, whose ``generator``
  names ``bench/traffic/<generator>.py`` (default ``stratified``);
* its deployment ``bench/cells/<workload>.json``, whose ``driver`` names
  ``bench/drivers/<driver>.py`` (default ``server``: one ``Server`` on
  the cell's chips);
* one reader ``bench/metrics/<metric>.py`` per metric.

Adding a cell, a mix, a generator, a driver, a metric or a configuration
adds files and entries and edits none.

A driver module holds ``Driver(cell, *, provider, platform, t_proc,
program_config)`` with ``set_weights(seed)``, ``warm()``,
``window(seed, seconds, *, trace, hook) -> WindowRecord``,
``memory_peak()``, ``free_program_state()``, ``weights``, ``devices``
and ``close()``.  A generator module holds ``requests(mix, seed, vocab,
seconds) -> list[RequestSpec]``: enough requests, in send order, for a
window of ``seconds`` and the drain after it.
"""

from __future__ import annotations

import dataclasses
import glob
import importlib.util
import json
import os
import shutil
import tempfile
import time
from pathlib import Path

import numpy as np

from bench import gen

ROOT = Path(__file__).resolve().parents[1]
DRAIN_MAX_S = 60.0      # how long past the close a due request may wait
TRACE_S = 8.0           # traced seconds at the end of a --trace 1 window

__all__ = ["Cell", "load_cell", "plugin", "generator", "WindowRecord", "ReqRecord",
           "Tracer", "weights_key", "ROOT"]


class BenchError(RuntimeError):
    """The run cannot produce a result (no chip, a wrong binding, ...)."""


_PLUGINS: dict = {}


def plugin(kind: str, name: str, root: Path = ROOT):
    """The module ``bench/<kind>/<name>.py`` under `root`, loaded from
    its file (a name may hold dots), once per file."""
    path = (Path(root) / "bench" / kind / f"{name}.py").resolve()
    mod = _PLUGINS.get(path)
    if mod is None:
        if not path.is_file():
            raise BenchError(f"no {kind} module {name!r} ({path} is missing)")
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _PLUGINS[path] = mod
    return mod


def generator(mix: dict, root: Path = ROOT):
    """The generator module that reads `mix`."""
    return plugin("traffic", mix.get("generator", "stratified"), root)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    spec: dict              # configuration file
    mix: dict               # traffic file
    deploy: dict            # cell file
    family: object          # bench/models/<family>.py
    root: Path = ROOT       # where its files were found

    @property
    def dims(self) -> dict:
        return self.family.dims(self.spec)

    @property
    def driver(self):
        return plugin("drivers", self.deploy.get("driver", "server"), self.root)

    @property
    def generator(self):
        return generator(self.mix, self.root)


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def load_cell(name: str, root: Path = ROOT) -> Cell:
    root = Path(root)
    bench = load_benchmark(root)
    wl = next((w for w in bench["workloads"] if w["name"] == name), None)
    if wl is None:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json; known: "
                         f"{[w['name'] for w in bench['workloads']]}")
    conf = next(c for c in bench["configs"] if c["name"] == wl["config"])
    spec = json.loads((root / conf["file"]).read_text())
    mix = gen.load_mix(root / "bench" / "traffic" / f"{wl['traffic']}.json")
    deploy = json.loads((root / "bench" / "cells" / f"{name}.json").read_text())
    family = plugin("models", spec["family"], root)
    return Cell(name, wl["chips"], spec, mix, deploy, family, root)


def weights_key(seed: int):
    """A PRNG key for any whole-number seed (more than 32 bits too)."""
    import jax
    import jax.numpy as jnp

    words = np.random.SeedSequence(int(seed) % (1 << 64)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32), impl="threefry2x32")


@dataclasses.dataclass
class ReqRecord:
    spec: gen.RequestSpec
    due: float
    req: object = None                      # the program's Request
    accepted: bool = False
    admitted: float | None = None           # start of the tick that admitted it
    times: list = dataclasses.field(default_factory=list)   # token stamps

    @property
    def first(self) -> float | None:
        return self.times[0] if self.times else None


@dataclasses.dataclass
class WindowRecord:
    """Everything one measured window recorded, for the metric readers."""
    cell: str
    t0: float
    t_end: float
    seconds: float
    requests: dict                          # rid -> ReqRecord
    prefill: list                           # (t_start, t_end, n, pos)
    decode: list                            # (t_start, t_end, contexts)
    ticks: list                             # (t_start, t_end)
    page_samples: list                      # (allocated, written) per tick
    slots: int
    chips: int
    deploy_s: float = 0.0
    warmup_s: float = 0.0
    setup_s: float = 0.0
    trace: object = None                    # bench.trace.Reduction
    traced: tuple | None = None             # (t0, t1) host clock of the trace
    late_s: float = 0.0                     # how late the generator submitted
    t_stop: float = 0.0                     # when serving stopped

    def in_window(self, t: float) -> bool:
        return self.t0 <= t <= self.t_end

    @property
    def due_in_window(self) -> list:
        return [r for r in self.requests.values() if r.due < self.t_end]


class Tracer:
    """Profiles the last TRACE_S seconds of the window.  The trace stops
    at the close, so the time stop_trace takes falls in the drain."""

    def __init__(self):
        self.state = "idle"
        self.dir = None
        self.span = None

    def poll(self, now, rec) -> None:
        import jax
        from jax.profiler import TraceAnnotation

        start = rec.t_end - min(TRACE_S, rec.seconds / 2)
        if self.state == "idle" and now >= start:
            self.dir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(self.dir)
            self.span = TraceAnnotation("bench.window")
            self.span.__enter__()
            self.t0 = time.perf_counter()
            self.state = "on"
        elif self.state == "on" and now >= rec.t_end:
            self.stop(rec)

    def stop(self, rec) -> None:
        import jax

        if self.state != "on":
            return
        t1 = time.perf_counter()
        self.span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state = "stopped"
        rec.traced = (self.t0, t1)

    def reduce(self, rec) -> None:
        """Read the trace once serving has stopped (reading it takes
        seconds, which would otherwise hold up the drain)."""
        from bench import trace as tr

        if self.state != "stopped":
            return
        try:
            path = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"), recursive=True)
            if not path:
                raise BenchError("the profiler wrote no trace")
            trace = tr.load(path[0])
            window = next(((s, e) for s, e, n in trace.host if n == "bench.window"), None)
            if window is None:
                raise BenchError("the trace holds no bench.window span")
            rec.trace = tr.reduce(trace, window)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
