"""The default driver: one ``Server`` on the cell's chips.

The timed path is the program's own: ``Runtime.deploy`` ->
``Server.submit`` / ``Server.step`` -> ``Scheduler.tick`` ->
``JaxEngine.prefill_step`` / ``decode_step`` -> the paged pool -> the
bound Pallas kernels.  The driver makes the weights from the seed, wraps
the engine's two steps from outside to time them (and, in a traced run,
to write host spans), and stamps every tick's emitted tokens with the
host clock when ``Server.step`` returns them.  Open-loop mixes submit
each request when it is due; closed-loop mixes keep ``clients`` requests
in flight, each client sending its next when its last is done.
"""

from __future__ import annotations

import gc
import time
from collections import deque
from typing import Callable

import numpy as np

from bench import harness

__all__ = ["Driver"]


class Driver:
    """One cell deployed on its chips: weights from a seed, warm-up,
    measured windows, and the state freed for the reference."""

    def __init__(self, cell: harness.Cell, *, provider: str = "pallas-tpu",
                 platform=None, t_proc: float | None = None,
                 program_config=None):
        import jax

        from repro.core import Runtime
        from repro.launch.serve import Server
        from repro.launch.train import make_bundle, serving_config

        self.cell = cell
        self.t_proc = time.perf_counter() if t_proc is None else t_proc
        d = cell.deploy
        self.devices = jax.devices()[: cell.chips]
        t = time.perf_counter()
        arch, layers = cell.spec["arch"], cell.dims["layers"]
        cfg = program_config or serving_config(arch, num_layers=layers)
        cell.family.check_program_config(cell.spec, cfg)
        self.cfg = cfg
        self.runtime = Runtime(host_env={})
        bundle = make_bundle(arch, reduced=program_config is not None,
                             num_layers=None if program_config is not None else layers)
        container = self.runtime.deploy(bundle, devices=self.devices, platform=platform,
                                        native_ops=True, autotune=False, profile=False)
        wrong = [f"{r.op}={r.bound}" for r in container.binding.reports
                 if r.bound != provider or not r.swapped]
        if wrong:
            raise harness.BenchError(f"ops not bound to {provider}: {wrong}")
        self.server = Server(cfg, container, slots=d["slots"], max_len=d["max_len"],
                             chunk=d["chunk"], paged=True, num_pages=d.get("num_pages"),
                             queue_depth=d["queue_depth"], interleave=d["interleave"],
                             clock=time.perf_counter)
        self.engine = self.server.engine
        self._abstract = jax.eval_shape(lambda: self.engine.params)
        self.engine.params = None            # the program's own seed-0 weights
        gc.collect()
        self.deploy_s = time.perf_counter() - t
        self.warmup_s = 0.0
        self._prefill_step = self.engine.prefill_step
        self._decode_step = self.engine.decode_step

    # -- weights and warm-up ------------------------------------------------
    def set_weights(self, seed: int) -> None:
        """The cell's weights from `seed`, made on the device in the
        served dtype by one jitted call, in the engine's sharding."""
        import functools

        import jax

        t = time.perf_counter()
        self.engine.params = None
        gc.collect()
        make = jax.jit(functools.partial(self.cell.family.init_weights, self.cell.spec,
                                         self._abstract),
                       out_shardings=self.engine.dep.param_sharding)
        self.engine.params = jax.block_until_ready(make(harness.weights_key(seed)))
        self.deploy_s += time.perf_counter() - t

    @property
    def weights(self):
        return self.engine.params

    def warm(self) -> None:
        """The first call of both compiled steps, on the park page with
        every row inactive: no slot state changes, and nothing compiles
        in the window."""
        d = self.cell.deploy
        t = time.perf_counter()
        self._prefill_step(0, np.zeros(1, np.int32), 0)
        self._decode_step(np.zeros((d["slots"], 1), np.int32),
                          np.full(d["slots"], d["max_len"] - 1, np.int32),
                          np.zeros(d["slots"], bool))
        self.warmup_s += time.perf_counter() - t

    # -- the measured window --------------------------------------------------
    def _instrument(self, rec: harness.WindowRecord, spans: bool) -> None:
        from contextlib import nullcontext

        from jax.profiler import TraceAnnotation

        span = TraceAnnotation if spans else (lambda name: nullcontext())
        engine, prefill, decode = self.engine, self._prefill_step, self._decode_step
        jit_decode, jit_prefill = engine._decode, engine._prefill

        def prefill_step(slot, tokens, pos):
            t = time.perf_counter()
            with span("bench.prefill_step"):
                out = prefill(slot, tokens, pos)
            rec.prefill.append((t, time.perf_counter(), int(len(tokens)), int(pos)))
            return out

        def decode_step(tokens, pos, active):
            t = time.perf_counter()
            with span("bench.decode_step"):
                out = decode(tokens, pos, active)
            rec.decode.append((t, time.perf_counter(),
                               [int(p) + 1 for p, a in zip(pos, active) if a]))
            return out

        engine.prefill_step, engine.decode_step = prefill_step, decode_step
        if spans:
            # the dispatch of each jitted step, so that what is left of the
            # step's span is the host's own work and the logits pull
            def dispatch(fn, name):
                def wrapped(*a, **k):
                    with TraceAnnotation(name):
                        return fn(*a, **k)
                return wrapped
            engine._decode = dispatch(jit_decode, "bench.dispatch_decode")
            engine._prefill = dispatch(jit_prefill, "bench.dispatch_prefill")
        self._restore = (jit_prefill, jit_decode)

    def _uninstrument(self) -> None:
        self.engine.prefill_step = self._prefill_step
        self.engine.decode_step = self._decode_step
        self.engine._prefill, self.engine._decode = self._restore

    def window(self, seed: int, seconds: float, *, trace: bool = False,
               mix: dict | None = None, drain: bool = True,
               hook: Callable | None = None) -> harness.WindowRecord:
        """Offer the cell's traffic for `seconds`, then keep serving (and
        offering) until every request due in the window has its first
        token, or DRAIN_MAX_S past the close."""
        from contextlib import nullcontext

        from jax.profiler import TraceAnnotation

        from repro.launch.serve import QUEUED, Request

        span = TraceAnnotation if trace else (lambda name: nullcontext())
        mix = mix or self.cell.mix
        d = self.cell.deploy
        sched = self.server.scheduler
        specs = harness.generator(mix, self.cell.root).requests(
            mix, seed, self.cell.dims.get("token_vocab", self.cell.dims["vocab"]), seconds)
        rec = harness.WindowRecord(cell=self.cell.name, t0=0.0, t_end=0.0, seconds=seconds,
                           requests={}, prefill=[], decode=[], ticks=[],
                           page_samples=[], slots=d["slots"], chips=self.cell.chips,
                           deploy_s=self.deploy_s, warmup_s=self.warmup_s)
        self._instrument(rec, spans=trace)
        if hook is not None:
            hook(self.engine)
        pending = deque(specs)
        queued: dict = {}
        tracer = harness.Tracer() if trace else None
        samples0 = len(sched.page_samples)

        def submit(spec, due):
            r = harness.ReqRecord(spec=spec, due=due)
            r.req = Request(rid=spec.index, prompt=spec.prompt, max_new=spec.max_new)
            r.accepted = self.server.submit(r.req)
            rec.requests[spec.index] = r
            if due < rec.t_end:
                rec.late_s = max(rec.late_s, time.perf_counter() - due)
            if r.accepted:
                queued[spec.index] = r
            return r

        t0 = time.perf_counter()
        rec.t0, rec.t_end = t0, t0 + seconds
        rec.setup_s = t0 - self.t_proc
        closed = mix["loop"] == "closed"
        if closed:
            for _ in range(mix["clients"]):
                submit(pending.popleft(), t0)
        try:
            while True:
                now = time.perf_counter()
                if tracer is not None:
                    tracer.poll(now, rec)
                if now >= rec.t_end:
                    waiting = [r for r in rec.due_in_window if r.accepted and not r.times]
                    if not drain or not waiting or now >= rec.t_end + harness.DRAIN_MAX_S:
                        break
                if not closed:
                    while pending and t0 + pending[0].offset_s <= now:
                        spec = pending.popleft()
                        submit(spec, t0 + spec.offset_s)
                if sched.idle:
                    # sleep until the next arrival, waking at least every
                    # 5 ms for the close and the start of the trace
                    nxt = t0 + pending[0].offset_s if pending and not closed else now
                    wake = min(nxt, now + 0.005)
                    with span("bench.wait_arrival"):
                        time.sleep(max(0.0, wake - time.perf_counter()))
                    continue
                ts = time.perf_counter()
                with span("bench.tick"):
                    emitted = self.server.step()
                te = time.perf_counter()
                rec.ticks.append((ts, te))
                for rid, _tok in emitted:
                    rec.requests[rid].times.append(te)
                for rid in [rid for rid, r in queued.items() if r.req.state != QUEUED]:
                    queued.pop(rid).admitted = ts
                if closed:
                    # a client sends its next request when its last one is done
                    for rid in {rid for rid, _ in emitted}:
                        if rec.requests[rid].req.done and pending:
                            submit(pending.popleft(), te)
        finally:
            rec.t_stop = time.perf_counter()
            if tracer is not None:
                tracer.stop(rec)
            self._uninstrument()
        if tracer is not None:
            tracer.reduce(rec)
        rec.page_samples = list(sched.page_samples[samples0:])
        return rec

    def reset(self) -> None:
        """Abandon the window's requests: a fresh page pool and scheduler
        on the same engine (a slot's state is reset by its next prefill
        at position 0).  For calibration only; a benchmark run serves
        one window."""
        from repro.launch.serve import PagedPool, Scheduler

        d, eng = self.cell.deploy, self.engine
        eng.pool = PagedPool(eng.slots, eng.max_len, eng.chunk, eng.pool.num_pages)
        self.server.scheduler = Scheduler(eng, queue_depth=d["queue_depth"],
                                          interleave=d["interleave"], clock=time.perf_counter)
        self.server.requests = []

    def memory_peak(self) -> int | None:
        peaks = [(dev.memory_stats() or {}).get("peak_bytes_in_use")
                 for dev in self.devices]
        peaks = [p for p in peaks if p is not None]
        return max(peaks) if peaks else None

    def free_program_state(self) -> None:
        """Free the engine's cache before the reference runs; the
        weights are the benchmark's and stay."""
        self.engine.cache = None
        gc.collect()

    def close(self) -> None:
        self.engine.params = None
        self.engine.cache = None
        self.runtime.cleanup()
        gc.collect()
