"""What the serving host does while the chip waits: spans and a log.

**Spans.**  `span(name)` is ``jax.profiler.TraceAnnotation``: a host span
written into the profiler's own trace, on the clock of the device's
events, so that an idle gap on the device can be charged to the host
phase that was open.  With no profiler session active a span costs its
constructor and nothing else, so the serving loop keeps them always; the
names are literals, never formatted per call.  They sit at phase level,
never one per row or token (docs/serving.md, "Tracing a server"):

    repro.tick                  Scheduler.tick
      repro.admit               queue -> free slots
      repro.prefill             one prefill unit
        repro.prefill.upload    host arrays -> device
        repro.prefill.dispatch  the jitted call, until it returns
        repro.prefill.wait      until the last token's logits are ready
        repro.prefill.pull      those logits -> host
        repro.sample            argmax and emit of the first token
      repro.decode              batch assembly and the engine call
        repro.decode.upload / .dispatch / .wait / .pull
      repro.sample              argmax and emit of the decode rows
      repro.pages               the page sample
    repro.gc                    a full (generation-2) collection

**Log.**  `host_log()` is the process's one `HostLog`: every XLA compile
(a load from the persistent cache too) and every full collection, their
ends on `time.perf_counter`, so that a window on that clock can count
what fell inside it.
"""

from __future__ import annotations

import gc
import time
from collections import deque

import jax
from jax.profiler import TraceAnnotation

__all__ = ["span", "HostLog", "host_log"]

span = TraceAnnotation

# recorded around every backend compile, a persistent-cache load included
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class HostLog:
    """Compilations and full collections, newest last, at most `maxlen`
    of each.

    ``compiles``: (end, seconds, function) per backend compile.
    ``collections``: (start, end) per generation-2 collection, each also
    written as a ``repro.gc`` span.  ``since``: when listening began; a
    window that starts earlier is not covered.
    """

    def __init__(self, maxlen: int = 4096):
        self.since = time.perf_counter()
        self.compiles: deque[tuple[float, float, str]] = deque(maxlen=maxlen)
        self.collections: deque[tuple[float, float]] = deque(maxlen=maxlen)
        self._gc: tuple[float, TraceAnnotation] | None = None

    def on_duration(self, event: str, seconds: float, **kwargs) -> None:
        """A ``jax.monitoring`` duration listener."""
        if event == COMPILE_EVENT:
            self.compiles.append((time.perf_counter(), seconds,
                                  str(kwargs.get("fun_name", "?"))))

    def on_gc(self, phase: str, info: dict) -> None:
        """A ``gc.callbacks`` hook: spans and logs full collections."""
        if info["generation"] != 2:
            return
        if phase == "start":
            self._gc = (time.perf_counter(), span("repro.gc"))
        elif self._gc is not None:
            start, s = self._gc
            s.__exit__(None, None, None)
            self.collections.append((start, time.perf_counter()))
            self._gc = None


_LOG: HostLog | None = None


def host_log() -> HostLog:
    """The process's log, listening from its first call on: the compile
    listener and the collection hook are registered once per process."""
    global _LOG
    if _LOG is None:
        _LOG = HostLog()
        jax.monitoring.register_event_duration_secs_listener(_LOG.on_duration)
        gc.callbacks.append(_LOG.on_gc)
    return _LOG
