"""Readers of what the program itself records: the admission stamp, the
compile log and the collection log, on hand-made window records."""

import bench_helpers  # noqa: F401  (the repository root on the path)

import sys
import time
import types

import pytest

from bench import gen, harness, run
from bench.peaks import PEAKS
from bench.readers import Run
from repro.launch.tracing import host_log

CELL = "mamba2-780m.chat-burst"


def _req(i, due, admitted=None, first=None):
    spec = gen.RequestSpec(index=i, prompt=None, max_new=1, offset_s=due)
    req = types.SimpleNamespace(admit_t=admitted, first_token_t=first)
    return harness.ReqRecord(spec=spec, due=due, req=req, accepted=True)


def _run(t0=0.0, seconds=10.0, **kw):
    rec = harness.WindowRecord(cell=CELL, t0=t0, t_end=t0 + seconds, seconds=seconds,
                               t_stop=t0 + seconds + 2.0, requests={}, prefill=[],
                               decode=[], ticks=[], page_samples=[], slots=4, chips=1)
    for k, v in kw.items():
        setattr(rec, k, v)
    return Run(rec, harness.load_cell(CELL), PEAKS["TPU v5 lite"])


def read(name, r):
    return run.read_metric(name, r)


def test_prefill_phase_counts_admitted_requests_due_in_the_window():
    reqs = {i: _req(i, due=float(i), admitted=i + 0.5, first=i + 0.5 + 0.1 * (i + 1))
            for i in range(9)}
    reqs[9] = _req(9, due=9.0)                      # never admitted: not counted
    reqs[10] = _req(10, due=10.5, admitted=10.6, first=20.0)   # due after the close
    r = _run(requests=reqs)
    assert read("prefill_phase_p90_ms", r) == pytest.approx(900.0)
    reqs[9].req.admit_t = 9.5                       # admitted, no first token: 12 - 9.5
    reqs[8].req.first_token_t = None                # 12 - 8.5
    assert read("prefill_phase_p90_ms", r) == pytest.approx(2500.0)


def test_prefill_phase_needs_the_admission_stamp():
    reqs = {0: _req(0, due=0.0, first=1.0)}
    reqs[0].req = types.SimpleNamespace(first_token_t=1.0)     # a program without it
    assert read("prefill_phase_p90_ms", _run(requests=reqs)) is None


@pytest.fixture
def log():
    """The process's log, with what a test adds to it taken out again."""
    log = host_log()
    compiles, collections = list(log.compiles), list(log.collections)
    yield log
    log.compiles.clear()
    log.compiles.extend(compiles)
    log.collections.clear()
    log.collections.extend(collections)


def test_compiles_and_pauses_that_end_inside_the_window(log):
    t0 = time.perf_counter() + 1e6                  # nothing real ends there
    log.compiles.extend([(t0 - 0.5, 2.0, "before"), (t0 + 1.0, 0.5, "f"),
                         (t0 + 9.0, 3.0, "g"), (t0 + 10.5, 0.1, "after")])
    # a pause counts whole where it ends, as a compile does
    log.collections.extend([(t0 - 0.2, t0 + 0.1), (t0 + 4.0, t0 + 4.25),
                            (t0 + 9.9, t0 + 10.2)])
    r = _run(t0=t0)
    assert read("compiles_in_window", r) == 2
    assert read("gc_pause_ms", r) == pytest.approx(550.0)


def test_an_empty_log_reads_zero(log):
    r = _run(t0=time.perf_counter() + 1e6)
    assert read("compiles_in_window", r) == 0
    assert read("gc_pause_ms", r) == 0.0


@pytest.mark.parametrize("name", ["compiles_in_window", "gc_pause_ms"])
def test_no_log_reads_nothing(name, log, monkeypatch):
    # a window opened before the log began listening is not covered
    assert read(name, _run(t0=log.since - 1.0)) is None
    # a program without the log (the import fails)
    monkeypatch.setitem(sys.modules, "repro.launch.tracing", None)
    assert read(name, _run(t0=time.perf_counter() + 1e6)) is None
