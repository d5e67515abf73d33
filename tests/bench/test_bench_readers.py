"""Metric readers on a hand-made window record."""

import bench_helpers  # noqa: F401  (the repository root on the path)

import json
from pathlib import Path

import pytest

from bench import gen, harness, run, trace as tr, work
from bench.peaks import PEAKS
from bench.readers import Run

ROOT = Path(__file__).resolve().parents[2]
P = PEAKS["TPU v5 lite"]


def _req(i, due, times, admitted=None, accepted=True):
    spec = gen.RequestSpec(index=i, prompt=None, max_new=len(times), offset_s=due)
    return harness.ReqRecord(spec=spec, due=due, accepted=accepted, admitted=admitted,
                             times=list(times))


def _run(cell="granite-3-8b.long-prompt", **kw):
    rec = harness.WindowRecord(cell=cell, t0=0.0, t_end=10.0, seconds=10.0, t_stop=12.0,
                               requests={}, prefill=[], decode=[], ticks=[],
                               page_samples=[], slots=4, chips=1)
    for k, v in kw.items():
        setattr(rec, k, v)
    return Run(rec, harness.load_cell(cell), P)


def read(name, r):
    return run.read_metric(name, r)


def test_ttft_counts_due_requests_and_missing_ones_as_waited():
    reqs = {i: _req(i, due=float(i), times=[i + 0.1 * (i + 1)]) for i in range(9)}
    reqs[9] = _req(9, due=9.0, times=[])            # never served: waited 12 - 9
    reqs[10] = _req(10, due=10.5, times=[10.6])     # due after the close: not counted
    r = _run(requests=reqs)
    assert read("ttft_p90_ms", r) == pytest.approx(900.0)
    reqs[8].times = [8.05]
    assert read("ttft_p90_ms", r) == pytest.approx(800.0)
    reqs[9].times = [20.0]                          # late, beyond the p90 rank
    assert read("ttft_p90_ms", r) == pytest.approx(800.0)
    reqs[7].times = []                              # a second one waits 12 - 7
    assert read("ttft_p90_ms", r) == pytest.approx(5000.0)


def test_itl_and_tokens_use_only_the_window():
    reqs = {0: _req(0, 0.0, [1.0, 1.5, 1.5, 2.5, 10.5]), 1: _req(1, 0.0, [9.0, 9.2])}
    r = _run(requests=reqs)
    # gaps 0.5, 0.0, 1.0, 0.2 (10.5 lies past the close)
    assert read("itl_p99_ms", r) == pytest.approx(1000.0)
    assert read("output_tokens_per_s", r) == pytest.approx(6 / 10.0)


def test_queue_wait_occupancy_and_step_times():
    reqs = {0: _req(0, 0.0, [1.0], admitted=0.5), 1: _req(1, 1.0, [], admitted=None)}
    decode = [(1.0, 1.02, [5, 9]), (2.0, 2.04, [6]), (11.0, 11.5, [1, 2, 3, 4])]
    prefill = [(0.1, 0.13, 128, 0), (0.2, 0.24, 100, 128)]
    r = _run(requests=reqs, decode=decode, prefill=prefill)
    assert read("queue_wait_p90_ms", r) == pytest.approx(11000.0)
    assert read("batch_occupancy", r) == pytest.approx(100 * 3 / 8)
    assert read("decode_step_ms", r) == pytest.approx(30.0)
    assert read("prefill_step_ms", r) == pytest.approx(35.0)
    spec = r.cell.spec
    flops = r.cell.family.prefill_flops(spec, 128, 0) + r.cell.family.prefill_flops(spec, 100, 128)
    assert read("mfu.prefill", r) == pytest.approx(100 * flops / (0.07 * P.flops_bf16))


def test_pages_written_share_keeps_the_window_ticks():
    r = _run(ticks=[(0.0, 1.0), (1.0, 2.0), (10.0, 10.5)], page_samples=[(4, 3), (4, 1), (8, 0)])
    assert read("pages_written_share", r) == pytest.approx(50.0)
    assert read("pages_written_share", _run()) is None


def test_roofline_share_needs_matching_kernel_events():
    d = harness.load_cell("granite-3-8b.long-prompt").dims
    calls = [(1.0, 1.03, [100, 200])]
    w = work.attention_decode([100, 200], heads=d["heads"], kv_heads=d["kv_heads"],
                              head_dim=d["head_dim"])
    need = work.roofline_time(w, P)[0] * d["attn_layers"]
    red = tr.Reduction(window_s=2.0, busy_s=1.5, device_ops=[], idle_gaps=[],
                       kernel={("decode", "flash_attention"): [4 * need, d["attn_layers"]]})
    r = _run("granite-3-8b.long-prompt", decode=calls, trace=red, traced=(0.5, 1.5))
    assert read("attn_decode_roofline", r) == pytest.approx(25.0)
    assert read("device_idle_share", r) == pytest.approx(25.0)
    red.kernel[("decode", "flash_attention")][1] -= 1       # a layer missing
    assert read("attn_decode_roofline", r) is None
    assert read("attn_prefill_roofline", r) is None          # no prefill traced
    assert read("attn_decode_roofline", _run("granite-3-8b.long-prompt")) is None


def test_every_reader_reads_nothing_from_an_empty_window():
    names = [p.stem for p in (ROOT / "bench" / "metrics").glob("*.py")]
    assert set(names) >= {m["name"] for m in json.loads(
        (ROOT / "BENCHMARK.json").read_text())["per_layer"]}
    r = _run()
    for name in names:
        if name not in ("deploy_s", "warmup_s", "setup_s", "output_tokens_per_s"):
            assert read(name, r) is None, name
