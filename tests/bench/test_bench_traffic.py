"""The traffic generator: determinism, the same work on every seed,
clipping, burstiness, and the closed loop's clients."""

import bench_helpers  # noqa: F401  (the repository root on the path)

import json
from pathlib import Path

import numpy as np
import pytest

from bench import gen, harness

stratified = harness.plugin("traffic", "stratified")
TRAFFIC = Path(__file__).resolve().parents[2] / "bench" / "traffic"
OPEN = {"loop": "open", "arrivals": {"rate": 4.0, "shape": 0.25}, "block": 16,
        "prompt": {"median": 100, "sigma": 0.9, "min": 40, "max": 300},
        "output": {"median": 20, "sigma": 0.6, "min": 8, "max": 40}}


def _key(reqs):
    return [(r.prompt.tolist(), r.max_new, r.offset_s) for r in reqs]


def test_same_seed_same_requests():
    a = stratified.make_requests(OPEN, 2**31 + 11, 1000, 40)
    b = stratified.make_requests(OPEN, 2**31 + 11, 1000, 40)
    assert _key(a) == _key(b)
    assert _key(a) != _key(stratified.make_requests(OPEN, 12, 1000, 40))


def test_every_seed_gets_the_same_work_per_block():
    schedule = None
    for seed in (1, 2, 2**33 + 5):
        reqs = stratified.make_requests(OPEN, seed, 1000, 32)
        # the seed draws the tokens; lengths, gaps and their order are fixed
        got = [(len(r.prompt), r.max_new, r.offset_s) for r in reqs]
        assert schedule is None or got == schedule
        schedule = got
        prompts, outputs = (sorted(stratified.lengths(OPEN[k], 16)) for k in ("prompt", "output"))
        for blk in (reqs[:16], reqs[16:]):
            assert sorted(len(r.prompt) for r in blk) == prompts
            assert sorted(r.max_new for r in blk) == outputs
        assert reqs[0].offset_s == 0.0
        # each block of gaps offers exactly `rate`
        first = np.diff([r.offset_s for r in reqs[:17]])
        assert first.sum() == pytest.approx(16 / OPEN["arrivals"]["rate"])


def test_lengths_are_clipped_lognormal():
    v = stratified.lengths({"median": 100, "sigma": 2.0, "min": 40, "max": 300}, 64)
    assert v.min() == 40 and v.max() == 300
    assert np.median(v) == pytest.approx(100, rel=0.05)
    assert np.all(np.diff(v) >= 0)


def test_gamma_gaps_have_the_stated_burstiness():
    g = stratified.gaps({"rate": 5.0, "shape": 0.25}, 4096)
    assert g.mean() == pytest.approx(0.2)
    assert g.std() / g.mean() == pytest.approx(2.0, rel=0.05)
    p = stratified.gaps({"rate": 5.0, "shape": 1.0}, 4096)
    assert p.std() / p.mean() == pytest.approx(1.0, rel=0.02)


def test_tokens_within_vocab_and_closed_loop_has_no_schedule():
    closed = dict(OPEN, loop="closed", clients=4)
    reqs = stratified.make_requests(closed, 3, 50, 20)
    assert all(r.offset_s is None for r in reqs)
    assert all(0 <= r.prompt.min() and r.prompt.max() < 50 for r in reqs)


@pytest.mark.parametrize("path", sorted(TRAFFIC.glob("*.json")), ids=lambda p: p.stem)
def test_mix_files_load(path):
    mix = gen.load_mix(path)
    assert callable(harness.generator(mix).requests)
    assert mix["block"] >= 8
    for k in ("prompt", "output"):
        assert mix[k]["min"] <= mix[k]["median"] <= mix[k]["max"]
    assert json.loads(path.read_text())["why"]


def test_closed_loop_keeps_each_client_to_one_request():
    """A tiny closed-loop run: at the window's start every client has
    sent one request, and no more than `clients` are ever in flight."""
    from bench_helpers import tiny_cell
    from repro.core.platform import POD_SIM

    cell, cfg = tiny_cell("granite-3-8b", loop="closed")
    bench = cell.driver.Driver(cell, provider="pallas-interpret", platform=POD_SIM,
                               program_config=cfg)
    try:
        bench.set_weights(5)
        bench.warm()
        rec = bench.window(5, 1.0)
    finally:
        bench.close()
    reqs = sorted(rec.requests.values(), key=lambda r: r.due)
    clients = cell.mix["clients"]
    assert [r.due for r in reqs[:clients]] == [rec.t0] * clients
    assert len(reqs) > clients
    for r in reqs:
        live = sum(1 for o in reqs if o.due <= r.due and not (o.req.done and o.times[-1] <= r.due))
        assert live <= clients
