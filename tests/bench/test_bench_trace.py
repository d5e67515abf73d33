"""The trace reduction, on a small recorded trace and on hand-made ones."""

import bench_helpers  # noqa: F401  (the repository root on the path)

from pathlib import Path

import numpy as np
import pytest

from bench import trace as tr

DATA = Path(__file__).resolve().parents[2] / "bench" / "data" / "granite_trace_small.json.gz"


@pytest.fixture(scope="module")
def recorded():
    t = tr.load_json(DATA)
    ticks = [h for h in t.host if h[2] == "bench.tick"]
    return t, (ticks[0][0], ticks[-1][1])


def test_names():
    assert tr.op_base("%flash_attention.15 = bf16[8,1,32,128]{3,2,1,0} custom-call(s32[31,8])") \
        == "flash_attention"
    assert tr.op_base("%copy-start.2 = (bf16[4096]) copy-start(...)") == "copy-start"
    assert tr.op_base("%while.5 = (s32[], bf16[1,128,4096])") == "while"
    assert tr.op_base("%fusion = f32[2]") == "fusion"
    assert tr.op_label("%fusion.97 = bf16[128,12800] fusion(x)") == "fusion.97"
    assert tr.module_base("jit_prefill_into(18287758729998928016)") == "prefill_into"
    assert tr.module_base("jit_decode(1)") == "decode"


def _raster(intervals, lo, hi):
    """Busy microseconds by brute force: a 1 us grid over the window."""
    grid = np.zeros(int((hi - lo) // 1000) + 1, bool)
    for s, e, *_ in intervals:
        a, b = int((max(s, lo) - lo) // 1000), int((min(e, hi) - lo) // 1000)
        if b > a:
            grid[a:b] = True
    return grid.sum() * 1e-6


def test_idle_share_matches_brute_force(recorded):
    t, (lo, hi) = recorded
    red = tr.reduce(t, (lo, hi))
    ops = next(iter(t.ops.values()))
    assert red.window_s == pytest.approx((hi - lo) / 1e9)
    assert red.busy_s == pytest.approx(_raster(ops, lo, hi), abs=2e-4)
    assert 0.0 < red.idle_share < 1.0


def test_kernel_time_split_by_enclosing_program(recorded):
    t, (lo, hi) = recorded
    red = tr.reduce(t, (lo, hi))
    mods = next(iter(t.modules.values()))
    want = {"prefill_into": [0.0, 0], "decode": [0.0, 0]}
    for s, e, name in next(iter(t.ops.values())):
        if not name.startswith("%flash_attention") or e <= lo or s >= hi:
            continue
        owner = [tr.module_base(m[2]) for m in mods if m[0] <= s < m[1]]
        want[owner[0]][0] += (min(e, hi) - max(s, lo)) / 1e9
        want[owner[0]][1] += 1
    for mod in want:
        got = red.kernel[(mod, "flash_attention")]
        assert got[1] == want[mod][1] > 0
        assert got[0] == pytest.approx(want[mod][0])
    # 20 attention layers: one kernel event per layer per step
    assert red.kernel[("prefill_into", "flash_attention")][1] % 20 == 0
    assert red.kernel[("decode", "flash_attention")][1] % 20 == 0


def test_exclusive_time_and_gap_attribution():
    # one program: a loop op that spans two kernels, then an idle gap
    # while the host pulls logits, then another op
    t = tr.Trace(
        modules={"/device:TPU:0": [(0, 100, "jit_decode(1)"), (150, 200, "jit_decode(1)")]},
        ops={"/device:TPU:0": [(0, 100, "%while.1 = ()"), (10, 40, "%flash_attention.2 = x"),
                               (50, 90, "%fusion.3 = y"), (150, 200, "%fusion.3 = y")]},
        host=[(0, 210, "bench.tick"), (95, 140, "bench.decode_step")])
    red = tr.reduce(t, (0, 200))
    assert red.busy_s == pytest.approx(150e-9)
    ops = dict(red.device_ops)
    assert ops["decode:while.1"] == pytest.approx(30e-9)        # 100 - 30 - 40
    assert ops["decode:fusion.3"] == pytest.approx(90e-9)
    assert dict(red.idle_gaps) == {"decode_step": pytest.approx(50e-9)}
    assert red.kernel[("decode", "flash_attention")] == [pytest.approx(30e-9), 1]


def test_no_device_ops_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce(tr.Trace({}, {}, []), (0, 1))
