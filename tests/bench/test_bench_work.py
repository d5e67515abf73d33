"""Operation and byte counts against hand counts at small shapes."""

import bench_helpers  # noqa: F401  (the repository root on the path)

import pytest

from bench import work
from bench.models import dense, mamba2
from bench.peaks import PEAKS, peaks_for


def test_attention_chunk_hand_count():
    # 2 queries at positions 3 and 4 attend 4 and 5 keys: 9 pairs;
    # 2 heads of 4, 1 kv head
    w = work.attention_chunk(2, 3, heads=2, kv_heads=1, head_dim=4)
    assert w.flops == 4 * 2 * 4 * 9
    assert w.bytes == 2 * (2 * 2 * 2 * 4 + 2 * 5 * 1 * 4)


def test_attention_decode_counts_active_rows_only():
    w = work.attention_decode([5, 1], heads=2, kv_heads=1, head_dim=4)
    assert w.flops == 4 * 2 * 4 * 6
    assert w.bytes == 2 * (2 * 2 * 2 * 4 + 2 * 6 * 1 * 4)
    assert work.attention_decode([], heads=2, kv_heads=1, head_dim=4).flops == 0


def test_ssd_scan_hand_count():
    w = work.ssd_scan(3, heads=2, head_dim=4, state=5)
    assert w.flops == 5 * 3 * 2 * 4 * 5
    assert w.bytes == 2 * 2 * 3 * 2 * 4 + 4 * 3 * 2 + 2 * 2 * 3 * 5 + 4 * 2 * 5 * 4


def test_roofline_time_names_the_bound():
    p = PEAKS["TPU v5 lite"]
    t, bound = work.roofline_time(work.Work(p.flops_bf16, 1.0), p)
    assert (t, bound) == (pytest.approx(1.0), "compute")
    t, bound = work.roofline_time(work.Work(1.0, p.hbm_bandwidth * 2), p)
    assert (t, bound) == (pytest.approx(2.0), "memory")


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")
    assert peaks_for("TPU v5 lite").ridge == pytest.approx(197e12 / 819e9)


DENSE = dict(name="t", hidden_size=8, num_attention_heads=2, num_key_value_heads=1,
             intermediate_size=12, num_hidden_layers=3, vocab_size=10, rms_norm_eps=1e-6,
             rope_theta=1e4, attention_multiplier=0.5, initializer_range=0.02)


def test_dense_step_flops_hand_count():
    # per token and layer: q 8x8, k and v 8x4 each, o 8x8, mlp 3x8x12
    per_token = 8 * 8 + 2 * 8 * 4 + 8 * 8 + 3 * 8 * 12
    att = 4 * 2 * 4 * (2 * 3 + 3)             # chunk of 2 at pos 3
    assert dense.prefill_flops(DENSE, 2, 3) == 3 * (2 * 2 * per_token + att) + 2 * 8 * 10
    att = 4 * 2 * 4 * (4 + 7)
    assert dense.decode_flops(DENSE, [4, 7]) == 3 * (2 * 2 * per_token + att) + 2 * 2 * 8 * 10


MAMBA = dict(name="m", d_model=8, n_layer=2, vocab_size=30, pad_vocab_size_multiple=16,
             norm_epsilon=1e-6, initializer_range=0.02, residual_in_fp32=True,
             mamba2_defaults=dict(d_state=4, d_conv=4, expand=2, headdim=4, ngroups=1))


def test_mamba2_step_flops_hand_count():
    d = mamba2.dims(MAMBA)
    assert (d["d_inner"], d["ssm_heads"], d["vocab"]) == (16, 4, 32)
    # projections z, x (8x16 each), B, C (8x4 each), dt (8x4), out 16x8
    proj = 8 * (16 + 16 + 4 + 4 + 4) + 16 * 8
    per_token = 2 * proj + 2 * 4 * 16 + 5 * 4 * 4 * 4
    assert mamba2.prefill_flops(MAMBA, 3, 0) == 2 * 3 * per_token + 2 * 8 * 32
    assert mamba2.decode_flops(MAMBA, [1, 9]) == 2 * (2 * per_token + 2 * 8 * 32)
