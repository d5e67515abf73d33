"""End-to-end runs of a tiny cell through bench.run.execute on the CPU
(kernels interpreted, no device metric), sound and with the timed path
broken underneath.  Each test file binds these to one family with an
``arch`` fixture."""

import numpy as np

from bench_helpers import run_tiny, tiny_cell

# The init's std: big enough that attention shapes the dense logits.  In
# Mamba-2 it sets only the tied embedding, and there the published 0.02
# keeps the token's own echo from hiding the SSM state.
STD = {"granite-3-8b": 0.3, "mamba2-780m": 0.02}


def _stale_state(engine):
    """The decode step returns its state unchanged."""
    step = engine._decode

    def decode(params, tok, cache, *a, **k):
        logits, _ = step(params, tok, cache, *a, **k)
        return logits, cache
    engine._decode = decode


def _half_batch(engine):
    """Half of the decoding rows are left out: their logits never computed."""
    step = engine.decode_step

    def decode_step(tok, pos, active):
        rows = np.flatnonzero(active)
        keep = np.zeros_like(active)
        keep[rows[: (len(rows) + 1) // 2]] = True
        logits = np.array(step(tok, pos, keep))
        logits[active & ~keep] = 0.0
        return logits
    engine.decode_step = decode_step


def _altered_token(engine):
    """Every decoded token is altered where it is produced."""
    step = engine.decode_step

    def decode_step(tok, pos, active):
        return np.roll(np.asarray(step(tok, pos, active)), 1, axis=-1)
    engine.decode_step = decode_step


def test_sound_run_is_correct_and_reports_no_device_metric(arch):
    cell, cfg = tiny_cell(arch, std=STD[arch])
    res = run_tiny(cell, cfg, seed=2**31 + 3)
    assert list(res) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["metrics"] == {}
    assert res["device"]["platform"] == "cpu"
    (name, chk), = res["checks"].items()
    assert chk["value"] <= chk["limit"] == cell.deploy["check_limit"]


def test_state_left_unchanged_is_caught(arch):
    cell, cfg = tiny_cell(arch, std=STD[arch])
    assert run_tiny(cell, cfg, hook=_stale_state)["correct"] is False


def test_half_the_batch_left_out_is_caught(arch):
    cell, cfg = tiny_cell(arch, std=STD[arch])
    cell.mix["arrivals"]["rate"] = 60.0         # keep several rows decoding
    assert run_tiny(cell, cfg, hook=_half_batch)["correct"] is False


def test_altered_token_is_caught(arch):
    cell, cfg = tiny_cell(arch, std=STD[arch])
    assert run_tiny(cell, cfg, hook=_altered_token)["correct"] is False
