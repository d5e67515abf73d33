"""The control: the plain reference in the program's place, with its
weights in float8 (e4m3, one scale per tensor), the precision below the
configurations' bfloat16.  On the chip it reads several times the
program's widest gap at every cell's own size (PERF.md).  Here, at sizes
a test run holds, it still opens gaps that bfloat16 does not."""

import bench_helpers  # noqa: F401  (the repository root on the path)

import dataclasses

import jax
import numpy as np
import pytest

from bench import check, harness
from bench.models import dense, mamba2
from bench_helpers import tiny_cell
from repro.core.platform import POD_SIM


def test_control_fails_where_the_served_program_passes():
    """A tiny granite-family cell served through the program: its tokens
    stay inside the limit, the control's do not."""
    cell, cfg = tiny_cell("granite-3-8b", std=0.3)
    bench = cell.driver.Driver(cell, provider="pallas-interpret", platform=POD_SIM,
                               program_config=cfg)
    program, control = [], []
    try:
        for seed in (1, 2, 3):
            bench.set_weights(seed)
            if seed == 1:
                bench.warm()
            rec = bench.window(seed, 1.0)
            picked = check.sample(rec.due_in_window, seed, 3)
            out = check.compare(cell, bench.weights, picked, control=True)
            program.append(out["gap"])
            control.append(out["control_gap"])
            bench.reset()
    finally:
        bench.close()
    assert max(program) < cell.deploy["check_limit"] < max(control)
    assert min(control) > 3 * max(program)


def _family(arch):
    from repro.launch.train import serving_config

    base = dataclasses.replace(serving_config(arch, reduced=True), dtype="bfloat16",
                               vocab_size=1024)
    if arch == "granite-3-8b":
        cfg = dataclasses.replace(base, d_model=256, num_layers=4, num_heads=4,
                                  num_kv_heads=2, head_dim=64, d_ff=512)
        spec = dict(name="g", family="dense", hidden_size=256, intermediate_size=512,
                    num_attention_heads=4, num_key_value_heads=2, num_hidden_layers=4,
                    vocab_size=1024, rope_theta=1e4, rms_norm_eps=1e-6,
                    initializer_range=0.02, attention_multiplier=64 ** -0.5)
        return dense, cfg, spec
    cfg = dataclasses.replace(base, d_model=256, num_layers=8, ssm_state=64, ssm_headdim=32)
    spec = dict(name="m", family="mamba2", d_model=256, n_layer=8, vocab_size=1024,
                pad_vocab_size_multiple=16, residual_in_fp32=True, norm_epsilon=1e-6,
                initializer_range=0.02,
                mamba2_defaults=dict(d_state=64, d_conv=4, expand=2, headdim=32, ngroups=1))
    return mamba2, cfg, spec


@pytest.mark.parametrize("arch", ["granite-3-8b", "mamba2-780m"])
def test_float8_weights_open_gaps_that_bfloat16_does_not(arch):
    """The reference alone, teacher-forced on random tokens at a width
    of 256 with the published init: weights rounded to bfloat16 leave
    the reference's choices (almost) alone, weights in float8 do not."""
    from repro.models.model import Model

    fam, cfg, spec = _family(arch)
    abstract = jax.eval_shape(Model(cfg).init, jax.random.PRNGKey(0))
    for seed in (1, 2, 3):
        w = jax.jit(lambda k: fam.init_weights(spec, abstract, k))(harness.weights_key(seed))
        rng = np.random.default_rng(seed)
        seqs = [rng.integers(0, 1000, size=48).astype(np.int32) for _ in range(3)]
        kw = dict(bucket=64, rows=32)
        ref = fam.reference_logits(spec, w, seqs, [16] * 3, **kw)
        read = {c: check.gaps(ref, [x.argmax(-1) for x in fam.reference_logits(
            spec, w, seqs, [16] * 3, control=c, **kw)]).max()
            for c in ("bfloat16", check.CONTROL)}
        assert read[check.CONTROL] > 0.1
        assert read[check.CONTROL] > 3 * read["bfloat16"]
