"""Helpers for the benchmark's CPU tests: tiny cells of both families,
served through the real path with the Pallas kernels interpreted.
Importing it puts the repository's root on the path, for ``bench``."""

import dataclasses
import io
import os
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

def tiny_cell(arch: str, loop: str = "open", std: float = 0.02, wide: bool = False):
    """A cell at the registry's reduced widths in bf16 (mamba2 ``wide``:
    d_model 128, 4 layers, state 32), with a mix and a deployment small
    enough for interpret mode."""
    from bench import harness
    from bench.models import dense, mamba2
    from repro.launch.train import serving_config

    cfg = dataclasses.replace(serving_config(arch, reduced=True), dtype="bfloat16")
    if arch == "granite-3-8b":
        spec = dict(name="tiny-granite", family="dense", arch=arch, dtype="bfloat16",
                    hidden_size=64, intermediate_size=96, num_attention_heads=4,
                    num_key_value_heads=2, num_hidden_layers=2, vocab_size=256,
                    rope_theta=10000.0, rms_norm_eps=1e-6, initializer_range=std,
                    attention_multiplier=0.25)
        family = dense
    else:
        d, layers, state, head = (128, 4, 32, 16) if wide else (64, 2, 16, 8)
        cfg = dataclasses.replace(cfg, d_model=d, num_layers=layers, ssm_state=state,
                                  ssm_headdim=head)
        spec = dict(name="tiny-mamba", family="mamba2", arch=arch, dtype="bfloat16",
                    d_model=d, n_layer=layers, vocab_size=250, pad_vocab_size_multiple=16,
                    residual_in_fp32=True, norm_epsilon=1e-6, initializer_range=std,
                    mamba2_defaults=dict(d_state=state, d_conv=4, expand=2, headdim=head,
                                         ngroups=1, chunk_size=256))
        family = mamba2
    mix = {"loop": loop, "arrivals": {"rate": 20.0, "shape": 0.25}, "clients": 3,
           "prompt": {"median": 10, "sigma": 0.5, "min": 3, "max": 20},
           "output": {"median": 8, "sigma": 0.5, "min": 4, "max": 12}, "block": 8}
    deploy = {"slots": 3, "max_len": 32, "chunk": 8, "num_pages": None, "interleave": 2,
              "queue_depth": 64, "check_requests": 3, "check_limit": 0.5}
    return harness.Cell(f"tiny-{arch}", 1, spec, mix, deploy, family), cfg


def run_tiny(cell, cfg, seed=7, seconds=1.5, hook=None):
    """One run of a tiny cell through bench.run.execute, with no chip:
    interpreted kernels, and no metrics (no peaks)."""
    from bench import run
    from repro.core.platform import POD_SIM

    return run.execute(cell, seed, seconds, False, provider="pallas-interpret",
                       platform=POD_SIM, program_config=cfg, hook=hook,
                       log=io.StringIO())
