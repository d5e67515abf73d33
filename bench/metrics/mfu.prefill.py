"""Model FLOPs of the window's prefill chunks (valid tokens, attention
over their context, the logits of the last token) over the summed host
time of those steps, times chips and peak bf16 FLOP/s."""

from bench.readers import step_mfu


def read(run):
    fam, spec = run.cell.family, run.cell.spec
    return step_mfu(run, run.rec.prefill, lambda c: fam.prefill_flops(spec, c[2], c[3]))
