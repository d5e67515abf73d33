"""Model FLOPs of the window's decode steps (active rows only) over the
summed host time of those steps, times chips and peak bf16 FLOP/s."""

from bench.readers import step_mfu


def read(run):
    fam, spec = run.cell.family, run.cell.spec
    return step_mfu(run, run.rec.decode, lambda c: fam.decode_flops(spec, c[2]))
