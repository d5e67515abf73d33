"""Host time of the first call of each of the cell's two compiled steps."""


def read(run):
    return run.rec.warmup_s
