"""Share of the traced window in which no operation ran on the device
(mean over the chips used)."""


def read(run):
    red = run.rec.trace
    return None if red is None else 100.0 * red.idle_share
