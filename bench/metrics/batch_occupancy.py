"""Mean over the window's decode steps of the share of slots decoding."""

from bench.readers import window_calls


def read(run):
    calls = window_calls(run.rec, run.rec.decode)
    if not calls:
        return None
    return 100.0 * sum(len(c[2]) for c in calls) / (len(calls) * run.rec.slots)
