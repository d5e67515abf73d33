"""90th percentile over every request due in the window of its first
token's time minus the time it was due.  A request refused, or with no
first token when the run stopped serving, counts with the time it had
waited by then, so a stall shows."""

from bench.readers import nearest_rank


def read(run):
    rec = run.rec
    waits = [(r.first if r.first is not None else rec.t_stop) - r.due
             for r in rec.due_in_window]
    v = nearest_rank(waits, 0.90)
    return None if v is None else v * 1e3
