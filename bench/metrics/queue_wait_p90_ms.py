"""90th percentile over the requests due in the window of the time from
due to the start of the scheduler tick that admitted it into a slot."""

from bench.readers import nearest_rank


def read(run):
    rec = run.rec
    waits = [(r.admitted if r.admitted is not None else rec.t_stop) - r.due
             for r in rec.due_in_window]
    v = nearest_rank(waits, 0.90)
    return None if v is None else v * 1e3
