"""Summed length of the full (generation-2) garbage collections that
ended inside the window: the program's collection log
(`repro.launch.tracing.host_log`).  None where the program keeps no
such log, or began it after the window opened."""


def read(run):
    try:
        from repro.launch.tracing import host_log
    except ImportError:
        return None
    log, rec = host_log(), run.rec
    if log.since > rec.t0:
        return None
    return 1e3 * sum(end - start for start, end in log.collections
                     if rec.t0 <= end <= rec.t_end)
