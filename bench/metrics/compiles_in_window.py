"""XLA compilations, loads from the persistent cache included, that
ended inside the window: the program's compile log
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
    return sum(1 for end, _, _ in log.compiles if rec.t0 <= end <= rec.t_end)
