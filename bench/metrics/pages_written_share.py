"""Pages holding written tokens over pages allocated, each averaged over
the window's scheduler ticks (the Scheduler's per-tick page samples)."""


def read(run):
    rec = run.rec
    samples = [s for s, (_, te) in zip(rec.page_samples, rec.ticks) if te <= rec.t_end]
    allocated = sum(a for a, _ in samples)
    if not allocated:
        return None
    return 100.0 * sum(w for _, w in samples) / allocated
