"""99th percentile over every gap between consecutive output tokens of a
request, both emitted inside the window, from the host stamps of the
ticks that emitted them."""

from bench.readers import nearest_rank


def read(run):
    rec = run.rec
    gaps = []
    for r in rec.requests.values():
        t = [x for x in r.times if rec.in_window(x)]
        gaps.extend(b - a for a, b in zip(t, t[1:]))
    v = nearest_rank(gaps, 0.99)
    return None if v is None else v * 1e3
