"""Every token emitted inside the window, over the window's length."""


def read(run):
    rec = run.rec
    n = sum(1 for r in rec.requests.values() for t in r.times if rec.in_window(t))
    return n / rec.seconds
