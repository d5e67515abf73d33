"""90th percentile over the requests due in the window and admitted of
their first token's time minus their admission (`Request.admit_t` and
`first_token_t`, the scheduler's clock): the time a request spends in
its slot before its first token, queued behind other slots' prefill
chunks included.  One with no first token counts with the time it had
waited when the run stopped serving, as in ttft_p90_ms.  None where the
program stamps no admission."""

from bench.readers import nearest_rank


def read(run):
    rec = run.rec
    phases = []
    for r in rec.due_in_window:
        admitted = getattr(r.req, "admit_t", None)
        if admitted is not None:
            first = r.req.first_token_t
            phases.append((first if first is not None else rec.t_stop) - admitted)
    v = nearest_rank(phases, 0.90)
    return None if v is None else v * 1e3
