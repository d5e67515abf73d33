"""The comparison that decides ``correct``.

Once the window has closed, a sample of the requests it finished is
drawn from the seed, the longest among them.  The plain reference (the
configuration's family module) runs once over each prompt followed by
the tokens the program served, and at every position where the program
served a token it reads how far that token's reference logit lies below
the reference's best.  The number compared is the widest such gap, in
standard deviations of the reference's logits at that position (so the
limit means the same at every width).  Greedy decoding serves the top
token of its own logits, so only its rounding can open a gap; a wrong
mask, page, state or token opens one of several deviations.

The control puts the reference itself in the program's place, with its
weights in float8 (e4m3, one scale per tensor), the precision below the
configuration's bfloat16, and reads the gap of the token the control
puts first at the same positions.
"""

from __future__ import annotations

import numpy as np

from bench import gen

__all__ = ["sample", "gaps", "compare", "SAMPLE_STREAM"]

SAMPLE_STREAM = 2


def sample(records, seed: int, count: int) -> list:
    """`count` finished requests: the longest (prompt plus served tokens)
    and others drawn from the seed."""
    done = sorted((r for r in records if r.req is not None and r.req.done),
                  key=lambda r: r.spec.index)
    if not done:
        return []
    longest = max(done, key=lambda r: (r.req.prompt_len + len(r.req.tokens), -r.spec.index))
    rest = [r for r in done if r is not longest]
    rng = gen.rng_for(seed, SAMPLE_STREAM)
    picked = [rest[i] for i in rng.permutation(len(rest))[: count - 1]]
    return [longest] + sorted(picked, key=lambda r: r.spec.index)


def _inputs(picked):
    seqs, starts, served = [], [], []
    for r in picked:
        toks = np.asarray(r.req.tokens, np.int32)
        seqs.append(np.concatenate([np.asarray(r.req.prompt, np.int32), toks[:-1]]))
        starts.append(r.req.prompt_len - 1)
        served.append(toks)
    return seqs, starts, served


def gaps(ref_logits, chosen) -> np.ndarray:
    """Per position: (best reference logit - reference logit of the
    chosen token) / std of the reference logits."""
    out = []
    for lg, tok in zip(ref_logits, chosen):
        lg = np.asarray(lg, np.float64)
        best = lg.max(-1)
        pick = lg[np.arange(len(tok)), tok]
        out.append((best - pick) / lg.std(-1))
    return np.concatenate(out) if out else np.zeros(0)


CONTROL = "float8_e4m3fn"


def _reference(cell, weights, picked, control: str | None):
    seqs, starts, served = _inputs(picked)
    rows = int(cell.mix["output"]["max"])
    lg = cell.family.reference_logits(cell.spec, weights, seqs, starts,
                                      bucket=int(cell.deploy["max_len"]), rows=rows,
                                      control=control)
    return lg, served


def compare(cell, weights, picked, *, control: bool = False) -> dict:
    """The widest gap of the served tokens and how many were compared;
    with `control`, also the widest gap of the tokens the float8
    control puts first at the same positions."""
    ref, served = _reference(cell, weights, picked, control=None)
    g = gaps(ref, served)
    out = {"gap": float(g.max()), "tokens": int(g.size)}
    if control:
        ctrl, _ = _reference(cell, weights, picked, control=CONTROL)
        out["control_gap"] = float(gaps(ref, [np.asarray(c).argmax(-1) for c in ctrl]).max())
    return out
