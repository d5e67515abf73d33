"""Serving engine: chunked-prefill equivalence, scheduler policy, e2e.

Three layers, cheapest first:

  * numeric — `Model.prefill_into` chunk-by-chunk into one slot of a
    batched cache must equal whole-sequence `prefill` AND the old
    prefill-by-decode loop, including partial final chunks and slot
    reuse over stale state;
  * policy — `Scheduler` driven by a fake engine and a fake clock:
    admission control, FCFS, interleave, refill, TTFT accounting, and
    the compiled-step invariants (prefill_steps == ceil(L/C),
    decode_steps == max_new - 1 on the chunked path);
  * end-to-end — a real `Server` on the pod-sim deployment: every
    request completes and its greedy tokens match an unbatched
    single-request reference.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config
from repro.core import Runtime
from repro.launch.mesh import make_host_mesh
from repro.launch.serve import (
    DECODING,
    PREFILLING,
    REJECT_QUEUE_FULL,
    REJECT_TOO_LONG,
    SERVING_STATS_SCHEMA,
    PagedPool,
    Request,
    Scheduler,
    Server,
)
from repro.launch.train import make_bundle
from repro.models import build_model

FAMILIES = [
    "qwen2.5-14b",            # dense GQA
    "mamba2-780m",            # pure SSM (state injection + conv tail)
    "jamba-1.5-large-398b",   # hybrid attn/mamba/moe
]


# ---------------------------------------------------------------------------
# numeric: chunked prefill == whole prefill == prefill-by-decode
# ---------------------------------------------------------------------------

def _chunked_prefill(model, params, prompt, cache, slot, chunk):
    """Drive prefill_into the way JaxEngine does: C-wide windows, the
    last one padded; returns (last-token logits (vocab,), cache)."""
    prefill = jax.jit(model.prefill_into)
    logits = None
    for start in range(0, len(prompt), chunk):
        n = min(chunk, len(prompt) - start)
        buf = np.zeros((1, chunk), np.int32)
        buf[0, :n] = prompt[start : start + n]
        logits, cache = prefill(params, jnp.asarray(buf), cache,
                                jnp.int32(slot), jnp.int32(start), jnp.int32(n))
    return np.asarray(logits[0]), cache


@pytest.mark.parametrize("arch", FAMILIES)
def test_chunked_prefill_matches_whole_prefill(arch):
    """ceil(14/4) chunks (partial tail) into slot 1 of a 3-slot cache ==
    whole-sequence prefill — after the slot served a longer prompt, so
    the pos==0 chunk must also reset the stale recurrent state."""
    cfg = ARCHS[arch].reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    L, chunk, slots, max_len = 14, 4, 3, 32
    prompt = np.asarray(
        jax.random.randint(jax.random.PRNGKey(1), (L,), 0, cfg.vocab_size),
        np.int32)

    logits_full, _ = jax.jit(model.prefill)(params, {"tokens": prompt[None]})
    want = np.asarray(logits_full)         # prefill returns (b, vocab): last token

    cache = model.init_cache(slots, max_len)
    stale = np.asarray(
        jax.random.randint(jax.random.PRNGKey(2), (max_len - 2,), 0,
                           cfg.vocab_size), np.int32)
    _, cache = _chunked_prefill(model, params, stale, cache, 1, chunk)
    got, cache = _chunked_prefill(model, params, prompt, cache, 1, chunk)
    np.testing.assert_allclose(got[None], want, atol=5e-4, rtol=5e-4)

    # continuation: one batched decode tick in the slot == the reference
    nxt = int(np.argmax(got))
    tok = np.zeros((slots, 1), np.int32)
    tok[1, 0] = nxt
    pos = np.full(slots, max_len - 1, np.int32)
    pos[1] = L
    act = np.zeros(slots, bool)
    act[1] = True
    logits_dec, _ = jax.jit(model.decode)(
        params, jnp.asarray(tok), cache, jnp.asarray(pos), jnp.asarray(act))
    ref_full, _ = jax.jit(model.prefill)(
        params, {"tokens": np.concatenate([prompt, [nxt]])[None]})
    np.testing.assert_allclose(np.asarray(logits_dec[1])[None],
                               np.asarray(ref_full), atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("arch", ["qwen2.5-14b", "mamba2-780m"])
def test_chunked_prefill_matches_prefill_by_decode(arch):
    """The new path == the old server's loop: prompt pushed one token at
    a time through the decode step into the same slot, then the last
    token's logits read off the final tick."""
    cfg = ARCHS[arch].reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    L, chunk, slots, max_len = 9, 4, 2, 16
    prompt = np.asarray(
        jax.random.randint(jax.random.PRNGKey(3), (L,), 0, cfg.vocab_size),
        np.int32)

    got, _ = _chunked_prefill(model, params, prompt, model.init_cache(slots, max_len),
                              0, chunk)

    cache = model.init_cache(slots, max_len)
    decode = jax.jit(model.decode)
    logits = None
    for i in range(L):
        tok = np.zeros((slots, 1), np.int32)
        tok[0, 0] = int(prompt[i])
        pos = np.full(slots, max_len - 1, np.int32)
        pos[0] = i
        act = np.zeros(slots, bool)
        act[0] = True
        logits, cache = decode(params, jnp.asarray(tok), cache,
                               jnp.asarray(pos), jnp.asarray(act))
    np.testing.assert_allclose(np.asarray(logits[0]), got, atol=5e-4, rtol=5e-4)


# Two blocks of a period-2 layer pattern: MoE every second layer (two
# attention positions), and the hybrid's attention next to SSM.  Each
# position's stacked leaves are read and written at block index i, the
# paged k/v pools in place by the attention kernels.
PERIOD_TWO = {
    "moe-every-2": lambda: dataclasses.replace(
        ARCHS["moonshot-v1-16b-a3b"].reduced(), moe_every=2, num_layers=4),
    "hybrid": lambda: ARCHS["jamba-1.5-large-398b"].reduced(),
}


@pytest.mark.parametrize("arch", sorted(PERIOD_TWO))
def test_paged_steps_match_contiguous_with_period_two(arch):
    """prefill_into and decode through shuffled block tables give exactly
    the contiguous cache's logits, chunk by chunk and step by step."""
    model = build_model(PERIOD_TWO[arch]())
    assert (model.period, model.num_blocks) == (2, 2)
    params = model.init(jax.random.PRNGKey(0))
    slots, chunk, max_len = 2, 4, 16
    nb = max_len // chunk
    num_pages = 1 + slots * nb
    tables = np.random.default_rng(0).permutation(
        np.arange(1, num_pages)).reshape(slots, nb).astype(np.int32)
    cont = model.init_cache(slots, max_len)
    paged = model.init_paged_cache(num_pages, chunk, slots)
    prefill, decode = jax.jit(model.prefill_into), jax.jit(model.decode)
    lengths = (10, 7)
    tok = np.zeros((slots, 1), np.int32)
    for slot, n_prompt in enumerate(lengths):
        prompt = np.asarray(jax.random.randint(
            jax.random.PRNGKey(10 + slot), (n_prompt,), 0, model.cfg.vocab_size))
        for start in range(0, n_prompt, chunk):
            n = min(chunk, n_prompt - start)
            buf = np.zeros((1, chunk), np.int32)
            buf[0, :n] = prompt[start:start + n]
            where = (jnp.int32(slot), jnp.int32(start), jnp.int32(n))
            want, cont = prefill(params, jnp.asarray(buf), cont, *where)
            got, paged = prefill(params, jnp.asarray(buf), paged, *where,
                                 block_row=jnp.asarray(tables[slot]))
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        tok[slot, 0] = int(np.argmax(want[0]))
    pos = np.asarray(lengths, np.int32)
    act = jnp.ones((slots,), bool)
    for _ in range(3):
        want, cont = decode(params, jnp.asarray(tok), cont, jnp.asarray(pos), act)
        got, paged = decode(params, jnp.asarray(tok), paged, jnp.asarray(pos), act,
                            block_tables=jnp.asarray(tables))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        tok = np.asarray(jnp.argmax(want, axis=-1), np.int32)[:, None]
        pos = pos + 1


# ---------------------------------------------------------------------------
# policy: Scheduler against a fake engine + fake clock (no jax)
# ---------------------------------------------------------------------------

class FakeClock:
    """Reads return the current time; the fake engine advances it one
    unit per compiled step, so TTFT == compiled steps before the first
    token."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class FakeEngine:
    """Duck-typed JaxEngine: deterministic logits (argmax == fed token +
    1 mod vocab), a call log, and a clock hook — everything the
    scheduler touches and nothing jax."""

    vocab = 16

    def __init__(self, *, slots=2, max_len=32, chunk=4,
                 prefill_mode="chunked", clock=None, paged=False,
                 num_pages=None):
        self.slots = slots
        self.max_len = max_len
        self.chunk = chunk
        self.prefill_mode = prefill_mode
        self.clock = clock
        self.paged = paged
        if paged:
            self.pool = PagedPool(slots, max_len, chunk, num_pages)
        self.log = []

    @property
    def prefill_unit(self):
        return self.chunk if self.prefill_mode == "chunked" else 1

    def _logits(self, token):
        v = np.zeros(self.vocab)
        v[(int(token) + 1) % self.vocab] = 1.0
        return v

    def prefill_step(self, slot, tokens, pos):
        self.log.append(("prefill", slot, len(tokens), pos))
        if self.clock is not None:
            self.clock.t += 1.0
        return self._logits(tokens[-1]) if self.prefill_mode == "chunked" else None

    def decode_step(self, tokens, pos, active):
        self.log.append(("decode", tuple(np.flatnonzero(active))))
        if self.clock is not None:
            self.clock.t += 1.0
        out = np.zeros((self.slots, self.vocab))
        for s in np.flatnonzero(active):
            out[s] = self._logits(tokens[s, 0])
        return out


def _mk(rid, plen, max_new=3):
    return Request(rid=rid, prompt=np.arange(plen, dtype=np.int32), max_new=max_new)


def _drain(sched, max_ticks=10_000):
    while not sched.idle:
        sched.tick()
        max_ticks -= 1
        assert max_ticks > 0, "scheduler failed to drain"


def test_admission_rejects_on_queue_depth():
    sched = Scheduler(FakeEngine(slots=1), queue_depth=2)
    assert sched.submit(_mk(0, 4))
    assert sched.submit(_mk(1, 4))
    assert not sched.submit(_mk(2, 4))
    assert not sched.submit(_mk(3, 4))
    assert sched.rejected[REJECT_QUEUE_FULL] == 2
    _drain(sched)
    assert sched.completed == 2


def test_admission_rejects_unservable_budget():
    """A request whose prompt+generation window cannot fit one slot is
    bounced at submit — never queued, never deadlocked."""
    sched = Scheduler(FakeEngine(chunk=4, max_len=16))
    assert not sched.submit(_mk(0, 10, max_new=8))    # 10 + 8 > 16
    assert not sched.submit(_mk(1, 0))                # empty prompt
    assert sched.submit(_mk(2, 15, max_new=1))        # exactly fits: 16
    assert sched.rejected[REJECT_TOO_LONG] == 2

    # the baseline path re-feeds the last prompt token, costing one slot
    base = Scheduler(FakeEngine(chunk=4, max_len=16, prefill_mode="decode"))
    assert not base.submit(_mk(0, 15, max_new=1))     # 15 + 1 + 1 > 16
    assert base.submit(_mk(1, 14, max_new=1))


def test_max_new_clamped_to_cap():
    sched = Scheduler(FakeEngine(), max_new_cap=3)
    req = _mk(0, 4, max_new=100)
    assert sched.submit(req)
    assert req.max_new == 3
    _drain(sched)
    assert len(req.tokens) == 3


def test_fcfs_admission_and_slot_refill():
    """One slot, three requests: served strictly in submit order, the
    freed slot re-admitting the next request on the following tick."""
    eng = FakeEngine(slots=1, chunk=4)
    sched = Scheduler(eng)
    reqs = [_mk(i, 4, max_new=2) for i in (7, 3, 5)]   # rids are NOT the order
    for r in reqs:
        assert sched.submit(r)
    _drain(sched)
    finish = sorted(reqs, key=lambda r: r.finish_t)
    assert [r.rid for r in finish] == [7, 3, 5]
    # every request prefilled its whole prompt into the recycled slot 0
    assert eng.log.count(("prefill", 0, 4, 0)) == 3
    assert {e[1] for e in eng.log if e[0] == "prefill"} == {0}


def test_interleave_bounds_prefill_and_keeps_decode_flowing():
    """interleave=1: at most one prefill unit per tick, while the
    already-decoding request still gets its token every tick
    (continuous batching, not phases)."""
    eng = FakeEngine(slots=2, chunk=2)
    sched = Scheduler(eng, interleave=1)
    sched.submit(_mk(0, 2, max_new=6))    # finishes prefill on tick 1
    sched.submit(_mk(1, 6, max_new=2))    # 3 chunks, one per tick
    per_tick = []
    for _ in range(100):
        if sched.idle:
            break
        eng.log.clear()
        sched.tick()
        per_tick.append(list(eng.log))
    assert sched.completed == 2
    # never more than `interleave` prefill units in one quantum
    assert all(sum(e[0] == "prefill" for e in t) <= 1 for t in per_tick)
    # ticks 2-3: request 1 still prefilling WHILE request 0 decodes —
    # continuous batching, not prefill-then-decode phases
    for t in per_tick[1:3]:
        kinds = [e[0] for e in t]
        assert "prefill" in kinds and "decode" in kinds


def test_compiled_step_invariants_chunked():
    """The regression pin: chunked prefill costs ceil(L/C) compiled
    steps and the final chunk's logits ARE the first token, so decode
    pays max_new - 1 ticks — no wasted re-feed step."""
    eng = FakeEngine(slots=2, chunk=4, max_len=64)
    sched = Scheduler(eng)
    reqs = [_mk(0, 4, 3), _mk(1, 7, 3), _mk(2, 9, 5), _mk(3, 1, 2)]
    for r in reqs:
        assert sched.submit(r)
    _drain(sched)
    for r in reqs:
        assert r.prefill_steps == math.ceil(r.prompt_len / 4), r
        assert r.decode_steps == r.max_new - 1, r
        assert len(r.tokens) == r.max_new


def test_compiled_step_invariants_baseline():
    """The priced inefficiency: prefill-by-decode pays L ticks with the
    logits discarded, then max_new decode ticks (the first one re-feeds
    the last prompt token)."""
    eng = FakeEngine(slots=2, chunk=4, max_len=64, prefill_mode="decode")
    sched = Scheduler(eng)
    reqs = [_mk(0, 4, 3), _mk(1, 7, 2)]
    for r in reqs:
        assert sched.submit(r)
    _drain(sched)
    for r in reqs:
        assert r.prefill_steps == r.prompt_len, r
        assert r.decode_steps == r.max_new, r
        assert len(r.tokens) == r.max_new


def test_ttft_accounting_with_fake_clock():
    """TTFT in engine-step units: chunked pays ceil(L/C) steps to first
    token; the baseline pays L prefill ticks plus one decode tick."""
    clock = FakeClock()
    eng = FakeEngine(slots=1, chunk=4, clock=clock)
    sched = Scheduler(eng, clock=clock)
    req = _mk(0, 8, max_new=2)
    sched.submit(req)
    _drain(sched)
    assert req.ttft == 2.0            # ceil(8/4) compiled steps
    assert req.finish_t >= req.first_token_t >= req.submit_t

    clock = FakeClock()
    eng = FakeEngine(slots=1, chunk=4, clock=clock, prefill_mode="decode")
    sched = Scheduler(eng, clock=clock)
    req = _mk(0, 8, max_new=2)
    sched.submit(req)
    _drain(sched)
    assert req.ttft == 9.0            # 8 prefill ticks + 1 decode tick



def test_admit_stamp_with_fake_clock():
    """admit_t: when a request took its slot, between submit and first
    token; a queued request's queue wait is admit_t - submit_t and its
    prefill phase first_token_t - admit_t."""
    clock = FakeClock()
    eng = FakeEngine(slots=1, chunk=4, clock=clock)
    sched = Scheduler(eng, clock=clock)
    first, second = _mk(0, 8, max_new=2), _mk(1, 4, max_new=2)
    sched.submit(first)
    sched.submit(second)
    _drain(sched)
    assert (first.submit_t, first.admit_t, first.first_token_t) == (0.0, 0.0, 2.0)
    # admitted when the first request's decode tick freed the slot
    assert (second.submit_t, second.admit_t, second.first_token_t) == (0.0, 3.0, 4.0)

    # a handoff adopted elsewhere keeps its first admission
    sched = Scheduler(FakeEngine(slots=2, clock=clock), clock=clock)
    handed, fresh = _mk(2, 4), _mk(3, 4)
    handed.admit_t = 1.0
    assert sched.adopt(handed) and sched.adopt(fresh)
    assert handed.admit_t == 1.0 and fresh.admit_t == clock.t

def test_modes_generate_identical_tokens():
    """Policy-level equivalence: with a deterministic engine both
    prefill modes must emit the same greedy chain for every request."""
    outs = {}
    for mode in ("chunked", "decode"):
        eng = FakeEngine(slots=2, chunk=4, prefill_mode=mode)
        sched = Scheduler(eng)
        reqs = [_mk(0, 5, 4), _mk(1, 8, 3), _mk(2, 3, 2)]
        for r in reqs:
            assert sched.submit(r)
        _drain(sched)
        outs[mode] = {r.rid: list(r.tokens) for r in reqs}
    assert outs["chunked"] == outs["decode"]


# ---------------------------------------------------------------------------
# policy: paged admission — budgets in pages, queue on pressure
# ---------------------------------------------------------------------------

def test_paged_budget_accepts_what_contiguous_rejects():
    """Regression pin for the contiguous budget's conservatism AND the
    paged fix.  L=17, C=8, max_len=18: the last chunk's C-wide write
    window ends at ceil(17/8)*8 = 24 > 18, so the contiguous path must
    keep rejecting (its slot really would overflow).  The paged path
    counts pages: ceil(24/8) = 3 pages == the block table's 3 rows, so
    the same request is admitted and served."""
    cont = Scheduler(FakeEngine(chunk=8, max_len=18))
    assert not cont.submit(_mk(0, 17, max_new=1))
    assert cont.rejected[REJECT_TOO_LONG] == 1

    paged = Scheduler(FakeEngine(chunk=8, max_len=18, paged=True))
    req = _mk(0, 17, max_new=1)
    assert paged.submit(req)
    _drain(paged)
    assert req.done and len(req.tokens) == 1
    # never-satisfiable still bounced at submit, not queued
    assert not paged.submit(_mk(1, 30, max_new=10))   # 5 pages > 3 rows
    assert not paged.submit(_mk(2, 0))                # empty prompt
    assert paged.rejected[REJECT_TOO_LONG] == 2


def test_paged_short_runs_alongside_long():
    """Pages are the admission currency: a long request holding most of
    the pool does not block a short one whose pages still fit — both
    run concurrently in separate slots."""
    eng = FakeEngine(slots=2, chunk=4, max_len=16, paged=True)
    sched = Scheduler(eng)
    long_req = _mk(0, 12, max_new=4)      # budget 16 -> 4 pages
    short_req = _mk(1, 4, max_new=1)      # ceil(max(4, 5)/4) = 2 pages
    assert sched.submit(long_req) and sched.submit(short_req)
    sched.tick()
    assert long_req.slot is not None and short_req.slot is not None
    assert eng.pool.allocator.used == 6
    _drain(sched)
    assert sched.peak_active == 2
    assert long_req.tokens and short_req.tokens
    assert eng.pool.allocator.available == eng.pool.allocator.capacity


def test_paged_out_of_pages_queues_then_admits():
    """Pool exhaustion is back-pressure, not rejection: a satisfiable
    request that finds no free pages stays queued — even with a free
    slot — and is admitted as soon as a completion frees pages."""
    eng = FakeEngine(slots=2, chunk=4, max_len=16, paged=True,
                     num_pages=1 + 5)     # park + 5: one long OR one short
    sched = Scheduler(eng)
    long_req = _mk(0, 12, max_new=1)      # budget 13 -> 4 pages
    short_req = _mk(1, 4, max_new=1)      # 2 pages > the 1 page left
    assert sched.submit(long_req)
    assert sched.submit(short_req)        # accepted: satisfiable, queues
    sched.tick()
    assert long_req.slot is not None
    assert short_req.slot is None and sched.queue, "short must wait, not reject"
    assert sched.rejected == {}
    while not long_req.done:
        sched.tick()
    _drain(sched)
    assert short_req.done and len(short_req.tokens) == 1
    assert eng.pool.allocator.available == eng.pool.allocator.capacity


def test_paged_admission_is_head_of_line():
    """FCFS in pages: when the head of the queue cannot get its pages,
    later (smaller) requests must NOT jump ahead even though they would
    fit and a slot is free — skipping would starve long requests."""
    eng = FakeEngine(slots=2, chunk=4, max_len=16, paged=True,
                     num_pages=1 + 5)
    sched = Scheduler(eng)
    first = _mk(0, 12, max_new=4)         # 4 pages, holds the pool a while
    second = _mk(1, 12, max_new=1)        # 4 pages: cannot fit alongside
    tiny = _mk(2, 3, max_new=1)           # 1 page: would fit — must wait
    for r in (first, second, tiny):
        assert sched.submit(r)
    sched.tick()
    assert first.slot is not None
    assert second.slot is None and tiny.slot is None
    assert [r.rid for r in sched.queue] == [1, 2]
    _drain(sched)
    assert first.finish_t <= second.finish_t <= tiny.finish_t


def test_paged_mode_generates_identical_tokens_and_samples_pages():
    """The paged scheduler is a pure layout change: same greedy chains
    as the contiguous chunked path, with the fragmentation series
    (allocated vs written pages) recorded every tick."""
    outs = {}
    for paged in (False, True):
        eng = FakeEngine(slots=2, chunk=4, paged=paged)
        sched = Scheduler(eng)
        reqs = [_mk(0, 5, 4), _mk(1, 8, 3), _mk(2, 3, 2)]
        for r in reqs:
            assert sched.submit(r)
        _drain(sched)
        outs[paged] = {r.rid: list(r.tokens) for r in reqs}
    assert outs[True] == outs[False]
    assert sched.page_samples, "paged runs must record the page series"
    assert all(used <= alloc for alloc, used in sched.page_samples)
    assert np.all(eng.pool.block_tables == PagedPool.PARK)   # fully released


def test_consolidated_stats_schema_pinned():
    """Every SERVING_STATS_SCHEMA key is always present — zeroed pool
    keys on the contiguous path, the per-tick page samples aggregated on
    the paged path — so the stats printer can iterate the schema and a
    new counter cannot be silently dropped from any consumer."""
    sched = Scheduler(FakeEngine(slots=2, chunk=4))
    for r in (_mk(0, 5, 2), _mk(1, 3, 2)):
        assert sched.submit(r)
    _drain(sched)
    stats = sched.consolidated_stats()
    assert set(stats) == SERVING_STATS_SCHEMA
    assert stats["completed"] == 2
    assert stats["ticks"] == sched.ticks > 0
    assert stats["pages-capacity"] == stats["pages-allocated-mean"] == 0

    eng = FakeEngine(slots=2, chunk=4, paged=True)
    sp = Scheduler(eng)
    for r in (_mk(0, 5, 4), _mk(1, 8, 3)):
        assert sp.submit(r)
    _drain(sp)
    stats = sp.consolidated_stats()
    assert set(stats) == SERVING_STATS_SCHEMA
    assert stats["pages-capacity"] == eng.pool.allocator.capacity
    assert 0 < stats["pages-written-mean"] <= stats["pages-allocated-mean"]
    assert stats["pages-allocated-peak"] >= stats["pages-allocated-mean"]
    assert 0 <= stats["fragmentation-pct"] < 100


# ---------------------------------------------------------------------------
# end-to-end: real Server on the pod-sim deployment
# ---------------------------------------------------------------------------

ARCH = "qwen2.5-14b"


@pytest.fixture(scope="module")
def served_container():
    rt = Runtime(host_env={})
    container = rt.deploy(make_bundle(ARCH, reduced=True),
                          mesh=make_host_mesh(data=1))
    yield get_config(ARCH).reduced(), container
    rt.cleanup()


def _pad_kv(cache, extra):
    out = {}
    for pk, entry in cache.items():
        e = {}
        for k, v in entry.items():
            if k in ("k", "v"):
                e[k] = jnp.pad(v, ((0, 0), (0, 0), (0, extra), (0, 0), (0, 0)))
            else:
                e[k] = v
        out[pk] = e
    return out


def _reference_tokens(model, params, prompt, max_new):
    """Unbatched greedy generation via the whole-sequence prefill path."""
    logits, cache = jax.jit(model.prefill)(
        params, {"tokens": jnp.asarray(prompt)[None]})
    cache = _pad_kv(cache, max_new)
    toks = [int(np.argmax(logits[0]))]     # prefill returns (b, vocab)
    decode = jax.jit(model.decode)
    pos = len(prompt)
    for _ in range(max_new - 1):
        lg, cache = decode(params, jnp.asarray([[toks[-1]]], jnp.int32),
                           cache, jnp.int32(pos))
        toks.append(int(np.argmax(lg[0])))
        pos += 1
    return toks


def test_e2e_serving_matches_unbatched_reference(served_container):
    """Full pod-sim run: continuous batching over 2 slots with partial
    chunks and slot reuse; every request completes, the compiled-step
    ledger matches the ceil(L/C) invariant, and every request's greedy
    tokens equal the unbatched single-request reference."""
    cfg, container = served_container
    server = Server(cfg, container, slots=2, max_len=32, chunk=4,
                    prefill_mode="chunked")
    rng = np.random.default_rng(11)
    lens = [4, 6, 9, 3]                      # multiple, partial, sub-chunk
    for rid, plen in enumerate(lens):
        prompt = rng.integers(0, cfg.vocab_size, size=plen).astype(np.int32)
        assert server.submit(Request(rid=rid, prompt=prompt, max_new=3))
    server.run()

    done = [r for r in server.requests if r.done]
    assert len(done) == len(lens)
    assert server.engine.prefill_calls == sum(math.ceil(n / 4) for n in lens)
    model, params = server.engine.model, server.engine.params
    for r in done:
        assert r.prefill_steps == math.ceil(r.prompt_len / 4)
        assert r.decode_steps == r.max_new - 1
        assert r.finish_t >= r.first_token_t >= r.submit_t
        assert r.tokens == _reference_tokens(model, params, r.prompt, r.max_new)


def test_e2e_paged_matches_contiguous(served_container):
    """The paged cache is a layout, not a policy: the same traffic served
    through page pools + block tables must emit exactly the contiguous
    chunked path's tokens (== the unbatched reference), and the pool
    must be fully drained when the server goes idle."""
    cfg, container = served_container
    rng = np.random.default_rng(11)
    lens = [4, 6, 9, 3]
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in lens]
    tokens = {}
    for paged in (False, True):
        server = Server(cfg, container, slots=2, max_len=32, chunk=4,
                        prefill_mode="chunked", paged=paged)
        for rid, p in enumerate(prompts):
            assert server.submit(Request(rid=rid, prompt=p.copy(), max_new=3))
        server.run()
        assert all(r.done for r in server.requests)
        tokens[paged] = {r.rid: list(r.tokens) for r in server.requests}
        if paged:
            pool = server.engine.pool
            assert pool.allocator.available == pool.allocator.capacity
            assert np.all(pool.block_tables == PagedPool.PARK)
            assert server.scheduler.page_samples
    assert tokens[True] == tokens[False]
    model, params = server.engine.model, server.engine.params
    for r in server.requests:
        assert r.tokens == _reference_tokens(model, params, r.prompt, r.max_new)


def _old_loop_tokens(model, params, prompt, max_new, max_len):
    """Unbatched replay of the pre-scheduler server: every prompt token
    pushed through decode with the logits discarded, then generation
    seeded by RE-FEEDING the last prompt token at position L — the
    duplicated-context quirk the chunked path fixes (its final chunk's
    logits are the true first token)."""
    cache = model.init_cache(1, max_len)
    decode = jax.jit(model.decode)
    pos = 0
    for t in prompt:
        _, cache = decode(params, jnp.asarray([[int(t)]], jnp.int32),
                          cache, jnp.int32(pos))
        pos += 1
    toks, last = [], int(prompt[-1])
    for _ in range(max_new):
        lg, cache = decode(params, jnp.asarray([[last]], jnp.int32),
                           cache, jnp.int32(pos))
        pos += 1
        last = int(np.argmax(lg[0]))
        toks.append(last)
    return toks


def test_e2e_baseline_replays_old_server_loop(served_container):
    """prefill_mode='decode' must be a faithful replay of the old
    prefill-by-decode server — including its duplicated-last-token
    seeding — so table7's baseline row prices exactly the behaviour the
    chunked path replaced."""
    cfg, container = served_container
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (4, 6)]
    server = Server(cfg, container, slots=2, max_len=32, chunk=4,
                    prefill_mode="decode")
    for rid, p in enumerate(prompts):
        assert server.submit(Request(rid=rid, prompt=p.copy(), max_new=3))
    server.run()
    assert server.engine.prefill_calls == 0       # never the chunked path
    model, params = server.engine.model, server.engine.params
    for r in server.requests:
        assert r.done
        assert r.tokens == _old_loop_tokens(model, params, r.prompt,
                                            r.max_new, 32)
