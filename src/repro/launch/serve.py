"""Production serving engine: chunked prefill + continuous batching.

The serving analogue of the paper's deployment story: the same bundle
that trained on the laptop serves on the pod, with the two compiled
paths a serving workload actually exercises —

  * **chunked prefill** — `Model.prefill_into` advances ONE slot of the
    batched cache by a fixed-width window of C prompt tokens per
    compiled step.  Prompt ingestion costs ceil(prompt_len / C) compiled
    steps instead of the O(prompt_len) whole-batch decode ticks the old
    prefill-by-decode loop burned (kept as ``prefill_mode="decode"``,
    the baseline row of benchmarks/table7_serving.py).
  * **batched decode** — one token for every active slot per compiled
    step, each slot at its own cache position (vector ``pos``), inactive
    slots parked at max_len-1 with their recurrent state frozen
    (``active`` mask).

Scheduling is split from compilation so it can be unit-tested with fake
clocks and fake engines:

  * `Scheduler` — pure-python continuous batching: FCFS admission from a
    bounded queue into fixed slots, a prefill/decode interleave ratio,
    per-request accounting (TTFT, compiled-step counts).  No jax
    computation; its phases are profiler spans (`repro.launch.tracing`).
  * `JaxEngine` — owns params/cache and the two jitted steps; counts
    every compiled-step invocation (the table7 scoreboard's honesty
    metric).
  * `Server` — the facade main() drives: Scheduler + JaxEngine + the
    request log.

Request lifecycle (documented in docs/serving.md):

    queued -> admitted (slot assigned) -> prefilling -> decoding -> done

Admission control rejects instead of deadlocking: a request is admitted
only if its prompt+generation budget fits the slot's cache window, and
`submit` bounces requests once the queue is `queue_depth` deep.

`--profile` / `--autotune` wire through both compiled paths unchanged:
every op call goes through the container's binding, so prefill
geometries (chunk_attention at C tokens) and decode geometries (Sq=1)
each resolve their own tuned configs — `print_dispatch_stats` shows
both after a run.
"""

from __future__ import annotations

import argparse
import dataclasses
import time
import types
from collections import deque
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.configs.base import ShapeConfig
from repro.core import Runtime
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import DeployOptions, make_deployment
from repro.launch.tracing import host_log, span
from repro.launch.train import make_bundle, serving_config

__all__ = ["BlockAllocator", "PagedPool", "Request", "Scheduler", "JaxEngine",
           "Server", "SERVING_STATS_SCHEMA", "DeploymentRejected",
           "estimate_footprint", "main"]

# scheduler states (docs/serving.md + docs/fleet.md state machines)
QUEUED = "queued"
PREFILLING = "prefilling"
DECODING = "decoding"
HANDOFF = "handoff"     # fleet mode: prefill finished, state in transit
DONE = "done"

# admission rejection reasons
REJECT_QUEUE_FULL = "queue-full"
REJECT_TOO_LONG = "too-long"

# Scheduler.consolidated_stats() keys — pinned, like the dispatch layer's
# STATS_SCHEMA: printers iterate this, so adding a counter here forces it
# into every consumer (and the schema test) at once.
SERVING_STATS_SCHEMA = frozenset({
    "submitted", "completed", "rejected-queue-full", "rejected-too-long",
    "handed-off", "adopted", "peak-active", "ticks",
    "pages-capacity", "pages-allocated-mean", "pages-written-mean",
    "pages-allocated-peak", "fragmentation-pct",
})


class DeploymentRejected(RuntimeError):
    """A deployment whose estimated footprint exceeds the memory budget.

    Raised by `JaxEngine` BEFORE any buffer is allocated, with the
    estimate attached — the caller (or table7's quantized-deploy row)
    reports exactly what did not fit and retries with ``quantize``."""

    def __init__(self, footprint: dict, budget: int):
        self.footprint = footprint
        self.budget = budget
        super().__init__(
            f"deployment needs ~{footprint['total_bytes']:,} bytes "
            f"(weights {footprint['weight_bytes']:,} + "
            f"kv {footprint['kv_bytes']:,}, quantize="
            f"{footprint['quantize']}) but the budget is {budget:,}")


def estimate_footprint(model, *, slots: int, max_len: int,
                       quantize: str | None = None, paged: bool = False,
                       num_pages: int | None = None,
                       page_size: int | None = None) -> dict:
    """Deployment memory estimate from abstract shapes — no allocation.

    Weights: quantizable leaves (the checkpoint quantizer's filter) cost
    1 byte per element plus fp32 per-channel scales when ``quantize`` is
    set, full dtype width otherwise.  KV: the model's abstract cache,
    which already reflects the storage dtype and scale leaves when the
    model was built with ``kv_quantize``."""
    import math

    from repro.checkpoint.manifest import _flatten, _quantizable

    wb = 0
    for path, s in _flatten(model.abstract_params()):
        n = math.prod(s.shape)
        if quantize and _quantizable(path, s):
            wb += n + (n // s.shape[-2]) * 4    # 1-byte codes + fp32 scales
        else:
            wb += n * jnp.dtype(s.dtype).itemsize
    cache = (model.abstract_paged_cache(num_pages, page_size, slots)
             if paged else model.abstract_cache(slots, max_len))
    kb = sum(math.prod(s.shape) * jnp.dtype(s.dtype).itemsize
             for s in jax.tree.leaves(cache))
    return {"weight_bytes": int(wb), "kv_bytes": int(kb),
            "total_bytes": int(wb + kb), "quantize": quantize or "none"}


@dataclasses.dataclass
class Request:
    """One generation request plus its complete serving record.

    The scheduler fills in the lifecycle fields; the benchmark reads
    them.  Timestamps come from the scheduler's injected clock, so a
    fake clock makes TTFT accounting exactly reproducible in tests.

    Attributes:
      rid: caller-chosen id (echoed in emitted (rid, token) pairs).
      prompt: (prompt_len,) int32 prompt tokens.
      max_new: generation budget; the scheduler may clamp it to its
        per-request cap at submit time.
      tokens: generated tokens (greedy argmax), filled during serving.
      state: queued -> prefilling -> decoding -> done.
      slot: cache row while admitted, else None.
      prefill_pos: prompt tokens ingested so far.
      next_pos: cache position the next fed token will be written to.
      submit_t / admit_t / first_token_t / finish_t: clock readings;
        TTFT is first_token_t - submit_t (first token falls out of the
        final prefill chunk's logits on the chunked path, out of the
        first decode tick on the baseline path).  admit_t is when the
        request first took a slot, so first_token_t - admit_t is its
        prefill phase, queued behind other slots' chunks included.
      prefill_steps / decode_steps: compiled steps this request consumed
        — the regression-pinned invariant is prefill_steps ==
        ceil(prompt_len / C) and decode_steps == max_new - 1 on the
        chunked path.
    """

    rid: int
    prompt: np.ndarray
    max_new: int
    tokens: list = dataclasses.field(default_factory=list)
    state: str = QUEUED
    slot: int | None = None
    prefill_pos: int = 0
    next_pos: int = 0
    submit_t: float | None = None
    admit_t: float | None = None
    first_token_t: float | None = None
    finish_t: float | None = None
    prefill_steps: int = 0
    decode_steps: int = 0
    order: int = -1     # FCFS sequence number, assigned at submit

    @property
    def prompt_len(self) -> int:
        return int(len(self.prompt))

    @property
    def done(self) -> bool:
        return self.state == DONE

    @property
    def ttft(self) -> float | None:
        if self.first_token_t is None or self.submit_t is None:
            return None
        return self.first_token_t - self.submit_t


class BlockAllocator:
    """Pure-python page bookkeeping for the paged KV cache.

    All-or-nothing allocation: `alloc(owner, n)` hands out n pages or
    None (never a partial grant — a half-provisioned request could not
    be admitted anyway), `free(owner)` returns every page the owner
    held.  Reserved pages (the park page) are never handed out.  The
    invariants the hypothesis suite pins (tests/test_block_allocator.py):
    no page is owned twice, free returns exactly what alloc granted, and
    pages-in-use never exceeds the pool.
    """

    def __init__(self, num_pages: int, *, reserved: int = 0):
        if num_pages <= reserved:
            raise ValueError(f"pool of {num_pages} pages with {reserved} reserved")
        self.num_pages = num_pages
        self.reserved = tuple(range(reserved))
        # stack of free page ids; pop() from the end -> lowest index first
        self._free = list(range(num_pages - 1, reserved - 1, -1))
        self.owned: dict[int, list[int]] = {}

    @property
    def capacity(self) -> int:
        return self.num_pages - len(self.reserved)

    @property
    def available(self) -> int:
        return len(self._free)

    @property
    def used(self) -> int:
        return self.capacity - len(self._free)

    def alloc(self, owner, n: int) -> list[int] | None:
        if owner in self.owned:
            raise ValueError(f"owner {owner!r} already holds pages")
        if n < 1:
            raise ValueError(f"alloc of {n} pages")
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        self.owned[owner] = pages
        return list(pages)

    def free(self, owner) -> list[int]:
        pages = self.owned.pop(owner, [])
        self._free.extend(pages)
        return list(pages)


class PagedPool:
    """BlockAllocator + per-slot block tables — the paged cache's map.

    Page size equals the prefill chunk C, so each compiled prefill step
    fills exactly one page.  Page 0 is reserved as the *park page*:
    inactive slots keep an all-zero table row, so their parked decode
    writes land there and their (masked, discarded) gathers read from
    there — the table never holds an out-of-pool index.  The default
    pool size (1 park + slots x max_blocks) matches the contiguous
    layout's capacity; pass `num_pages` to serve under memory pressure.
    """

    PARK = 0

    def __init__(self, slots: int, max_len: int, page_size: int,
                 num_pages: int | None = None):
        self.page_size = page_size
        self.max_blocks = -(-max_len // page_size)
        self.num_pages = (1 + slots * self.max_blocks
                          if num_pages is None else num_pages)
        self.allocator = BlockAllocator(self.num_pages, reserved=1)
        self.block_tables = np.zeros((slots, self.max_blocks), np.int32)

    def alloc(self, owner, n: int) -> list[int] | None:
        return self.allocator.alloc(owner, n)

    def free(self, owner) -> list[int]:
        return self.allocator.free(owner)

    def assign(self, slot: int, pages: list[int]) -> None:
        row = np.zeros(self.max_blocks, np.int32)
        row[: len(pages)] = pages
        self.block_tables[slot] = row

    def release(self, slot: int) -> None:
        self.block_tables[slot] = self.PARK


class JaxEngine:
    """The compiled half of the server: params, cache, two jitted steps.

    Owns the batched cache (slots x max_len) and exposes exactly the two
    operations the scheduler needs, both with static shapes so each
    compiles once:

      * prefill_step(slot, tokens, pos) — one prefill work unit.  In
        ``chunked`` mode this is Model.prefill_into over a C-wide window
        (slot/pos/n_valid traced — every request reuses one executable)
        and returns the window's last-token logits.  In ``decode`` mode
        (the baseline the old server implemented) it is ONE prompt token
        pushed through the whole-batch decode step, logits discarded —
        O(prompt_len) compiled ticks per request, kept so table7 can
        price the difference.
      * decode_step(tokens, pos, active) — one batched decode tick;
        every row at its own position, inactive rows parked at
        max_len-1 with recurrent state frozen.

    ``prefill_calls`` / ``decode_calls`` count compiled-step dispatches;
    the scoreboard derives per-request costs from the per-Request
    counters and cross-checks the totals against these.

    With ``paged=True`` the cache k/v are page *pools* (page size = C)
    addressed through ``self.pool``'s per-slot block tables; the
    scheduler drives the allocator (admission in pages actually needed)
    and this engine just threads the tables into both compiled steps.
    Paged mode requires chunked prefill — the page-per-chunk invariant
    is what keeps every prefill write inside one page.

    With ``window=W`` every attention call is sliding-window: a token
    attends only its trailing W keys (windowed decode/chunk_attention
    ABI).  The engine just threads the traced width into both compiled
    steps; the *scheduler* exploits it — out-of-window pages are parked
    and recycled, so a paged request's admission footprint is capped at
    ceil(W/page)+1 pages no matter how long it runs (docs/serving.md).
    """

    def __init__(self, cfg, container, *, slots: int, max_len: int,
                 chunk: int = 16, prefill_mode: str = "chunked",
                 paged: bool = False, num_pages: int | None = None,
                 window: int | None = None, quantize: str | None = None,
                 memory_budget: int | None = None):
        if prefill_mode not in ("chunked", "decode"):
            raise ValueError(f"unknown prefill_mode {prefill_mode!r}")
        if chunk < 1 or chunk > max_len:
            raise ValueError(f"chunk {chunk} outside [1, max_len={max_len}]")
        if paged and prefill_mode != "chunked":
            raise ValueError("paged cache requires prefill_mode='chunked'")
        if window is not None and window < 1:
            raise ValueError(f"sliding window of {window} tokens")
        if quantize == "none":
            quantize = None
        if quantize is not None and quantize not in ("int8", "fp8"):
            raise ValueError(f"quantize must be int8/fp8/none, got {quantize!r}")
        self.cfg = cfg
        self.slots = slots
        self.max_len = max_len
        self.chunk = chunk
        self.prefill_mode = prefill_mode
        self.paged = paged
        self.window = window
        self.quantize = quantize
        host_log()
        shape = ShapeConfig("serve", max_len, slots, "decode")
        self.dep = make_deployment(
            cfg, shape, container.mesh,
            options=DeployOptions(donate=False, kv_quantize=quantize),
            binding=container.binding,
        )
        self.model = self.dep.model
        self.pool = PagedPool(slots, max_len, chunk, num_pages) if paged else None
        # admission control for the deployment itself: the footprint is
        # priced from abstract shapes and checked against the budget
        # BEFORE any weight or cache buffer exists, so an over-budget
        # config is rejected instead of OOM-killed mid-allocation.
        self.footprint = estimate_footprint(
            self.model, slots=slots, max_len=max_len, quantize=quantize,
            paged=paged, num_pages=self.pool.num_pages if paged else None,
            page_size=chunk if paged else None)
        if (memory_budget is not None
                and self.footprint["total_bytes"] > memory_budget):
            raise DeploymentRejected(self.footprint, memory_budget)
        # params and cache are created by compiled programs on the
        # container's own devices: nothing lands on the default device
        # first, and no fp32 init temporary outlives its leaf
        mesh = container.mesh
        whole = NamedSharding(mesh, PartitionSpec())
        params = jax.jit(self.model.init, out_shardings=self.dep.param_sharding)(
            jax.random.PRNGKey(0))
        if quantize is not None:
            from repro.checkpoint.manifest import quantize_tree

            # storage-form {"q", "scale"} subtrees no longer match the
            # per-leaf sharding tree, so quantized serving keeps them
            # whole on the mesh (the single-host serving path)
            self.params = jax.device_put(quantize_tree(params, quantize), whole)
        else:
            self.params = params
        if paged:
            self.cache = jax.jit(
                lambda: self.model.init_paged_cache(self.pool.num_pages, chunk, slots),
                out_shardings=whole)()
        else:
            self.cache = jax.jit(lambda: self.model.init_cache(slots, max_len),
                                 out_shardings=whole)()
        self._prefill = jax.jit(self.model.prefill_into)
        self._decode = jax.jit(self.model.decode)
        self.prefill_calls = 0
        self.decode_calls = 0

    # -- prefill ----------------------------------------------------------
    @property
    def prefill_unit(self) -> int:
        """Prompt tokens ingested per prefill_step call."""
        return self.chunk if self.prefill_mode == "chunked" else 1

    def prefill_step(self, slot: int, tokens: np.ndarray, pos: int):
        """Ingest one prefill unit into `slot` at cache position `pos`.

        tokens: (n,) int32 with 1 <= n <= prefill_unit.  Returns the
        logits (vocab,) of tokens[-1] in chunked mode, None in decode
        (baseline) mode — mirroring the old server, which discarded
        them and re-fed the last prompt token at position L to recover
        them, both wasting a tick AND conditioning the first generated
        token on a duplicated context token.  table7's baseline row
        prices the tick; tests/test_serving.py pins the replay.
        """
        n = int(tokens.shape[0])
        if self.prefill_mode == "chunked":
            with span("repro.prefill.upload"):
                buf = np.zeros((1, self.chunk), np.int32)
                buf[0, :n] = tokens
                kw = {}
                if self.paged:
                    kw["block_row"] = jnp.asarray(self.pool.block_tables[slot])
                if self.window is not None:
                    kw["window"] = jnp.int32(self.window)
                tok = jnp.asarray(buf)
                where = (jnp.int32(slot), jnp.int32(pos), jnp.int32(n))
            with span("repro.prefill.dispatch"):
                logits, self.cache = self._prefill(self.params, tok, self.cache,
                                                   *where, **kw)
            self.prefill_calls += 1
            with span("repro.prefill.wait"):
                # the last token's row, sliced on the device behind the step
                last = logits[0].block_until_ready()
            with span("repro.prefill.pull"):
                return np.asarray(last)
        # baseline: one whole-batch decode tick per prompt token
        assert n == 1
        tok = np.zeros((self.slots, 1), np.int32)
        tok[slot, 0] = int(tokens[0])
        posv = np.full(self.slots, self.max_len - 1, np.int32)
        posv[slot] = pos
        act = np.zeros(self.slots, bool)
        act[slot] = True
        kw = {}
        if self.window is not None:
            kw["window"] = jnp.int32(self.window)
        _, self.cache = self._decode(
            self.params, jnp.asarray(tok), self.cache,
            jnp.asarray(posv), jnp.asarray(act), **kw,
        )
        self.decode_calls += 1
        return None

    # -- decode -----------------------------------------------------------
    def decode_step(self, tokens: np.ndarray, pos: np.ndarray,
                    active: np.ndarray) -> np.ndarray:
        """One batched decode tick.  tokens (slots, 1), pos (slots,),
        active (slots,) bool; returns (slots, vocab) logits (garbage on
        inactive rows)."""
        with span("repro.decode.upload"):
            kw = {}
            if self.paged:
                kw["block_tables"] = jnp.asarray(self.pool.block_tables)
            if self.window is not None:
                kw["window"] = jnp.int32(self.window)
            tok, at, act = jnp.asarray(tokens), jnp.asarray(pos), jnp.asarray(active)
        with span("repro.decode.dispatch"):
            logits, self.cache = self._decode(self.params, tok, self.cache, at, act,
                                              **kw)
        self.decode_calls += 1
        with span("repro.decode.wait"):
            logits.block_until_ready()
        with span("repro.decode.pull"):
            return np.asarray(logits)

    # -- KV handoff (the fleet's slot migration) --------------------------
    def export_slot(self, slot: int, n_tokens: int) -> tuple[dict, int]:
        """One slot's cache state out of the paged pools, for a KV handoff.

        ``n_tokens`` is the number of positions written so far (prompt
        length right after prefill; prompt + decoded on a mid-decode
        migration).  Returns ``(arrays, pages_used)``: the slot's written
        pages in block-table order plus its SSM rows, as host numpy —
        what `repro.tuning.bundle.KVHandoff` serializes.  Paged mode
        only: the contiguous layout has no per-slot page identity to
        ship.
        """
        if not self.paged:
            raise ValueError("slot export requires the paged cache")
        if n_tokens < 1:
            raise ValueError(f"export of {n_tokens} tokens")
        pages_used = -(-n_tokens // self.pool.page_size)
        pages = self.pool.block_tables[slot][:pages_used]
        return self.model.export_paged_slot(self.cache, pages, slot), pages_used

    def import_slot(self, slot: int, arrays: dict, pages_used: int) -> None:
        """Scatter a KV handoff into this engine's own pages.

        The receiving scheduler already leased this slot's pages from
        its own allocator (`Scheduler.adopt`); the handoff's page stack
        lands in the first ``pages_used`` entries of the slot's block
        table — page *numbering* never crosses replicas, only contents.
        """
        if not self.paged:
            raise ValueError("slot import requires the paged cache")
        pages = self.pool.block_tables[slot][:pages_used]
        self.cache = self.model.import_paged_slot(self.cache, arrays,
                                                  pages, slot)


class Scheduler:
    """Continuous batching policy: pure python, deterministic, no jax.

    One `tick()` is the scheduling quantum:

      1. **admit** — pop FCFS from the queue into free slots (requests
         were budget-checked at submit; admission just assigns slots).
      2. **prefill** — run up to `interleave` prefill work units, FCFS
         across prefilling requests.  The interleave ratio is the
         latency knob: higher drains prompts faster (better TTFT under
         prefill backlog), lower keeps decode ticks flowing (better
         per-token latency for running requests).
      3. **decode** — one batched decode tick if anything is decoding.

    Admission control (at `submit`):
      * queue bounded at `queue_depth` — excess rejected (queue-full);
      * `max_new` clamped to `max_new_cap`;
      * **contiguous**: the prompt+generation budget must fit one slot's
        cache window: prompt_len + max_new <= max_len AND every chunk's
        C-wide write window stays in bounds (ceil(prompt_len/C)*C <=
        max_len — conservative: the whole window is reserved up front);
        the baseline path needs one extra slot for its duplicated last
        prompt token.  Unfit requests are rejected (too-long), never
        queued — a queued request is guaranteed servable.
      * **paged**: the budget is counted in *pages actually needed*
        (ceil(budget / page)); a request is rejected only when that can
        never be satisfied (more pages than the block table holds or
        than exist in the pool).  A satisfiable request that finds the
        pool momentarily exhausted *queues* — `_admit` allocates pages
        FCFS and stops at the first request the pool cannot serve yet,
        so it admits as soon as a completion frees pages.

    The clock is injected so tests can drive TTFT accounting with a
    deterministic fake; the engine is injected so policy tests need no
    compiled model at all.

    **Fleet mode** (repro.serving) runs one Scheduler per replica as
    that replica's *local* policy.  ``on_handoff`` turns a scheduler
    into a prefill-pool policy: when a request's prompt is fully
    ingested it emits the first token, then — instead of decoding —
    calls the hook (with the slot still held, so the fleet can export
    the pages), releases the slot/pages locally, and marks the request
    HANDOFF.  `adopt` is the decode-pool counterpart: place a
    handed-off request straight into a free slot with pages leased from
    THIS engine's allocator, no queue and no prefill.
    """

    def __init__(self, engine, *, queue_depth: int = 64,
                 max_new_cap: int = 1 << 30, interleave: int = 2,
                 clock: Callable[[], float] = time.monotonic,
                 on_handoff: Callable[[Request], None] | None = None):
        if on_handoff is not None and engine.prefill_mode != "chunked":
            raise ValueError("handoff (prefill-pool role) requires chunked "
                             "prefill: the final chunk's logits are the "
                             "first token the handoff carries")
        self.engine = engine
        self.paged = bool(getattr(engine, "paged", False))
        # sliding-window width (getattr: policy tests drive fakes that
        # predate the windowed engine)
        self.window = getattr(engine, "window", None)
        self.queue_depth = queue_depth
        self.max_new_cap = max_new_cap
        self.interleave = max(1, interleave)
        self.clock = clock
        self.on_handoff = on_handoff
        self.queue: deque[Request] = deque()
        self.active: list[Request | None] = [None] * engine.slots
        # sliding-window page recycling: physical pages whose logical
        # block fell out of the attention window, banked per request
        # (keyed by order) until the write head claims a new block
        self._spare: dict[int, list[int]] = {}
        self.rejected: dict[str, int] = {}
        self.submitted = 0
        self.completed = 0
        self.handed_off = 0
        self.adopted = 0
        self.peak_active = 0
        self.ticks = 0
        # (pages allocated, pages holding written tokens) per tick — the
        # fragmentation series the table7 --paged scoreboard reports and
        # consolidated_stats() aggregates
        self.page_samples: list[tuple[int, int]] = []

    # -- admission --------------------------------------------------------
    def _budget(self, prompt_len: int, max_new: int) -> int:
        """Highest cache position + 1 this request can touch."""
        c = self.engine.prefill_unit
        chunks_end = -(-prompt_len // c) * c       # last chunk's write window
        gen_end = prompt_len + max_new
        if self.engine.prefill_mode == "decode":
            gen_end += 1                           # baseline re-feeds last token
        return max(chunks_end, gen_end)

    def _pages_needed(self, prompt_len: int, max_new: int, *,
                      capped: bool = True) -> int:
        """Pages a request must lease up front.

        With a sliding window the footprint is *capped*: logical blocks
        wholly behind the window are parked as the write head advances
        and their physical pages re-mapped to the blocks ahead
        (`_slide_window`), so at most ceil(W/page)+1 pages — the blocks
        the window straddles plus the one being written — are ever live.
        This is what shrinks windowed admission from O(prompt+gen) to
        O(window).  `capped=False` gives the uncapped count (`adopt`
        needs it: a KV handoff scatters the full written prefix, so the
        adopting slot's table must map every written block up front).
        """
        page = self.engine.pool.page_size
        full = -(-self._budget(prompt_len, max_new) // page)
        w = self.window
        if capped and w is not None:
            return min(full, -(-w // page) + 1)
        return full

    def servable(self, prompt_len: int, max_new: int) -> bool:
        """Can this request EVER be served by this engine's geometry?
        (The admission budget check, independent of momentary load —
        the fleet router uses it against a template replica.)"""
        if prompt_len < 1:
            return False
        if self.paged:
            pool = self.engine.pool
            # the block table must index every logical block the budget
            # touches (the window caps leased pages, not logical extent)
            if (self._pages_needed(prompt_len, max_new, capped=False)
                    > pool.max_blocks):
                return False
            return (self._pages_needed(prompt_len, max_new)
                    <= pool.allocator.capacity)
        return self._budget(prompt_len, max_new) <= self.engine.max_len

    def submit(self, req: Request) -> bool:
        """Admission-checked enqueue; returns False (and records why)
        when the request is rejected."""
        self.submitted += 1
        req.max_new = min(req.max_new, self.max_new_cap)
        if not self.servable(req.prompt_len, req.max_new):
            self.rejected[REJECT_TOO_LONG] = self.rejected.get(REJECT_TOO_LONG, 0) + 1
            return False
        if len(self.queue) >= self.queue_depth:
            self.rejected[REJECT_QUEUE_FULL] = self.rejected.get(REJECT_QUEUE_FULL, 0) + 1
            return False
        if req.order < 0:
            # the fleet pre-assigns globally-unique FCFS orders (one
            # allocator may host slots from many submit counters); a
            # standalone scheduler numbers its own
            req.order = self.submitted
        req.submit_t = self.clock()
        req.state = QUEUED
        self.queue.append(req)
        return True

    def adopt(self, req: Request) -> bool:
        """Decode-pool side of a KV handoff: place a handed-off request
        straight into a free slot, leasing its remaining-budget pages
        from THIS engine's allocator (the handoff contents are scattered
        by the caller via ``engine.import_slot`` once this returns True).
        Returns False when no slot or no pages are available right now —
        the fleet keeps the artifact pending and retries, exactly like
        paged admission queues on pool exhaustion."""
        slot = next((s for s in range(self.engine.slots)
                     if self.active[s] is None), None)
        if slot is None:
            return False
        if self.paged:
            # uncapped even under a sliding window: import_slot scatters
            # the handoff's full written prefix, so every written block
            # needs a mapped page; _slide_window recycles from there
            pages = self.engine.pool.alloc(
                req.order,
                self._pages_needed(req.prompt_len, req.max_new, capped=False),
            )
            if pages is None:
                return False
            self.engine.pool.assign(slot, pages)
        req.slot = slot
        req.state = DECODING
        if req.admit_t is None:        # a handoff keeps its first admission
            req.admit_t = self.clock()
        self.active[slot] = req
        self.adopted += 1
        self.peak_active = max(
            self.peak_active, sum(r is not None for r in self.active)
        )
        return True

    def _admit(self) -> None:
        for s in range(self.engine.slots):
            if not self.queue:
                break
            if self.active[s] is not None:
                continue
            if self.paged:
                # FCFS in pages: allocate head-of-line's pages or wait —
                # skipping ahead would starve long requests forever
                req = self.queue[0]
                pages = self.engine.pool.alloc(
                    req.order, self._pages_needed(req.prompt_len, req.max_new)
                )
                if pages is None:
                    break                          # out of pages: stay queued
                self.queue.popleft()
                self.engine.pool.assign(s, pages)
            else:
                req = self.queue.popleft()
            req.slot = s
            req.state = PREFILLING
            req.prefill_pos = 0
            req.admit_t = self.clock()
            self.active[s] = req

    # -- lifecycle helpers ------------------------------------------------
    def _emit(self, req: Request, token: int, out: list) -> None:
        if req.first_token_t is None:
            req.first_token_t = self.clock()
        req.tokens.append(token)
        out.append((req.rid, token))
        if len(req.tokens) >= req.max_new:
            self._finish(req)

    def _finish(self, req: Request) -> None:
        req.state = DONE
        req.finish_t = self.clock()
        if self.paged:
            self._spare.pop(req.order, None)
            self.engine.pool.free(req.order)
            self.engine.pool.release(req.slot)
        self.active[req.slot] = None
        req.slot = None
        self.completed += 1

    def _handoff(self, req: Request) -> None:
        """Prefill-pool exit: hand the finished slot to the fleet (the
        hook exports the pages while the slot is still held), then
        release the local slot/pages — the artifact now carries the
        state, so this replica owes the request nothing further."""
        req.state = HANDOFF
        self.on_handoff(req)
        if self.paged:
            self._spare.pop(req.order, None)
            self.engine.pool.free(req.order)
            self.engine.pool.release(req.slot)
        self.active[req.slot] = None
        req.slot = None
        self.handed_off += 1

    def _slide_window(self, req: Request) -> None:
        """Sliding-window page recycling (paged + windowed engines only).

        A logical block whose last position can never be attended again
        ((j+1)*page <= head - W) is *dead*: its table entry is parked —
        the kernel's gather then reads the poison-inert park page and the
        window mask discards it — and its physical page is banked in the
        request's spare list.  The block the write head is about to enter
        is mapped from that bank.  Pages never return to the shared
        allocator mid-flight (another admission could snap them up and
        deadlock this request's next write); the lease cap in
        `_pages_needed` already priced the steady state, and everything
        goes back at `_finish`.  Repro note: live blocks are always the
        contiguous run [ (head-W)//page, head//page ], at most
        ceil(W/page)+1 of them — the lease cap.
        """
        pool = self.engine.pool
        w = self.window
        page = pool.page_size
        head = req.prefill_pos if req.state == PREFILLING else req.next_pos
        row = pool.block_tables[req.slot]
        spare = self._spare.setdefault(req.order, [])
        dead = max(0, head - w) // page
        spare.extend(int(p) for p in row[:dead] if p != pool.PARK)
        row[:dead] = pool.PARK
        nb = head // page                  # block the next write lands in
        if nb < pool.max_blocks and row[nb] == pool.PARK:
            # the lease cap guarantees a banked page is available here
            assert spare, "sliding-window lease underflow"
            row[nb] = spare.pop()

    # -- the quantum ------------------------------------------------------
    def tick(self) -> list[tuple[int, int]]:
        """Admit, prefill up to `interleave` units, one decode tick.
        Returns the (rid, token) pairs emitted this quantum.  Each phase
        is a span (`repro.launch.tracing`)."""
        with span("repro.tick"):
            self.ticks += 1
            with span("repro.admit"):
                self._admit()
                self.peak_active = max(
                    self.peak_active, sum(r is not None for r in self.active)
                )
            out: list[tuple[int, int]] = []

            for _ in range(self.interleave):
                req = min(
                    (r for r in self.active if r is not None and r.state == PREFILLING),
                    key=lambda r: r.order, default=None,
                )
                if req is None:
                    break
                with span("repro.prefill"):
                    self._prefill_unit(req, out)

            decoding = [r for r in self.active if r is not None and r.state == DECODING]
            if decoding:
                with span("repro.decode"):
                    logits = self._decode_batch(decoding)
                with span("repro.sample"):
                    for r in decoding:
                        r.decode_steps += 1
                        r.next_pos += 1
                        self._emit(r, int(np.argmax(logits[r.slot])), out)
            if self.paged:
                with span("repro.pages"):
                    self._sample_pages()
            return out

    def _prefill_unit(self, req: Request, out: list) -> None:
        """One prefill work unit of `req`; its first token when the
        prompt is done."""
        if self.paged and self.window is not None:
            self._slide_window(req)
        n = min(self.engine.prefill_unit, req.prompt_len - req.prefill_pos)
        window = req.prompt[req.prefill_pos : req.prefill_pos + n]
        logits = self.engine.prefill_step(req.slot, window, req.prefill_pos)
        req.prefill_steps += 1
        req.prefill_pos += n
        if req.prefill_pos >= req.prompt_len:
            req.next_pos = req.prompt_len
            req.state = DECODING
            if logits is not None:
                # chunked path: the final chunk's logits ARE the first
                # token — no decode tick spent re-feeding the prompt
                with span("repro.sample"):
                    self._emit(req, int(np.argmax(logits)), out)
            if self.on_handoff is not None and not req.done:
                # prefill-pool role: decode happens on another replica
                self._handoff(req)

    def _decode_batch(self, decoding: list[Request]) -> np.ndarray:
        """The batched decode step's inputs, and its logits."""
        if self.paged and self.window is not None:
            for r in decoding:
                self._slide_window(r)
        tok = np.zeros((self.engine.slots, 1), np.int32)
        pos = np.full(self.engine.slots, self.engine.max_len - 1, np.int32)
        act = np.zeros(self.engine.slots, bool)
        for r in decoding:
            # baseline seeds from the re-fed last prompt token (its
            # prefill discarded the logits); chunked always has tokens
            tok[r.slot, 0] = r.tokens[-1] if r.tokens else int(r.prompt[-1])
            pos[r.slot] = r.next_pos
            act[r.slot] = True
        return self.engine.decode_step(tok, pos, act)

    def _sample_pages(self) -> None:
        """Pages allocated against pages holding written tokens."""
        page = self.engine.pool.page_size
        w = self.window
        used = 0
        for r in self.active:
            if r is None:
                continue
            head = r.prefill_pos if r.state == PREFILLING else r.next_pos
            written = -(-head // page)
            if w is not None:
                # recycled (out-of-window) blocks no longer hold
                # readable tokens — count only the live window
                written -= max(0, head - w) // page
            used += written
        self.page_samples.append((self.engine.pool.allocator.used, used))

    @property
    def idle(self) -> bool:
        return not self.queue and all(r is None for r in self.active)

    def consolidated_stats(self) -> dict[str, float]:
        """The schema-pinned serving counters, pool occupancy included.

        Every key in SERVING_STATS_SCHEMA is always present (0 on the
        contiguous path), mirroring the dispatch layer's consolidated
        stats: printers iterate the schema, so a new counter cannot be
        silently dropped from any output, and the per-tick
        ``page_samples`` series — previously reachable only from the
        benchmark — aggregates here for every consumer.
        """
        samples = self.page_samples
        alloc_mean = (sum(a for a, _ in samples) / len(samples)
                      if samples else 0.0)
        written_mean = (sum(w for _, w in samples) / len(samples)
                        if samples else 0.0)
        stats: dict[str, float] = {
            "submitted": self.submitted,
            "completed": self.completed,
            "rejected-queue-full": self.rejected.get(REJECT_QUEUE_FULL, 0),
            "rejected-too-long": self.rejected.get(REJECT_TOO_LONG, 0),
            "handed-off": self.handed_off,
            "adopted": self.adopted,
            "peak-active": self.peak_active,
            "ticks": self.ticks,
            "pages-capacity": (self.engine.pool.allocator.capacity
                               if self.paged else 0),
            "pages-allocated-mean": alloc_mean,
            "pages-written-mean": written_mean,
            "pages-allocated-peak": (max((a for a, _ in samples), default=0)
                                     if self.paged else 0),
            "fragmentation-pct": (100.0 * (1.0 - written_mean / alloc_mean)
                                  if alloc_mean else 0.0),
        }
        assert set(stats) == SERVING_STATS_SCHEMA
        return stats


class Server:
    """Scheduler + JaxEngine + request log — what main() and the
    benchmark drive.  `submit` admission-checks and records, `run`
    ticks until idle, `requests` holds every Request (accepted or not)
    with its full serving record."""

    def __init__(self, cfg, container, *, slots: int, max_len: int,
                 chunk: int = 16, prefill_mode: str = "chunked",
                 queue_depth: int = 64, max_new_cap: int = 1 << 30,
                 interleave: int = 2, paged: bool = False,
                 num_pages: int | None = None, window: int | None = None,
                 quantize: str | None = None,
                 memory_budget: int | None = None,
                 clock: Callable[[], float] = time.monotonic):
        self.engine = JaxEngine(cfg, container, slots=slots, max_len=max_len,
                                chunk=chunk, prefill_mode=prefill_mode,
                                paged=paged, num_pages=num_pages,
                                window=window, quantize=quantize,
                                memory_budget=memory_budget)
        self.scheduler = Scheduler(self.engine, queue_depth=queue_depth,
                                   max_new_cap=max_new_cap,
                                   interleave=interleave, clock=clock)
        self.requests: list[Request] = []

    def submit(self, req: Request) -> bool:
        self.requests.append(req)
        return self.scheduler.submit(req)

    def step(self) -> list[tuple[int, int]]:
        return self.scheduler.tick()

    def run(self, max_ticks: int = 1 << 20) -> None:
        """Tick until every accepted request completes."""
        ticks = 0
        while not self.scheduler.idle:
            self.step()
            ticks += 1
            if ticks > max_ticks:
                raise RuntimeError("scheduler failed to drain (livelock?)")

    # old name, kept for callers of the previous server
    drain = run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-14b")
    ap.add_argument("--layers", type=int, default=None, metavar="N",
                    help="serve the published widths in the config's own "
                         "dtype, cut to the first N layers (default: the "
                         "reduced CPU config)")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--chunk", type=int, default=16,
                    help="prefill chunk width C: each compiled prefill step "
                         "ingests C prompt tokens into one slot")
    ap.add_argument("--prefill-mode", choices=("chunked", "decode"),
                    default="chunked",
                    help="'decode' replays the old prefill-by-decode loop "
                         "(O(prompt_len) whole-batch ticks) as a baseline")
    ap.add_argument("--paged", action="store_true",
                    help="page the KV cache (page size = --chunk) with "
                         "per-slot block tables; admission budgets in pages "
                         "actually needed (requires chunked prefill)")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="paged pool size incl. the reserved park page "
                         "(default: 1 + slots * ceil(max_len/chunk), the "
                         "contiguous layout's capacity)")
    ap.add_argument("--window", type=int, default=None, metavar="W",
                    help="sliding-window attention: every token attends "
                         "only its trailing W keys; with --paged, "
                         "out-of-window pages are parked and recycled, "
                         "capping each request's admission footprint at "
                         "ceil(W/chunk)+1 pages")
    ap.add_argument("--quantize", choices=("none", "int8", "fp8"),
                    default="none",
                    help="serve with 1-byte weights (quant_matmul storage "
                         "subtrees) and a quantized KV cache — ~4x smaller "
                         "fp32 footprint (docs/quantization.md)")
    ap.add_argument("--memory-budget", type=int, default=None, metavar="BYTES",
                    help="reject the deployment (DeploymentRejected) if the "
                         "estimated weights+KV footprint exceeds this")
    ap.add_argument("--queue-depth", type=int, default=64,
                    help="admission control: submits beyond this queue depth "
                         "are rejected, not buffered")
    ap.add_argument("--interleave", type=int, default=2,
                    help="prefill work units per scheduler tick (the "
                         "prefill/decode interleave ratio)")
    ap.add_argument("--native-ops", action="store_true",
                    help="swap in native kernels where the platform has them "
                         "(or set REPRO_NATIVE_OPS=1; references have no "
                         "tuner, so autotune needs this)")
    ap.add_argument("--profile", action="store_true",
                    help="capture op geometries into REPRO_WORKLOAD_PROFILE "
                         "(feed repro.tuning.warm; or set REPRO_PROFILE=1)")
    ap.add_argument("--autotune", action="store_true",
                    help="resolve kernel configs from the site tuning cache "
                         "(or set REPRO_AUTOTUNE=1)")
    ap.add_argument("--max-tuned-entries", type=int, default=None, metavar="K",
                    help="per-op cap on the geometry-dispatch table; cold "
                         "cached buckets beyond it are LRU-evicted "
                         "(or set REPRO_TUNING_MAX_ENTRIES)")
    ap.add_argument("--tuning-bundle", default=None, metavar="PATH",
                    help="portable tuning bundle to import before binding "
                         "(python -m repro.tuning.bundle export; or set "
                         "REPRO_TUNING_BUNDLE) — entries revalidate against "
                         "this platform, so a laptop-warmed artifact deploys "
                         "here with zero searches")
    args = ap.parse_args(argv)

    enable_compile_cache()
    reduced = args.layers is None
    bundle = make_bundle(args.arch, reduced=reduced, num_layers=args.layers)
    runtime = Runtime()
    container = runtime.deploy(bundle, devices=jax.devices()[:1],
                               native_ops=True if args.native_ops else None,
                               profile=True if args.profile else None,
                               autotune=True if args.autotune else None,
                               max_tuned_entries=args.max_tuned_entries,
                               tuning_bundle=args.tuning_bundle)
    cfg = serving_config(args.arch, reduced=reduced, num_layers=args.layers)

    try:
        server = Server(cfg, container, slots=args.slots, max_len=args.max_len,
                        chunk=args.chunk, prefill_mode=args.prefill_mode,
                        queue_depth=args.queue_depth, paged=args.paged,
                        num_pages=args.num_pages, window=args.window,
                        quantize=args.quantize,
                        memory_budget=args.memory_budget)
    except DeploymentRejected as e:
        print(f"deployment rejected: {e}")
        runtime.cleanup()
        return 2
    fp = server.engine.footprint
    print(f"footprint: weights {fp['weight_bytes']:,}B + "
          f"kv {fp['kv_bytes']:,}B = {fp['total_bytes']:,}B "
          f"(quantize={fp['quantize']})")
    rng = np.random.default_rng(0)
    t0 = time.time()
    for rid in range(args.requests):
        prompt = rng.integers(0, cfg.vocab_size, size=rng.integers(2, 6)).astype(np.int32)
        server.submit(Request(rid=rid, prompt=prompt, max_new=args.max_new))
    server.run()
    dt = time.time() - t0

    done = [r for r in server.requests if r.done]
    total_tokens = sum(len(r.tokens) for r in done)
    ttfts = sorted(r.ttft for r in done)
    print(f"served {len(done)} requests / {total_tokens} tokens "
          f"in {dt:.2f}s ({total_tokens / max(dt, 1e-9):.1f} tok/s, "
          f"prefill_mode={args.prefill_mode})")
    if ttfts:
        print(f"TTFT p50 {ttfts[len(ttfts) // 2] * 1e3:.1f}ms "
              f"max {ttfts[-1] * 1e3:.1f}ms | compiled steps: "
              f"prefill={server.engine.prefill_calls} "
              f"decode={server.engine.decode_calls}")
    if server.scheduler.rejected:
        print("rejected: " + " ".join(
            f"{k}={v}" for k, v in sorted(server.scheduler.rejected.items())))
    if args.paged:
        pool = server.engine.pool
        stats = server.scheduler.consolidated_stats()
        print(f"paged pool: {pool.num_pages} pages x {pool.page_size} tokens "
              f"(park+{int(stats['pages-capacity'])}) | "
              f"peak_active={int(stats['peak-active'])} | "
              f"pages allocated/used mean "
              f"{stats['pages-allocated-mean']:.1f}"
              f"/{stats['pages-written-mean']:.1f} "
              f"(fragmentation {stats['fragmentation-pct']:.0f}%)")
    if container.workload is not None:
        print(f"captured {len(container.workload)} op geometries -> "
              f"{container.workload.path} (warm with: python -m repro.tuning.warm)")
    print_dispatch_stats(container)
    runtime.cleanup()
    return 0


def print_dispatch_stats(container) -> None:
    """Per-op geometry-dispatch stats after an autotuned run, from the one
    consolidated (schema-pinned) stats dict: how many compiled geometries
    resolved their own tuned entry (exact) vs fell back to the nearest
    bucket, a dtype-crossing borrow, a demoted bundle candidate, or the
    platform default — plus table fullness/size and the bind-time
    lifecycle counters (LRU eviction, bundle import outcomes).  Iterating
    the schema (not an ad hoc format string) is what guarantees a new
    counter cannot be silently dropped from this output."""
    if not container.autotune:
        return
    from repro.tuning.dispatch import DISPATCH_PATHS, consolidated_stats

    if container.tuning_imports is not None:
        c = container.tuning_imports.counts()
        print(f"tuning bundle [{container.tuning_imports.source}]: "
              + " ".join(f"{k}={v}" for k, v in sorted(c.items())))
    reports = {r.op: r for r in container.binding.reports}
    for name in container.binding:
        impl = container.binding.impl(name)
        dispatch = getattr(impl.fn, "stats", None)
        # impl.config survives the profiled_binding wrap; impl.fn.stats is
        # forwarded through it, but consolidated_stats needs the dispatch
        # object itself — reconstruct a view from config + stats
        table = getattr(impl, "config", None)
        if dispatch is None or table is None or not hasattr(table, "stats"):
            continue
        if not sum(dispatch.values()):
            continue
        # the profiled wrapper hides the TunedDispatch instance but forwards
        # its counters; a facade with .stats/.table is all the consolidation
        # needs
        view = types.SimpleNamespace(stats=dispatch, table=table)
        stats = consolidated_stats(view, reports[name].geometries)
        total = sum(stats[p] for p in DISPATCH_PATHS)
        parts = " ".join(f"{p}={stats[p]}" for p in DISPATCH_PATHS)
        line = (f"dispatch {name:<18} {total} "
                f"geometr{'y' if total == 1 else 'ies'} traced: {parts}")
        line += (f" | table {stats['table-entries']}"
                 + (f"/{stats['table-cap']}" if stats["table-cap"] else "")
                 + (f" (+{stats['table-demoted']} demoted)"
                    if stats["table-demoted"] else "")
                 + f" ~{stats['table-bytes']}B")
        lifecycle = " ".join(
            f"{k}={stats[k]}" for k in ("evicted-lru", "bundle-imported",
                                        "bundle-demoted", "bundle-rejected")
            if stats[k])
        if lifecycle:
            line += f" | {lifecycle}"
        print(line)


if __name__ == "__main__":
    raise SystemExit(main())
