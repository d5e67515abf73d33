"""FleetScheduler: disaggregated prefill/decode serving.

The fleet splits serving across two replica pools.  **Prefill**
replicas ingest prompts with chunked prefill; the moment a prompt's
final chunk emits the first token, the slot's cache state leaves the
replica as a **KV handoff** — the per-slot page contents plus recurrent
(SSM) rows, serialized through the same checksummed-manifest artifact
path as tuning bundles (`repro.tuning.bundle.KVHandoff`).  **Decode**
replicas adopt pending handoffs into free slots (leasing pages from
their *own* allocator — page numbers never cross replicas) and tick
them to completion.

Because decoding is greedy and every replica runs the same params,
migration is token-exact: the fleet's output for a request set is
identical to a single-host chunked server's (pinned by
tests/test_fleet.py and the --fleet benchmark).  The same property
powers crash recovery — when a replica dies, its in-flight requests
are re-submitted as *prompt + tokens-emitted-so-far* with the
remaining budget, and the re-prefilled continuation picks up exactly
where the lost replica stopped.

Bookkeeping is split between user-facing **records** (the Request the
caller submitted: accumulates tokens, timestamps, step counts across
any number of migrations) and internal **work items** (the Request
clone a replica actually holds; replaced wholesale on crash recovery).
The KVHandoff bytes carry the engine state across the pool boundary;
the work item carries the scheduling metadata.  All timing flows from
one injected clock, so the whole fleet — elastic controller included —
is deterministic under a fake clock.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Callable

import numpy as np

from repro.launch.serve import (
    DECODING,
    DONE,
    HANDOFF,
    QUEUED,
    REJECT_QUEUE_FULL,
    REJECT_TOO_LONG,
    Request,
)
from repro.serving.replica import ACTIVE, DEAD, DRAINED, JOINING, Replica
from repro.tuning.bundle import KVHandoff

__all__ = ["FleetScheduler"]


class FleetScheduler:
    """Routes requests across prefill/decode replica pools.

    ``factory(role, host_id) -> Replica`` provisions capacity — the
    constructor uses it for the initial pools and the elastic
    controller uses it to grow the decode pool at runtime (each new
    decode replica warm-starts from a tuning bundle; see
    serving/elastic.py).

    Per tick: controller step (deaths, stragglers, rescale) -> activate
    joiners -> route queue into prefill slots -> tick every replica ->
    adopt pending handoffs FCFS -> merge emissions into records.
    """

    def __init__(self, factory: Callable[[str, int], Replica], *,
                 prefill: int = 1, decode: int = 1,
                 clock: Callable[[], float] = time.monotonic,
                 queue_depth: int = 64, max_new_cap: int = 1 << 30,
                 controller=None):
        self.factory = factory
        self.clock = clock
        self.queue_depth = queue_depth
        self.max_new_cap = max_new_cap
        self.prefill_pool: list[Replica] = []
        self.decode_pool: list[Replica] = []
        self.records: dict[int, Request] = {}
        self.items: dict[int, Request] = {}
        # rid -> replica id currently holding the item (None while the
        # item is queued or its state travels as a handoff artifact)
        self.owner: dict[int, int | None] = {}
        self.queue: deque[int] = deque()
        self.pending_handoffs: deque[bytes] = deque()
        self.events: list[str] = []
        self.rejected: dict[str, int] = {}
        self.submitted = 0
        self.completed = 0
        self.handoffs = 0
        self.adoptions = 0
        self.recovered = 0
        self.handoff_bytes = 0
        self.ticks = 0
        self._next_host = 0
        self._order = 0
        self._now = clock()
        self._blocked_rid: int | None = None
        for _ in range(max(1, prefill)):
            self.add_replica("prefill")
        for _ in range(max(1, decode)):
            self.add_replica("decode")
        self.controller = controller
        if controller is not None:
            controller.attach(self)

    # -- pool management ---------------------------------------------------
    def replicas(self) -> list[Replica]:
        return self.prefill_pool + self.decode_pool

    def add_replica(self, role: str, *, join_at: float | None = None) -> Replica:
        """Provision one replica through the factory.  With a future
        ``join_at`` the replica starts JOINING (the controller's
        provision delay) and activates once the clock reaches it."""
        rep = self.factory(role, self._next_host)
        self._next_host += 1
        if role == "prefill":
            rep.set_handoff_hook(
                lambda req, _rep=rep: self._on_handoff(_rep, req))
            self.prefill_pool.append(rep)
        else:
            self.decode_pool.append(rep)
        if join_at is not None and join_at > self._now:
            rep.state = JOINING
            rep.join_at = join_at
        return rep

    def _remove(self, rep: Replica) -> None:
        for pool in (self.prefill_pool, self.decode_pool):
            if rep in pool:
                pool.remove(rep)

    def decode_demand(self) -> int:
        """Open work items — what pool_rescale_plan sizes the decode
        pool against (everything accepted and not yet done will need a
        decode slot)."""
        return sum(1 for r in self.records.values() if r.state != DONE)

    # -- admission ---------------------------------------------------------
    def submit(self, req: Request) -> bool:
        """Admission-checked intake.  The geometry check runs against a
        template replica from EACH pool: a request a prefill replica
        could serve but no decode replica could adopt must be rejected
        up front, not discovered as a stuck handoff."""
        self.submitted += 1
        req.max_new = min(req.max_new, self.max_new_cap)
        templates = [p[0] for p in (self.prefill_pool, self.decode_pool) if p]
        if not all(t.scheduler.servable(req.prompt_len, req.max_new)
                   for t in templates):
            self.rejected[REJECT_TOO_LONG] = \
                self.rejected.get(REJECT_TOO_LONG, 0) + 1
            return False
        if len(self.queue) >= self.queue_depth:
            self.rejected[REJECT_QUEUE_FULL] = \
                self.rejected.get(REJECT_QUEUE_FULL, 0) + 1
            return False
        req.state = QUEUED
        req.submit_t = self.clock()
        self.records[req.rid] = req
        item = Request(rid=req.rid, prompt=np.asarray(req.prompt, np.int32),
                       max_new=req.max_new)
        self._order += 1
        item.order = self._order    # fleet-global FCFS / allocator-owner id
        self.items[req.rid] = item
        self.owner[req.rid] = None
        self.queue.append(req.rid)
        return True

    # -- handoff path ------------------------------------------------------
    def _export(self, rep: Replica, req: Request) -> None:
        """Serialize a slot's cache state into a pending KVHandoff
        artifact.  Must run while the slot (and its pages) are still
        held by ``rep``."""
        arrays, pages_used = rep.engine.export_slot(req.slot, req.next_pos)
        blob = KVHandoff(
            rid=req.rid, source=rep.name, next_pos=req.next_pos,
            pages_used=pages_used, page_size=rep.engine.pool.page_size,
            arrays=arrays,
        ).to_bytes()
        self.pending_handoffs.append(blob)
        self.owner[req.rid] = None
        self.handoffs += 1
        self.handoff_bytes += len(blob)
        self.events.append(
            f"t={self._now:.1f} handoff rid={req.rid} from {rep.name} "
            f"({len(blob)} bytes, {pages_used} page(s))")

    def _on_handoff(self, rep: Replica, req: Request) -> None:
        # Scheduler._handoff hook: slot still held, pages still leased
        self._export(rep, req)

    def _adopt_pending(self) -> None:
        """Place pending handoffs onto decode replicas, strictly FCFS:
        a blocked head-of-line artifact waits for capacity rather than
        being overtaken (the same no-starvation rule as paged
        admission)."""
        while self.pending_handoffs:
            h = KVHandoff.from_bytes(self.pending_handoffs[0])
            item = self.items.get(h.rid)
            if item is None or self.records[h.rid].state == DONE:
                # stale artifact (request finished via crash recovery)
                self.pending_handoffs.popleft()
                continue
            if not self._try_adopt(h, item):
                if self._blocked_rid != h.rid:
                    self._blocked_rid = h.rid
                    self.events.append(
                        f"t={self._now:.1f} adoption of rid={h.rid} waiting "
                        f"for decode capacity")
                break
            self.pending_handoffs.popleft()
            self._blocked_rid = None

    def _try_adopt(self, h: KVHandoff, item: Request) -> bool:
        # least-loaded first (pool order breaks ties): handoffs spread
        # over the decode pool instead of filling its first replica
        for rep in sorted(self.decode_pool, key=lambda r: -r.free_slots()):
            if not (rep.alive and rep.state == ACTIVE):
                continue
            if rep.engine.pool.page_size != h.page_size:
                raise ValueError(
                    f"handoff rid={h.rid} page_size {h.page_size} != "
                    f"{rep.name} page_size {rep.engine.pool.page_size}")
            if rep.scheduler.adopt(item):
                rep.engine.import_slot(item.slot, dict(h.arrays), h.pages_used)
                self.owner[h.rid] = rep.id
                self.adoptions += 1
                self.events.append(
                    f"t={self._now:.1f} adopt rid={h.rid} on {rep.name} "
                    f"(pos {h.next_pos})")
                return True
        return False

    # -- fault handling ----------------------------------------------------
    def on_replica_dead(self, rep: Replica, now: float) -> int:
        """Crash recovery: the replica's engine state is gone, so every
        item it held is re-submitted as prompt + emitted tokens with
        the remaining budget — greedy decoding makes the re-prefilled
        continuation token-identical to the lost one.  Returns the
        number of requests recovered."""
        rep.state = DEAD
        rep.alive = False
        self._remove(rep)
        lost = [rid for rid, oid in self.owner.items() if oid == rep.id]
        self.events.append(
            f"t={now:.1f} {rep.name} dead; recovering {len(lost)} request(s)")
        for rid in lost:
            self.owner[rid] = None
            rec = self.records[rid]
            item = self.items.get(rid)
            if item is None or rec.state == DONE:
                continue
            rec.prefill_steps += item.prefill_steps
            rec.decode_steps += item.decode_steps
            replacement = Request(
                rid=rid,
                prompt=np.concatenate([np.asarray(rec.prompt, np.int32),
                                       np.asarray(rec.tokens, np.int32)]),
                max_new=rec.max_new - len(rec.tokens),
            )
            replacement.order = item.order   # keeps FCFS seniority
            self.items[rid] = replacement
            self.queue.appendleft(rid)       # head of line: it was here first
            self.recovered += 1
            self.events.append(
                f"t={now:.1f} requeue rid={rid}: {len(rec.tokens)} emitted, "
                f"{replacement.max_new} remaining")
        return len(lost)

    def drain_replica(self, rep: Replica, now: float,
                      reason: str = "drain") -> int:
        """Graceful retirement (straggler eviction, scale-in): decoding
        slots leave as KV handoffs — no tokens are lost and no work is
        redone — while not-yet-prefilled slots and the local queue go
        back to the global queue.  Returns exported-slot count."""
        exported = 0
        for req in list(rep.active_requests()):
            if req.state == DECODING:
                self._export(rep, req)
                req.state = HANDOFF
                exported += 1
            else:       # PREFILLING: partial chunks can't migrate; redo
                self.queue.appendleft(req.rid)
                self.owner[req.rid] = None
                req.state = QUEUED
            if rep.scheduler.paged:
                rep.engine.pool.free(req.order)
                rep.engine.pool.release(req.slot)
            rep.scheduler.active[req.slot] = None
            req.slot = None
        while rep.scheduler.queue:
            q = rep.scheduler.queue.pop()
            self.queue.appendleft(q.rid)
            self.owner[q.rid] = None
        rep.state = DRAINED
        self._remove(rep)
        self.events.append(
            f"t={now:.1f} drain {rep.name} ({reason}): {exported} slot(s) "
            f"exported")
        return exported

    # -- the fleet quantum -------------------------------------------------
    def _route(self) -> None:
        for rep in self.prefill_pool:
            if not (rep.alive and rep.state == ACTIVE):
                continue
            avail = rep.free_slots() - len(rep.scheduler.queue)
            while avail > 0 and self.queue:
                rid = self.queue.popleft()
                if not rep.scheduler.submit(self.items[rid]):
                    self.queue.appendleft(rid)
                    return
                self.owner[rid] = rep.id
                avail -= 1

    def _merge(self, emissions: list[tuple[int, int]], now: float) -> None:
        for rid, tok in emissions:
            rec = self.records[rid]
            if rec.state == DONE:
                continue
            if rec.first_token_t is None:
                rec.first_token_t = now
            rec.tokens.append(int(tok))
        for rid in [r for r, item in self.items.items() if item.done]:
            rec = self.records[rid]
            item = self.items.pop(rid)
            if rec.state == DONE:
                continue
            rec.state = DONE
            rec.finish_t = now
            rec.prefill_steps += item.prefill_steps
            rec.decode_steps += item.decode_steps
            self.completed += 1
            self.owner.pop(rid, None)

    def tick(self) -> list[tuple[int, int]]:
        """One fleet quantum; returns every (rid, token) emitted."""
        now = self.clock()
        self._now = now
        self.ticks += 1
        if self.controller is not None:
            self.controller.step(self, now)
        for rep in self.replicas():
            if rep.state == JOINING and rep.alive and now >= rep.join_at:
                rep.state = ACTIVE
                self.events.append(f"t={now:.1f} {rep.name} active")
        self._route()
        emissions: list[tuple[int, int]] = []
        for rep in self.replicas():
            emissions.extend(rep.tick())
        self._adopt_pending()
        self._merge(emissions, now)
        return emissions

    @property
    def idle(self) -> bool:
        return (not self.queue and not self.pending_handoffs
                and all(r.state == DONE for r in self.records.values()))

    def run(self, max_ticks: int = 1 << 20) -> None:
        """Tick until every accepted request completes."""
        ticks = 0
        while not self.idle:
            self.tick()
            ticks += 1
            if ticks > max_ticks:
                raise RuntimeError("fleet failed to drain (livelock?)")

    def stats(self) -> dict[str, float]:
        return {
            "submitted": self.submitted,
            "completed": self.completed,
            "rejected-queue-full": self.rejected.get(REJECT_QUEUE_FULL, 0),
            "rejected-too-long": self.rejected.get(REJECT_TOO_LONG, 0),
            "handoffs": self.handoffs,
            "adoptions": self.adoptions,
            "recovered": self.recovered,
            "handoff-bytes": self.handoff_bytes,
            "pending-handoffs": len(self.pending_handoffs),
            "prefill-replicas": len(self.prefill_pool),
            "decode-replicas": len(self.decode_pool),
            "ticks": self.ticks,
        }
