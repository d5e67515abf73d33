"""Disaggregated serving fleet: KV handoff, crash recovery, elasticity.

Everything runs on FakeReplica (repro.serving.replica): a real paged
pool whose page contents are the fed token values, a recurrent
state/conv row per slot, and next-token = (prev + 1) % vocab — so a
lost page, a mis-scattered handoff, or a dropped SSM row turns into a
hard failure, and the expected token chain for any request is exact.
All clocks are fake; every scenario is deterministic.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.ft import (
    StragglerConfig,
    StragglerDetector,
    Supervisor,
    SupervisorConfig,
)
from repro.launch.serve import DECODING, DONE, Request, Scheduler
from repro.serving import (
    ACTIVE,
    DRAINED,
    JOINING,
    ElasticController,
    FakeFleetEngine,
    FakeReplica,
    FleetScheduler,
)
from repro.tuning.bundle import BundleFormatError, KVHandoff

VOCAB = 16


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def expected_tokens(req: Request) -> list[int]:
    last = int(req.prompt[-1])
    return [(last + k) % VOCAB for k in range(1, req.max_new + 1)]


def make_fleet(clock, *, prefill=1, decode=2, controller=None, **rep_kw):
    kw = dict(slots=2, max_len=40, chunk=4)
    kw.update(rep_kw)

    def factory(role, host_id):
        rep = FakeReplica(host_id, role, clock=clock, **kw)
        rep.set_latency(0.01)
        return rep

    return FleetScheduler(factory, prefill=prefill, decode=decode,
                          clock=clock, controller=controller)


def drive(fleet, clock, *, max_ticks=500, per_tick=None):
    for _ in range(max_ticks):
        if fleet.idle:
            return
        fleet.tick()
        clock.t += 1.0
        if per_tick is not None:
            per_tick(clock.t)
    raise AssertionError("fleet did not drain")


def seeded_requests(n, *, seed=7, max_new=5, lo=3, hi=12):
    rng = np.random.default_rng(seed)
    return [Request(rid=rid,
                    prompt=rng.integers(0, VOCAB,
                                        int(rng.integers(lo, hi))).astype(np.int32),
                    max_new=max_new)
            for rid in range(n)]


# ------------------------------------------------------- KVHandoff bytes --
def _sample_handoff():
    return KVHandoff(
        rid=3, source="prefill-0", next_pos=11, pages_used=3, page_size=4,
        arrays={"kv": np.arange(12, dtype=np.int64).reshape(3, 4),
                "state": np.array([42], np.int64)},
    )


@pytest.mark.parametrize("dtype", ["int64", "bfloat16", "float8_e4m3fn"])
def test_kv_handoff_round_trip(dtype):
    """Served caches are bf16 (or fp8 when quantized): dtypes numpy only
    knows through ml_dtypes must come back bit-exact and typed."""
    h = _sample_handoff()
    h.arrays["kv"] = h.arrays["kv"].astype(jnp.dtype(dtype))
    out = KVHandoff.from_bytes(h.to_bytes())
    assert (out.rid, out.source, out.next_pos) == (3, "prefill-0", 11)
    assert (out.pages_used, out.page_size) == (3, 4)
    np.testing.assert_array_equal(out.arrays["kv"], h.arrays["kv"])
    np.testing.assert_array_equal(out.arrays["state"], h.arrays["state"])
    assert out.arrays["kv"].dtype == jnp.dtype(dtype)


def test_kv_handoff_rejects_truncation_and_noise():
    blob = _sample_handoff().to_bytes()
    with pytest.raises(BundleFormatError):
        KVHandoff.from_bytes(blob[: len(blob) // 2])
    with pytest.raises(BundleFormatError):
        KVHandoff.from_bytes(b"not a tarball at all")


def test_kv_handoff_rejects_payload_corruption():
    # flip bytes in the payload region until the checksum path trips —
    # any accepted artifact must have a verified state member
    blob = bytearray(_sample_handoff().to_bytes())
    rejected = False
    for i in range(len(blob) - 1, len(blob) - 200, -1):
        tampered = bytearray(blob)
        tampered[i] ^= 0xFF
        try:
            KVHandoff.from_bytes(bytes(tampered))
        except BundleFormatError:
            rejected = True
            break
    assert rejected


def test_kv_handoff_rejects_bad_geometry():
    with pytest.raises(BundleFormatError):
        # 2 pages x 4 tokens cannot cover next_pos=11
        KVHandoff.from_bytes(KVHandoff(
            rid=1, source="x", next_pos=11, pages_used=2, page_size=4,
            arrays={"kv": np.zeros((2, 4), np.int64)},
        ).to_bytes())


# ------------------------------------------------- engine-level handoff --
def test_fake_engine_slot_export_import_moves_state():
    src, dst = (FakeFleetEngine(slots=2, max_len=16, chunk=4) for _ in range(2))
    src.pool.assign(0, src.pool.alloc("a", 2))
    src.prefill_step(0, np.array([3, 5, 7, 9], np.int32), 0)
    src.prefill_step(0, np.array([2], np.int32), 4)
    arrays, pages_used = src.export_slot(0, 5)
    assert pages_used == 2
    blob = KVHandoff(rid=0, source="s", next_pos=5, pages_used=pages_used,
                     page_size=4, arrays=arrays).to_bytes()
    h = KVHandoff.from_bytes(blob)
    dst.pool.assign(1, dst.pool.alloc("b", 2))
    dst.import_slot(1, dict(h.arrays), h.pages_used)
    # recurrent rows and every written position crossed intact
    assert dst.state[1] == 3 + 5 + 7 + 9 + 2
    assert dst.conv[1] == 2
    got = [dst.kv[dst.pool.block_tables[1][p // 4], p % 4] for p in range(5)]
    assert got == [3, 5, 7, 9, 2]


# ----------------------------------------------------------- fleet paths --
def test_fleet_token_identical_to_single_host():
    clock = FakeClock()
    fleet = make_fleet(clock, prefill=1, decode=2)
    fleet_reqs = seeded_requests(8)
    for r in fleet_reqs:
        assert fleet.submit(r)
    drive(fleet, clock)

    # same seeded set through one single-host chunked scheduler
    sclock = FakeClock()
    sched = Scheduler(FakeFleetEngine(slots=2, max_len=40, chunk=4),
                      queue_depth=64, clock=sclock)
    solo_reqs = seeded_requests(8)
    for r in solo_reqs:
        assert sched.submit(r)
    for _ in range(500):
        if sched.idle:
            break
        sched.tick()
        sclock.t += 1.0
    assert sched.idle

    for f, s in zip(fleet_reqs, solo_reqs):
        assert f.tokens == s.tokens == expected_tokens(f)
    assert fleet.completed == 8
    assert fleet.handoffs == fleet.adoptions == 8
    assert all(r.state == DONE for r in fleet_reqs)


def test_fleet_ttft_and_steps_accounting():
    clock = FakeClock()
    fleet = make_fleet(clock, prefill=1, decode=1)
    req = Request(rid=0, prompt=np.arange(1, 8, dtype=np.int32), max_new=4)
    assert fleet.submit(req)
    drive(fleet, clock)
    # chunked invariants survive the migration: ceil(7/4) prefill steps,
    # max_new - 1 decode steps (first token falls out of prefill)
    assert req.prefill_steps == 2
    assert req.decode_steps == 3
    assert req.ttft is not None and req.ttft >= 0


def test_fleet_rejects_unservable_and_queue_full():
    clock = FakeClock()
    fleet = make_fleet(clock, prefill=1, decode=1, max_len=16)
    fleet.queue_depth = 2
    too_long = Request(rid=99, prompt=np.zeros(64, np.int32), max_new=4)
    assert not fleet.submit(too_long)
    ok = [Request(rid=i, prompt=np.arange(1, 5, dtype=np.int32), max_new=2)
          for i in range(3)]
    assert fleet.submit(ok[0]) and fleet.submit(ok[1])
    assert not fleet.submit(ok[2])          # queue full
    assert fleet.rejected == {"too-long": 1, "queue-full": 1}


# ------------------------------------------------------------- fault paths --
def storm_controller(*, rescale=True, heartbeat_timeout=2.5,
                     provision_delay=2.0, max_decode=4):
    return ElasticController(
        Supervisor(0, SupervisorConfig(heartbeat_timeout=heartbeat_timeout)),
        detector=StragglerDetector(0, StragglerConfig(evict_after=10 ** 6)),
        min_decode=1, max_decode=max_decode,
        rescale=rescale, provision_delay=provision_delay)


def test_replica_kill_mid_decode_recovers_token_identical():
    clock = FakeClock()
    fleet = make_fleet(clock, prefill=1, decode=2,
                       controller=storm_controller(rescale=False))
    reqs = seeded_requests(8, max_new=8)
    for r in reqs:
        assert fleet.submit(r)

    state = {"killed": None}

    def maybe_kill(_t):
        if state["killed"] is None:
            busy = next((rep for rep in fleet.decode_pool
                         if any(r.state == DECODING and r.tokens
                                for r in rep.active_requests())), None)
            if busy is not None:
                state["killed"] = busy
                busy.kill()

    drive(fleet, clock, per_tick=maybe_kill)
    assert state["killed"] is not None
    assert fleet.recovered > 0              # requests really were in flight
    assert len(fleet.decode_pool) == 1      # static fleet: not replaced
    for r in reqs:
        assert r.tokens == expected_tokens(r), (r.rid, r.tokens)
    assert any("dead; recovering" in e for e in fleet.events)
    assert any("requeue rid=" in e for e in fleet.events)


def test_kill_and_rescale_storm_replaces_capacity():
    clock = FakeClock()
    ctl = storm_controller(rescale=True, max_decode=2)
    fleet = make_fleet(clock, prefill=1, decode=2, controller=ctl)
    reqs = seeded_requests(10, max_new=8)
    for r in reqs:
        assert fleet.submit(r)

    state = {"killed": None}

    def maybe_kill(t):
        if state["killed"] is None and t >= 4.0:
            busy = max(fleet.decode_pool, key=lambda rep: len(rep.active_requests()))
            state["killed"] = busy
            busy.kill()

    drive(fleet, clock, per_tick=maybe_kill)
    assert ctl.provisioned >= 1             # pool grew back
    assert any(e for e in fleet.events if "rescale: decode pool" in e)
    alive_decode = [r for r in fleet.decode_pool if r.alive]
    assert len(alive_decode) >= 1
    for r in reqs:
        assert r.tokens == expected_tokens(r), (r.rid, r.tokens)


def test_straggler_evicted_via_graceful_drain():
    clock = FakeClock()
    ctl = ElasticController(
        Supervisor(0, SupervisorConfig(heartbeat_timeout=100.0)),
        detector=StragglerDetector(0, StragglerConfig(
            threshold=2.0, patience=2, evict_after=4)),
        min_decode=1, max_decode=3, rescale=False)
    fleet = make_fleet(clock, prefill=1, decode=3, controller=ctl)
    slow = fleet.decode_pool[1]
    slow.set_latency(0.2)                   # ~20x the healthy 0.01
    reqs = [Request(rid=i, prompt=np.arange(1, 6, dtype=np.int32), max_new=8)
            for i in range(8)]
    for r in reqs:
        assert fleet.submit(r)
    drive(fleet, clock)
    assert slow.state == DRAINED
    assert fleet.recovered == 0             # graceful: no recomputation
    assert any("drain" in e and "straggler" in e for e in fleet.events)
    for r in reqs:
        assert r.tokens == expected_tokens(r)
    # drained slots re-entered the pool as handoffs and were re-adopted
    assert fleet.adoptions > fleet.completed - fleet.recovered - 1


def test_out_of_pages_handoff_waits_then_drains():
    clock = FakeClock()

    def factory(role, host_id):
        # decode pool sized for one request at a time (1 park + 4 pages)
        pages = None if role == "prefill" else 5
        rep = FakeReplica(host_id, role, slots=2, max_len=16, chunk=4,
                          num_pages=pages, clock=clock)
        rep.set_latency(0.01)
        return rep

    fleet = FleetScheduler(factory, prefill=1, decode=1, clock=clock)
    reqs = [Request(rid=i, prompt=np.arange(2, 9, dtype=np.int32), max_new=4)
            for i in range(3)]
    for r in reqs:
        assert fleet.submit(r)
    drive(fleet, clock)
    assert any("waiting for decode capacity" in e for e in fleet.events)
    for r in reqs:
        assert r.tokens == expected_tokens(r)
    assert fleet.stats()["pending-handoffs"] == 0


def test_provisioned_replica_joins_after_delay():
    clock = FakeClock()
    ctl = storm_controller(rescale=True, provision_delay=3.0, max_decode=2)
    fleet = make_fleet(clock, prefill=1, decode=1, controller=ctl)
    for r in seeded_requests(6, max_new=6):
        assert fleet.submit(r)
    fleet.tick()                            # demand forces a grow plan
    joiner = fleet.decode_pool[-1]
    assert joiner.state == JOINING
    assert joiner.tick() == []              # joining replicas take no work
    clock.t = 4.0
    fleet.tick()
    assert joiner.state == ACTIVE
    drive(fleet, clock)


def test_warm_start_event_logged_on_provision():
    clock = FakeClock()

    def factory(role, host_id):
        rep = FakeReplica(host_id, role, slots=2, max_len=40, chunk=4,
                          clock=clock)
        rep.set_latency(0.01)
        rep.warm_start = {"bundle-imported": 3, "searched": 0}
        return rep

    ctl = storm_controller(rescale=True, max_decode=3, provision_delay=0.0)
    fleet = FleetScheduler(factory, prefill=1, decode=1, clock=clock,
                           controller=ctl)
    for r in seeded_requests(8, max_new=6):
        assert fleet.submit(r)
    drive(fleet, clock)
    warm = [e for e in fleet.events if "warm-start" in e]
    assert warm and all("bundle-imported=3" in e for e in warm)


def test_replica_role_validation():
    clock = FakeClock()
    with pytest.raises(ValueError):
        FakeReplica(0, "training", clock=clock)
    decode = FakeReplica(1, "decode", clock=clock)
    with pytest.raises(ValueError):
        decode.set_handoff_hook(lambda req: None)


# ---------------------------------------------------------------------------
# end-to-end: real JaxEngines on the pod-sim deployment
# ---------------------------------------------------------------------------

from repro.configs import get_config                     # noqa: E402
from repro.core import Runtime                           # noqa: E402
from repro.launch.mesh import make_host_mesh             # noqa: E402
from repro.launch.serve import JaxEngine, Server         # noqa: E402
from repro.launch.train import make_bundle               # noqa: E402
from repro.serving import Replica                        # noqa: E402


@pytest.fixture(scope="module", params=["qwen2.5-14b", "mamba2-780m"])
def fleet_container(request):
    """One attention arch and one SSM arch: the handoff artifact must
    carry paged KV pages for the former and state/conv recurrent rows
    for the latter."""
    rt = Runtime(host_env={})
    container = rt.deploy(make_bundle(request.param, reduced=True),
                          mesh=make_host_mesh(data=1))
    yield get_config(request.param).reduced(), container
    rt.cleanup()


def test_e2e_fleet_token_identical_to_single_host(fleet_container):
    """Real engines, real handoffs: a 1-prefill + 1-decode fleet emits
    exactly the tokens of one single-host paged chunked server over the
    same seeded request set, and the decode pool drains clean."""
    cfg, container = fleet_container
    clock = FakeClock()

    def factory(role, host_id):
        eng = JaxEngine(cfg, container, slots=2, max_len=32, chunk=4,
                        prefill_mode="chunked", paged=True)
        return Replica(host_id, role, eng, clock=clock)

    fleet = FleetScheduler(factory, prefill=1, decode=1, clock=clock)
    rng = np.random.default_rng(11)
    lens = [4, 6, 9, 3]
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in lens]
    reqs = [Request(rid=i, prompt=p.copy(), max_new=3)
            for i, p in enumerate(prompts)]
    for r in reqs:
        assert fleet.submit(r)
    drive(fleet, clock)
    assert fleet.handoffs == fleet.adoptions == len(lens)

    server = Server(cfg, container, slots=2, max_len=32, chunk=4,
                    prefill_mode="chunked", paged=True)
    for i, p in enumerate(prompts):
        assert server.submit(Request(rid=i, prompt=p.copy(), max_new=3))
    server.run()
    solo = {r.rid: list(r.tokens) for r in server.requests}
    for r in reqs:
        assert r.tokens == solo[r.rid], (r.rid, r.tokens, solo[r.rid])
    for rep in fleet.replicas():
        pool = rep.engine.pool
        assert pool.allocator.available == pool.allocator.capacity
