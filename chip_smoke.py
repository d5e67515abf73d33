"""Serve the main path once on a TPU chip and check what comes out.

    python chip_smoke.py              # one chip: phases A and B
    python chip_smoke.py --chips 4    # four chips: the fleet against one chip

Every phase goes through the entry points a user calls:
``make_bundle`` -> ``Runtime.deploy(native_ops=True)`` ->
``Server(paged=True)`` -> paged KV cache -> the bound Pallas kernels.
The deploy is hermetic (``host_env={}``, autotune and profiling off), and
weights and requests are made from fixed seeds.

* Phase A: granite-3-8b at its published widths in bf16, cut to the
  first 20 of its 40 layers (the whole model does not fit one chip).
* Phase B: mamba2-780m whole, all 48 layers.

Each phase serves 8 requests of a few hundred prompt tokens and 32 new
tokens, requires every op to be bound to the native ``pallas-tpu``
kernels, and compares the last prefill chunk's logits with a
reference-bound deploy of the same weights (relative L2 <= 5e-2).

``--chips 4`` runs only the disaggregated fleet (one prefill and three
decode replicas, each deployed on its own chip) and the one-chip server
it must match token for token.

The last line of standard output is one JSON object naming the device;
it is printed only when every phase passed.  Without a TPU, or outside a
checkout of the repository, the script exits non-zero.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

SEED = 0
REQUESTS = 8
PROMPT_LEN = (200, 400)        # prompt lengths, drawn uniformly
MAX_NEW = 32
SLOTS, MAX_LEN, CHUNK = 8, 2048, 128   # paged: one page per prefill chunk
# Both deploys run bf16 and share weights and XLA code; they differ in
# the kernels only.  Each kernel is 2-4e-3 from an fp32 oracle on a v5e,
# and depth amplifies that: measured on a v5e, granite (20 layers)
# 1.7e-2 and mamba2 (48 layers) 3.9e-2, where the bf16 reference itself
# is 4.1e-2 from an fp32 forward.  A wrong mask, page or scale is O(1).
LOGIT_TOL = 5e-2
NATIVE_PROVIDER = "pallas-tpu"

# arch -> (layers served, why)
PHASES = {
    "granite-3-8b": (
        20,
        "40 layers are 8.17e9 parameters, 16.3 GB in bf16, above one v5e "
        "chip's 16 GB of HBM; 20 layers hold 4.18e9 (8.4 GB) and leave room "
        "for the 1.35 GB paged KV pool twice (it is not donated)"),
    "mamba2-780m": (48, "whole: 48 layers, 1.6 GB in bf16"),
}


class SmokeFailure(RuntimeError):
    pass


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _import_repo() -> None:
    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro" / "__init__.py").is_file():
        raise SmokeFailure(f"no repository next to chip_smoke.py ({src} missing)")
    sys.path.insert(0, str(src))


def _requests(vocab: int):
    import numpy as np

    from repro.launch.serve import Request

    rng = np.random.default_rng(SEED)
    lens = rng.integers(PROMPT_LEN[0], PROMPT_LEN[1] + 1, size=REQUESTS)
    return [Request(rid=i, prompt=rng.integers(0, vocab, size=n).astype(np.int32),
                    max_new=MAX_NEW) for i, n in enumerate(lens)]


def _deploy(bundle, device, *, native: bool):
    from repro.core import Runtime

    rt = Runtime(host_env={})
    container = rt.deploy(bundle, devices=[device], native_ops=native,
                          autotune=False, profile=False)
    if native:
        bad = [f"{r.op}={r.bound}" for r in container.binding.reports
               if r.bound != NATIVE_PROVIDER or not r.swapped]
        _check(not bad, f"ops not bound to {NATIVE_PROVIDER}: {bad}")
    return rt, container


def _serve(cfg, container, requests):
    """A Server over `requests`; returns (server, compile s, serve s)."""
    import numpy as np

    from repro.launch.serve import Server

    server = Server(cfg, container, slots=SLOTS, max_len=MAX_LEN, chunk=CHUNK,
                    paged=True)
    eng = server.engine
    # the first call of each compiled step, on the park page with every
    # row inactive: no slot state changes, and the serve timing below
    # is then free of compilation
    t0 = time.perf_counter()
    eng.prefill_step(0, np.zeros(1, np.int32), 0)
    eng.decode_step(np.zeros((SLOTS, 1), np.int32),
                    np.full(SLOTS, MAX_LEN - 1, np.int32), np.zeros(SLOTS, bool))
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for r in requests:
        _check(server.submit(r), f"request {r.rid} rejected at admission")
    server.run()
    serve_s = time.perf_counter() - t0
    for r in requests:
        _check(r.done and len(r.tokens) == MAX_NEW,
               f"request {r.rid} ended {r.state} with {len(r.tokens)} tokens")
    return server, compile_s, serve_s


def _on_device(tree, device) -> bool:
    import jax

    return all(leaf.devices() == {device} for leaf in jax.tree.leaves(tree))


def _logit_error(cfg, bundle, engine, prompt, device) -> float:
    """Relative L2 of the last prefill chunk's logits: the engine's native
    step against a reference-bound deploy sharing the engine's weights."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.launch.steps import DeployOptions, make_deployment
    from repro.configs.base import ShapeConfig

    n = len(prompt)
    starts = range(0, n, CHUNK)
    pool = engine.pool
    pages = pool.alloc("logit-check", len(starts))
    pool.assign(0, pages)
    for pos in starts:
        native = engine.prefill_step(0, prompt[pos:pos + CHUNK], pos)
    pool.free("logit-check")
    pool.release(0)

    rt, container = _deploy(bundle, device, native=False)
    model = make_deployment(
        cfg, ShapeConfig("serve", MAX_LEN, 1, "decode"), container.mesh,
        options=DeployOptions(donate=False), binding=container.binding).model
    nblocks = len(starts)
    cache = jax.jit(lambda: model.init_paged_cache(1 + nblocks, CHUNK, 1),
                    out_shardings=NamedSharding(container.mesh, PartitionSpec()))()
    row = jax.numpy.arange(1, 1 + nblocks, dtype=jax.numpy.int32)
    step = jax.jit(model.prefill_into)
    for pos in starts:
        chunk = prompt[pos:pos + CHUNK]
        buf = np.zeros((1, CHUNK), np.int32)
        buf[0, :len(chunk)] = chunk
        logits, cache = step(engine.params, buf, cache, 0, pos, len(chunk),
                             block_row=row)
    ref = np.asarray(logits[0], np.float64)[:cfg.vocab_size]
    got = np.asarray(native, np.float64)[:cfg.vocab_size]
    rt.cleanup()
    _check(bool(np.all(np.isfinite(got))), "native logits are not finite")
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


def _peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def phase(arch: str, device) -> None:
    from repro.launch.train import make_bundle, serving_config

    layers, why = PHASES[arch]
    cfg = serving_config(arch, num_layers=layers)
    bundle = make_bundle(arch, num_layers=layers)
    print(f"[{arch}] d_model={cfg.d_model} heads={cfg.num_heads}/"
          f"{cfg.num_kv_heads}x{cfg.head_dim} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size} dtype={cfg.dtype}", flush=True)
    print(f"[{arch}] cut: {layers} layers served — {why}", flush=True)
    rt, container = _deploy(bundle, device, native=True)
    providers = sorted({f"{r.op}:{r.bound}" for r in container.binding.reports})
    print(f"[{arch}] bound: {' '.join(providers)}", flush=True)
    requests = _requests(cfg.vocab_size)
    server, compile_s, serve_s = _serve(cfg, container, requests)
    tokens = sum(len(r.tokens) for r in requests)
    print(f"[{arch}] compile+first steps {compile_s:.1f}s; served "
          f"{len(requests)} requests, {sum(r.prompt_len for r in requests)} "
          f"prompt + {tokens} generated tokens in {serve_s:.1f}s", flush=True)
    err = _logit_error(cfg, bundle, server.engine, requests[0].prompt, device)
    print(f"[{arch}] last-chunk logits, native vs reference: rel L2 "
          f"{err:.3e} (limit {LOGIT_TOL:.0e})", flush=True)
    _check(err <= LOGIT_TOL, f"{arch}: logit error {err:.3e} > {LOGIT_TOL}")
    print(f"[{arch}] peak_bytes_in_use {_peak_bytes(device)} (process so far)",
          flush=True)
    del server
    rt.cleanup()
    gc.collect()


def fleet_phase(devices) -> None:
    """One prefill and three decode replicas, each on its own chip,
    against the one-chip server over the same requests."""
    from repro.launch.serve import JaxEngine, Request
    from repro.launch.train import make_bundle, serving_config
    from repro.serving import FleetScheduler, Replica

    arch = "granite-3-8b"
    layers, why = PHASES[arch]
    cfg = serving_config(arch, num_layers=layers)
    bundle = make_bundle(arch, num_layers=layers)
    print(f"[fleet] {arch} cut to {layers} layers — {why}", flush=True)

    rt, container = _deploy(bundle, devices[0], native=True)
    solo_reqs = _requests(cfg.vocab_size)
    server, _, solo_s = _serve(cfg, container, solo_reqs)
    solo = {r.rid: list(r.tokens) for r in solo_reqs}
    print(f"[fleet] one-chip server: {len(solo)} requests in {solo_s:.1f}s",
          flush=True)
    del server
    rt.cleanup()
    gc.collect()

    runtimes, engines = [], {}

    def factory(role: str, host_id: int) -> Replica:
        device = devices[host_id]
        rt, container = _deploy(bundle, device, native=True)
        runtimes.append(rt)
        engine = JaxEngine(cfg, container, slots=SLOTS, max_len=MAX_LEN,
                           chunk=CHUNK, paged=True)
        engines[host_id] = (engine, device)
        return Replica(host_id, role, engine)

    fleet = FleetScheduler(factory, prefill=1, decode=len(devices) - 1)
    reqs = [Request(rid=r.rid, prompt=r.prompt.copy(), max_new=MAX_NEW)
            for r in _requests(cfg.vocab_size)]
    t0 = time.perf_counter()
    for r in reqs:
        _check(fleet.submit(r), f"fleet rejected request {r.rid}")
    fleet.run(max_ticks=10_000)
    fleet_s = time.perf_counter() - t0
    for host_id, (engine, device) in sorted(engines.items()):
        _check(_on_device(engine.params, device)
               and _on_device(engine.cache, device),
               f"replica {host_id}: state is not all on {device}")
    print("[fleet] replica placement: " + " ".join(
        f"{rep.name}@{engines[rep.id][1].id}" for rep in fleet.replicas()),
        flush=True)
    _check(fleet.handoffs == len(reqs), f"{fleet.handoffs} handoffs")
    for r in reqs:
        _check(r.tokens == solo[r.rid],
               f"request {r.rid}: fleet {r.tokens} != one chip {solo[r.rid]}")
    adopted = {rep.name: rep.scheduler.adopted for rep in fleet.decode_pool}
    print(f"[fleet] {len(reqs)} requests in {fleet_s:.1f}s, {fleet.handoffs} "
          f"KV handoffs ({fleet.handoff_bytes} bytes), adopted {adopted}; "
          f"tokens identical to the one-chip server", flush=True)
    for rt in runtimes:
        rt.cleanup()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip fleet phase")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU is present (JAX found {dev.platform!r} "
              "devices); this script runs only on a TPU", file=sys.stderr)
        return 2
    try:
        _import_repo()
        from repro.launch.compile_cache import enable_compile_cache

        print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
              f"compile cache {enable_compile_cache()}", flush=True)
        if args.chips == 4:
            _check(len(devices) >= 4, f"--chips 4 needs 4 chips, found {len(devices)}")
            fleet_phase(devices[:4])
        else:
            for arch in PHASES:
                phase(arch, dev)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
