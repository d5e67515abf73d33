"""The serving loop's spans and the process's compile and collection log
(`repro.launch.tracing`).

A tiny paged `Server` on the CPU is ticked under the profiler and its
host spans are read back from the trace: the span tree is what
docs/serving.md ("Tracing a server") promises.  The log counts one
compile for a fresh jit and none for the cached call, and one entry per
full collection.
"""

import gc
import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import Runtime
from repro.launch.mesh import make_host_mesh
from repro.launch.serve import Request, Server
from repro.launch.tracing import host_log
from repro.launch.train import make_bundle

ARCH = "qwen2.5-14b"


def _spans(tmp_path, fn):
    """Run `fn` under the profiler; the ``repro.`` host spans it wrote,
    as (start_ns, end_ns, name)."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path[0])
    return sorted((e.start_ns, e.end_ns, e.name) for plane in data.planes
                  if plane.name == "/host:CPU" for line in plane.lines
                  for e in line.events if e.name.startswith("repro."))


def _parent(spans, child):
    """The innermost span that holds `child`."""
    s, e, _ = child
    holders = [p for p in spans if p is not child and p[0] <= s and e <= p[1]]
    return max(holders, key=lambda p: p[0])[2] if holders else None


@pytest.fixture(scope="module")
def served_spans(tmp_path_factory):
    """Spans of a paged server that admits, prefills over two chunks,
    samples and decodes."""
    rt = Runtime(host_env={})
    container = rt.deploy(make_bundle(ARCH, reduced=True), mesh=make_host_mesh(data=1))
    server = Server(get_config(ARCH).reduced(), container, slots=2, max_len=32,
                    chunk=4, paged=True, clock=time.perf_counter)
    rng = np.random.default_rng(3)
    for rid, plen in enumerate([6, 3]):
        prompt = rng.integers(0, 100, size=plen).astype(np.int32)
        assert server.submit(Request(rid=rid, prompt=prompt, max_new=3))
    server.step()                      # compiles both steps outside the trace
    try:
        yield _spans(tmp_path_factory.mktemp("trace"), server.run)
    finally:
        rt.cleanup()


@pytest.mark.parametrize("child, parent", [
    ("repro.admit", "repro.tick"),
    ("repro.prefill", "repro.tick"),
    ("repro.decode", "repro.tick"),
    ("repro.pages", "repro.tick"),
    ("repro.decode.upload", "repro.decode"),
    ("repro.decode.dispatch", "repro.decode"),
    ("repro.decode.wait", "repro.decode"),
    ("repro.decode.pull", "repro.decode"),
    ("repro.prefill.upload", "repro.prefill"),
    ("repro.prefill.dispatch", "repro.prefill"),
    ("repro.prefill.wait", "repro.prefill"),
    ("repro.prefill.pull", "repro.prefill"),
])
def test_span_tree(served_spans, child, parent):
    found = [s for s in served_spans if s[2] == child]
    assert found, f"no {child} span"
    assert {_parent(served_spans, s) for s in found} == {parent}


def test_sample_spans_follow_the_logits(served_spans):
    """The first token is sampled inside its prefill unit, the decode
    rows after the decode step, both inside the tick."""
    parents = {_parent(served_spans, s) for s in served_spans if s[2] == "repro.sample"}
    assert parents == {"repro.prefill", "repro.tick"}
    ticks = [s for s in served_spans if s[2] == "repro.tick"]
    assert all(_parent(served_spans, t) is None for t in ticks)


def test_engine_phases_run_in_order(served_spans):
    """upload, dispatch, wait, pull: one after another, within a step."""
    for step in ("prefill", "decode"):
        names = [f"repro.{step}.{p}" for p in ("upload", "dispatch", "wait", "pull")]
        for outer in (s for s in served_spans if s[2] == f"repro.{step}"):
            inner = [s for s in served_spans if s[2] in names
                     and outer[0] <= s[0] and s[1] <= outer[1]]
            assert [s[2] for s in inner] == names
            assert all(a[1] <= b[0] for a, b in zip(inner, inner[1:]))


def test_compile_log_counts_a_fresh_jit_once():
    log = host_log()
    x = jnp.arange(8.0)

    def tripled_plus_one(v):
        return v * 3 + 1

    f = jax.jit(tripled_plus_one)
    t = time.perf_counter()
    f(x).block_until_ready()
    fresh = [c for c in log.compiles if c[0] >= t]
    assert len(fresh) == 1 and "tripled_plus_one" in fresh[0][2] and fresh[0][1] > 0
    t = time.perf_counter()
    f(x).block_until_ready()
    assert not [c for c in log.compiles if c[0] >= t]


def test_collection_log_keeps_full_collections_only(tmp_path):
    log = host_log()
    t = time.perf_counter()
    gc.collect(0)
    gc.collect(1)
    assert not [c for c in log.collections if c[1] >= t]
    spans = _spans(tmp_path, gc.collect)
    full = [c for c in log.collections if c[1] >= t]
    assert full and all(t <= start <= end for start, end in full)
    # the profiler's own work may set off another full collection
    assert spans and {s[2] for s in spans} == {"repro.gc"}
