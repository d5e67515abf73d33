"""The harness finds every cell's files by name, and BENCHMARK.json
keeps to its schema."""

import bench_helpers  # noqa: F401  (the repository root on the path)

import importlib.util
import io
import json
import re
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness, run

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_each_cell_is_found_by_name(workload):
    cell = harness.load_cell(workload)
    conf = next(c for c in BENCH["configs"] if c["name"] ==
                next(w for w in BENCH["workloads"] if w["name"] == workload)["config"])
    assert cell.spec["name"] == conf["name"] and cell.spec["source"] == conf["source"]
    # the program config the harness builds runs what the file states
    from repro.launch.train import serving_config

    cell.family.check_program_config(cell.spec, serving_config(
        cell.spec["arch"], num_layers=cell.dims["layers"]))
    d = cell.deploy
    # a request at the mix's longest fits one slot's pages
    assert cell.mix["prompt"]["max"] + cell.mix["output"]["max"] <= d["max_len"]
    assert d["chunk"] == 128 and d["check_requests"] >= 1 and d["check_limit"] > 0


def test_unknown_workload_raises():
    with pytest.raises(harness.BenchError):
        harness.load_cell("no-such-cell")


STUB_FAMILY = '''
import numpy as np

def dims(spec):
    return {"vocab": spec["vocab"], "layers": 0}

def reference_logits(spec, weights, seqs, starts, bucket, rows, control=None):
    """The next token is the last one plus one."""
    eye = np.eye(spec["vocab"])
    return [eye[(np.asarray(seq[start:]) + 1) % spec["vocab"]] for seq, start in zip(seqs, starts)]
'''

STUB_GENERATOR = '''
import numpy as np
from bench.gen import RequestSpec, rng_for

def requests(mix, seed, vocab, seconds):
    rng = rng_for(seed, 0)
    return [RequestSpec(i, rng.integers(0, vocab, size=5).astype(np.int32), mix["answer"], 0.0)
            for i in range(mix["count"])]
'''

STUB_DRIVER = '''
"""Serves every request at once: each token the last one plus `step`."""
import time
import types

import jax
import numpy as np
from bench import harness

class Driver:
    def __init__(self, cell, *, provider=None, platform=None, t_proc=None,
                 program_config=None):
        self.cell, self.devices, self.weights = cell, jax.devices()[:1], {}

    def set_weights(self, seed):
        pass

    def warm(self):
        pass

    def window(self, seed, seconds, *, trace=False, hook=None):
        c = self.cell
        t0 = time.perf_counter()
        rec = harness.WindowRecord(cell=c.name, t0=t0, t_end=t0 + seconds, seconds=seconds,
                                   requests={}, prefill=[], decode=[], ticks=[],
                                   page_samples=[], slots=1, chips=c.chips)
        vocab = c.dims["vocab"]
        for s in c.generator.requests(c.mix, seed, vocab, seconds):
            toks, last = [], int(s.prompt[-1])
            for _ in range(s.max_new):
                last = (last + c.deploy["step"]) % vocab
                toks.append(last)
            req = types.SimpleNamespace(prompt=s.prompt, prompt_len=len(s.prompt),
                                        tokens=toks, done=True)
            rec.requests[s.index] = harness.ReqRecord(spec=s, due=t0, req=req, accepted=True,
                                                      times=[t0] * len(toks))
        return rec

    def memory_peak(self):
        return None

    def free_program_state(self):
        pass

    def close(self):
        pass
'''


@pytest.mark.parametrize("step,correct", [(1, True), (2, False)], ids=["sound", "altered"])
def test_a_cell_from_added_files_alone_runs(tmp_path, step, correct):
    """A cell whose configuration, family, mix, generator, deployment and
    driver are all new files is found by name and runs to a result, with
    no edit to an existing file; its check still decides `correct`."""
    files = {
        "BENCHMARK.json": json.dumps({
            "configs": [{"name": "stub", "file": "bench/configs/stub.json"}],
            "workloads": [{"name": "stub.echo", "config": "stub", "traffic": "echo",
                           "chips": 1}]}),
        "bench/configs/stub.json": json.dumps({"name": "stub", "family": "stubfam",
                                               "vocab": 11}),
        "bench/models/stubfam.py": STUB_FAMILY,
        "bench/traffic/echo.json": json.dumps({
            "generator": "stubgen", "loop": "open", "arrivals": {"rate": 1.0},
            "count": 4, "answer": 3, "output": {"max": 3}}),
        "bench/traffic/stubgen.py": STUB_GENERATOR,
        "bench/cells/stub.echo.json": json.dumps({
            "driver": "stubdrv", "max_len": 8, "check_requests": 2, "check_limit": 0.1,
            "step": step}),
        "bench/drivers/stubdrv.py": STUB_DRIVER,
    }
    for rel, text in files.items():
        (tmp_path / rel).parent.mkdir(parents=True, exist_ok=True)
        (tmp_path / rel).write_text(text)
    cell = harness.load_cell("stub.echo", root=tmp_path)
    assert Path(cell.driver.__file__) == tmp_path / "bench/drivers/stubdrv.py"
    assert Path(cell.generator.__file__) == tmp_path / "bench/traffic/stubgen.py"
    res = run.execute(cell, 5, 0.5, False, log=io.StringIO())
    assert res["correct"] is correct
    assert res["attempted"] == 4 and res["failed"] == 0 and res["metrics"] == {}


def test_a_missing_plugin_raises(tmp_path):
    with pytest.raises(harness.BenchError):
        harness.plugin("drivers", "no-such-driver", tmp_path)


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
def test_each_metric_has_a_reader(metric):
    path = ROOT / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location("m", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert callable(mod.read)


def test_metric_selection_by_workload():
    lp = "granite-3-8b.long-prompt"
    spec = {"end_to_end": [{"name": "a"}, {"name": "b", "workloads": ["other"]}],
            "per_layer": [{"name": "c", "workloads": [lp]}, {"name": "d", "workloads": ["x"]}]}
    assert run.metric_names(spec, lp, False) == ["a"]
    assert run.metric_names(spec, lp, True) == ["c"]
    assert "attn_prefill_roofline" in run.metric_names(BENCH, lp, True)
    assert run.metric_names(BENCH, lp, False) == [m["name"] for m in BENCH["end_to_end"]]


def test_schema():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir()
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in BENCH[k]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (ROOT / c["file"]).is_file()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["moves"] in e2e
        assert set(m.get("workloads", [])) <= {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_no_chip_exits_nonzero_without_a_result():
    """Here JAX sees only the CPU: the command refuses, prints nothing."""
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "granite-3-8b.long-prompt",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr
