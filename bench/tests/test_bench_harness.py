"""The harness end to end on the CPU: every cell of BENCHMARK.json runs
from its files at a tiny size and prints a well-formed, correct line."""

import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from bench.corpus import Shape, make_corpus, make_queries
from bench.harness import load_cell, run_cell
from bench.sigma_df import document_frequency, spread
from bench.tests.conftest import ROOT, TEST_CELLS, TINY
from bench.traffic import arrival_times

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)
# every cell of BENCHMARK.json, and one of each traffic kind
WORKLOADS = sorted({w["name"] for w in SPEC["workloads"]}
                   | {c["name"] for c in TEST_CELLS})


def _run(root, workload, trace, seed=2_147_483_661):
    return run_cell(root, workload, seed, 1.0, trace,
                    t_start=time.perf_counter(), require_tpu=False,
                    shape_override=TINY)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_runs_from_files(workload, trace, test_root):
    cell = load_cell(test_root, workload)
    r = _run(test_root, workload, bool(trace))
    assert list(r)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(r)
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    for c in r["checks"].values():
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"]
    dev = r["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    json.dumps(r)                       # one JSON object, as printed
    if trace:
        assert {"busy_s", "window_s"} <= set(dev)
        names = {m["name"] for m in cell.per_layer}
        assert set(r["metrics"]) <= names
        # host-side readers find something even off the chip
        assert r["metrics"], "no per-layer metric read"
    else:
        assert set(r["metrics"]) == {m["name"] for m in cell.end_to_end}
        for m in cell.end_to_end:
            v = r["metrics"][m["name"]]
            assert v["unit"] == m["unit"] and v["value"] > 0


def test_window_that_compiles_is_not_measured(monkeypatch, test_root):
    """A window that compiles counts as warm-up and runs again; where
    every window compiles, the run fails instead of reporting."""
    import bench.harness as harness
    real = harness.Bench.window
    windows = []

    def counted(self, *a, **kw):
        windows.append(real(self, *a, **kw))
        return windows[-1]
    monkeypatch.setattr(harness.Bench, "window", counted)
    monkeypatch.setattr(harness, "_warm", lambda *a, **kw: (0, 0, 0))
    # a size no other test compiles, so nothing is in memory already
    small = {"n_docs": 2500, "assumed": {"vocab_ranks": 2500}}
    r = run_cell(test_root, "touche-offline", 5, 1.0, False,
                 t_start=time.perf_counter(), require_tpu=False,
                 shape_override=small)
    assert windows[0].compiles and not windows[-1].compiles
    assert len(windows) >= 2 and r["correct"]
    assert r["metrics"]["setup_s"]["value"] > windows[0].window_s

    def compiling(self, *a, **kw):
        ctx = real(self, *a, **kw)
        ctx.compiles = {"backend_compile_duration": 1}
        return ctx
    monkeypatch.setattr(harness.Bench, "window", compiling)
    with pytest.raises(harness.WindowCompiled):
        run_cell(test_root, "touche-offline", 6, 1.0, False,
                 t_start=time.perf_counter(), require_tpu=False,
                 shape_override=small)


def test_new_cell_from_added_files_alone(tmp_path):
    """A later cell is files and entries added, not edits: a new traffic
    mix and a new metric reader are found by name."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests",
                                                  "testdata"))
    with open(tmp_path / "bench/traffic/offline-b8-k10.json", "w") as f:
        json.dump({"kind": "batch", "batch": 8, "k": 10,
                   "pool_batches": 4, "sample": 16, "why": "test"}, f)
    with open(tmp_path / "bench/metrics/batches.offline.py", "w") as f:
        f.write("def read(ctx):\n    return len(ctx.batches)\n")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "quora-offline-small",
                              "config": "beir-quora",
                              "traffic": "offline-b8-k10", "chips": 1,
                              "why": "test"})
    if "beir-quora" not in {c["name"] for c in spec["configs"]}:
        spec["configs"].append({"name": "beir-quora",
                                "file": "bench/configs/beir-quora.json"})
    qps = next(m for m in spec["end_to_end"] if m["name"] == "qps")
    qps["workloads"].append("quora-offline-small")
    spec["per_layer"].append({"name": "batches.offline", "unit": "count",
                              "better": "higher",
                              "source": "program_counter",
                              "layer": "retriever and planner",
                              "moves": "qps",
                              "workloads": ["quora-offline-small"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    r = _run(str(tmp_path), "quora-offline-small", True)
    assert r["correct"] and r["metrics"]["batches.offline"]["value"] >= 1
    r = _run(str(tmp_path), "quora-offline-small", False)
    assert set(r["metrics"]) == {"qps", "setup_s"}


def _shape(name="beir-touche2020", **over):
    with open(os.path.join(ROOT, "bench/configs", name + ".json")) as f:
        cfg = json.load(f)
    cfg = {**cfg, "n_docs": 4000,
           "assumed": {**cfg["assumed"], "vocab_ranks": 5000}}
    return Shape.from_config({**cfg, **over})


def test_mix_is_deterministic_per_seed():
    shape = _shape()
    a = make_queries(shape, 2**31 + 5, n_chunks=3, chunk=64)
    b = make_queries(shape, 2**31 + 5, n_chunks=3, chunk=64)
    c = make_queries(shape, 2**31 + 6, n_chunks=3, chunk=64)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not all(np.array_equal(x, y) for x, y in zip(a, c))
    ca, cb = make_corpus(shape, 7), make_corpus(shape, 7)
    assert np.array_equal(ca.tokens, cb.tokens)
    assert np.array_equal(ca.offsets, cb.offsets)
    assert ca.tokens.max() < shape.n_vocab and ca.tokens.min() >= 0
    ta = arrival_times(200.0, 3.0, 11)
    assert np.array_equal(ta, arrival_times(200.0, 3.0, 11))
    assert not np.array_equal(ta, arrival_times(200.0, 3.0, 12))


def test_window_work_barely_moves_with_the_seed():
    """Stratified chunks: every seed's window reads about the same number
    of postings, and far closer than independent draws would."""
    shape = _shape()
    share, cdf = shape.law()
    strat, iid = [], []
    for s in range(8):
        df = document_frequency(make_corpus(shape, 1000 + s))
        qs = make_queries(shape, 1000 + s, n_chunks=4, chunk=64)
        strat.append(np.mean([df[q].sum() for q in qs]))
        rng = np.random.default_rng(s)
        iid.append(np.mean([df[np.minimum(cdf.searchsorted(
            rng.random(q.size)), shape.n_vocab - 1)].sum() for q in qs]))
    assert spread(strat) < 0.02
    assert spread(strat) < spread(iid) / 3


def test_arrivals_count_and_range():
    t = arrival_times(300.0, 4.0, 5)
    assert t.size == 1200 and np.all(np.diff(t) >= 0)
    assert 0.0 <= t[0] and t[-1] < 4.0
    gaps = np.diff(t)
    assert abs(gaps.mean() * 300.0 - 1.0) < 0.02
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.1   # exponential


def test_no_chip_no_result():
    """Without a TPU the command exits non-zero and prints no result."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        SPEC["workloads"][0]["name"], "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=ROOT,
                       env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode == 2 and p.stdout.strip() == ""
    assert "needs 1 TPU chip" in p.stderr
