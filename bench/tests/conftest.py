"""Benchmark tests run on the CPU at tiny sizes, with their own JAX
compilation cache so they never touch the checkout's."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# a few thousand documents: the same code paths as the deployments, sized
# for a test run (the reference check and the traffic stay as they are)
TINY = {"n_docs": 3000, "assumed": {"vocab_ranks": 3000}}
# mixes that only the tests' cells use
TEST_MIXES = os.path.join(ROOT, "bench", "tests", "traffic")


@pytest.fixture(autouse=True)
def _private_compile_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(tmp_path / "jax_cache"))


# one cell of each traffic kind, whatever BENCHMARK.json lists today
TEST_CELLS = [
    {"name": "touche-offline", "config": "beir-touche2020",
     "traffic": "offline-b64-k100", "chips": 1, "why": "test"},
    {"name": "quora-online", "config": "beir-quora",
     "traffic": "open-poisson-k10-test", "chips": 1, "why": "test"},
]


# the metrics a cell of each kind reports: end to end, then per layer
_KIND = {"batch": (("qps", "queries/s"),),
         "open_loop": (("p50_ms", "ms"), ("p95_ms", "ms"))}
_LAYER = {"batch": ".offline", "open_loop": ".online"}


@pytest.fixture
def test_root(tmp_path):
    """A checkout whose BENCHMARK.json lists ``TEST_CELLS`` beside its own
    cells (the benchmark's files are the repository's, linked, and its
    traffic directory holds the tests' mixes too)."""
    bench = tmp_path / "bench"
    bench.mkdir()
    for entry in os.listdir(os.path.join(ROOT, "bench")):
        if entry != "traffic":
            os.symlink(os.path.join(ROOT, "bench", entry), bench / entry)
    (bench / "traffic").mkdir()
    for d in (os.path.join(ROOT, "bench", "traffic"), TEST_MIXES):
        for f in os.listdir(d):
            os.symlink(os.path.join(d, f), bench / "traffic" / f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    listed = {w["name"] for w in spec["workloads"]}
    configs = {c["name"] for c in spec["configs"]}
    for cell in TEST_CELLS:
        if cell["name"] in listed:
            continue
        spec["workloads"].append(cell)
        if cell["config"] not in configs:
            configs.add(cell["config"])
            spec["configs"].append(
                {"name": cell["config"],
                 "file": f"bench/configs/{cell['config']}.json"})
        with open(bench / "traffic" / (cell["traffic"] + ".json")) as f:
            kind = json.load(f)["kind"]
        e2e = {m["name"]: m for m in spec["end_to_end"]}
        for name, unit in _KIND[kind]:
            e2e.setdefault(name, {"name": name, "unit": unit,
                                  "workloads": []})
            e2e[name]["workloads"].append(cell["name"])
        spec["end_to_end"] = list(e2e.values())
        layer = {m["name"]: m for m in spec["per_layer"]}
        for f in os.listdir(os.path.join(ROOT, "bench", "metrics")):
            name = f[:-3]
            if f.endswith(".py") and name.endswith(_LAYER[kind]):
                layer.setdefault(name, {"name": name, "unit": "1",
                                        "moves": _KIND[kind][-1][0],
                                        "workloads": []})
                layer[name]["workloads"].append(cell["name"])
        spec["per_layer"] = list(layer.values())
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return str(tmp_path)
