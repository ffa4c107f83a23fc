"""Benchmark harness — one section per paper table.

    PYTHONPATH=src python -m benchmarks.run [--fast]

Emits CSV-ish lines ``table,key=value,...`` and writes
benchmarks/out/results.json plus BENCH_1.json (fused pipeline + vectorized
indexing — the PR-1 perf trajectory numbers), BENCH_2.json (gathered vs
full-scan retrieval regimes — the PR-2 numbers) and BENCH_3.json (cost-model
planner vs forced regimes + residency transfer audit — the PR-3 numbers) at
the repo root.
"""

from __future__ import annotations

import argparse
import json
import os
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="smaller corpora (CI-sized)")
    ap.add_argument("--force", action="store_true",
                    help="allow a --fast run to overwrite full-scale "
                         "BENCH_* artifacts")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from . import fused, gathered, kernels_bench, planner, throughput, \
        tokenization, variants

    # every BENCH_* write goes through the clobber guard: a --fast run
    # refuses to replace a committed full-scale artifact (the PR-4
    # incident) unless --force
    def _write(path, payload):
        planner._guarded_write(path, payload, fast=args.fast,
                               force=args.force)

    results = {}
    t0 = time.time()

    results["bench1_fused"] = fused.run(fast=args.fast)
    for section, r in results["bench1_fused"].items():
        print(f"bench1_{section}," + ",".join(
            f"{k}={v}" for k, v in r.items()), flush=True)
    _write("BENCH_1.json", results["bench1_fused"])

    results["bench2_gathered"] = gathered.run(fast=args.fast)
    for r in results["bench2_gathered"]["cells"]:
        print("bench2_gathered," + ",".join(
            f"{k}={v}" for k, v in r.items()), flush=True)
    _write("BENCH_2.json", results["bench2_gathered"])

    results["bench3_planner"] = planner.run(fast=args.fast)
    for r in results["bench3_planner"]["cells"]:
        print("bench3_planner," + ",".join(
            f"{k}={v}" for k, v in r.items()), flush=True)
    _write("BENCH_3.json", results["bench3_planner"])
    _write("BENCH_4.json", results["bench3_planner"]["pruned"])

    sizes = ((1000, 3000), (5000, 10000)) if args.fast else \
        ((2000, 5000), (10000, 20000), (50000, 50000))
    results["table1_throughput"] = throughput.run(sizes=sizes)
    for r in results["table1_throughput"]:
        print("table1," + ",".join(f"{k}={v}" for k, v in r.items()),
              flush=True)

    n_docs = 300 if args.fast else 800
    results["table2_tokenization"] = tokenization.run(n_docs=n_docs)
    for r in results["table2_tokenization"]:
        print("table2," + ",".join(f"{k}={v}" for k, v in r.items()),
              flush=True)

    results["tokenize_throughput"] = tokenization.run_throughput(
        n_docs=1000 if args.fast else 3000)
    print("tokenize_throughput," + ",".join(
        f"{k}={v}" for k, v in results["tokenize_throughput"].items()),
        flush=True)

    results["table3_variants"] = variants.run(n_docs=n_docs)
    for r in results["table3_variants"]:
        print("table3," + ",".join(f"{k}={v}" for k, v in r.items()),
              flush=True)

    results["kernels"] = kernels_bench.run(
        n_docs=2048 if args.fast else 8192,
        n_vocab=2000 if args.fast else 8000)
    for r in results["kernels"]:
        print("kernels," + ",".join(f"{k}={v}" for k, v in r.items()),
              flush=True)

    os.makedirs("benchmarks/out", exist_ok=True)
    with open("benchmarks/out/results.json", "w") as f:
        json.dump(results, f, indent=1)
    print(f"done in {time.time() - t0:.1f}s -> benchmarks/out/results.json")


if __name__ == "__main__":
    main()
