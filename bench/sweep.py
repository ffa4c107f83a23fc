"""Find the knee of an open-loop cell once, by a sweep on the chip.

    python3 bench/sweep.py --workload quora-online --seed <n> \
        --seconds 10 --rates 250,500,1000,2000

Builds the cell's system once, then for each rate runs the cell's mix
with ``rate_qps`` replaced (warm-up, then an open-loop window) and prints
one JSON line: p50 and p95 over all requests, the share completed within
a second of the last send, the backlog (sent but not answered) at each
quarter of the sending window, the load generator's lag and the mean
formed batch. The knee is the highest rate at which nearly every request
completes and the backlog at the end is no longer than at the start; the
cell's traffic file then states four fifths of it as a number.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench.harness import Bench, load_cell
    cell = load_cell(ROOT, args.workload)
    bench = Bench(ROOT, cell, args.seed)
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            traffic = {**cell.traffic, "rate_qps": rate}
            plan = bench.plan(args.seconds, traffic)
            bench.warm(plan, traffic)
            ctx = bench.window(plan, args.seconds, traffic=traffic)
            lat = ctx.latency_s
            t0 = 0.0
            due = plan.due_s + 0.005
            done = due + lat
            quarters = []
            for f in (0.25, 0.5, 0.75, 1.0):
                t = t0 + f * ctx.close_s
                quarters.append(int((due <= t).sum() - (done <= t).sum()))
            print(json.dumps({
                "rate_qps": rate, "requests": ctx.queries,
                "failed": ctx.failed,
                "p50_ms": 1e3 * float(np.percentile(lat, 50)),
                "p95_ms": 1e3 * float(np.percentile(lat, 95)),
                "done_1s_after_close": float(
                    (done <= ctx.close_s + 1.0).mean()),
                "backlog_quarters": quarters,
                "gen_lag_p95_ms": 1e3 * float(
                    np.percentile(ctx.gen_lag_s, 95)),
                "mean_batch": ctx.frontend.get("mean_batch"),
                "compiles_in_window": ctx.compiles,
                "at": time.strftime("%H:%M:%S")}), flush=True)
    finally:
        bench.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
