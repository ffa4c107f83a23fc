"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/calibrate.py --workload <name> --seeds a,b,c \
        --seconds <s>

For each seed, in this one process: build the cell, run its window as a
run does, sample its answers as a run does, and print one JSON line with

* ``program`` - the compared numbers of the program's answers against the
  float64 reference (the lower reading of each limit);
* ``control`` - the same numbers for the control: the reference itself,
  computed in bfloat16 (the precision below the configuration's float32
  scores), put in the program's place for the same sampled queries. It
  has to come out as not correct; its ``score_err`` is the upper reading.

The benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def control_answers(bench, sample, k: int):
    """The bfloat16 reference's boards for the sampled queries."""
    import ml_dtypes
    import numpy as np
    from bench import check
    low = check.reference_for(bench.cfg, bench.corpus,
                              [a.query for a in sample],
                              dtype=ml_dtypes.bfloat16)
    out = []
    for a in sample:
        ids, vals = low.top_k(a.query, k)
        out.append(check.Answer(a.query, ids, vals.astype(np.float32)))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import check
    from bench.harness import Bench, load_cell
    cell = load_cell(ROOT, args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        bench = Bench(ROOT, cell, seed)
        try:
            plan = bench.plan(args.seconds)
            bench.warm(plan)
            ctx = bench.window(plan, args.seconds)
            bench.free_program()
            sample = bench.sample(ctx)
            prog = check.compare(bench.cfg, bench.corpus, sample, plan.k)
            ctrl = check.compare(bench.cfg, bench.corpus,
                                 control_answers(bench, sample, plan.k),
                                 plan.k)
        finally:
            bench.close()
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "sampled": len(sample),
                          "program": {k: v["value"] for k, v in prog.items()},
                          "control": {k: v["value"] for k, v in ctrl.items()},
                          "program_correct": check.passed(prog),
                          "control_correct": check.passed(ctrl)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
