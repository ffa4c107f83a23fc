"""Run one benchmark cell once, on the chip this process holds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

From the root of a checkout. The cell, its configuration, its traffic mix
and its metrics are found by name from ``BENCHMARK.json`` (see
``bench/harness.py``). Standard error carries the set-up phases and, as
its last lines, each compared number beside its limit; the last line of
standard output is the result as one JSON object. Exits 2, printing no
result, when JAX finds no TPU or fewer chips than the cell asks for, and
exits 3, printing no result, when three windows in a row compiled
something (a window that compiles is not measured but run again).
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench.harness import NoChip, WindowCompiled, run_cell
    try:
        result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=T_START)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    except WindowCompiled as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
