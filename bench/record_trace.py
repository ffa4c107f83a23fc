"""Record a small profiler trace of a cell's window, for the trace
reduction's test (``bench/testdata``), and print the trace's layout.

    python3 bench/record_trace.py --workload touche-offline --seed <n> \
        --n-docs 20000 --seconds 1 --out bench/testdata/<name>.xplane.pb

Runs the cell at ``--n-docs`` documents on the chip this process holds,
traces its window exactly as a ``--trace 1`` run does, copies the
``.xplane.pb`` to ``--out`` and prints each plane's lines with a few
events and their stats.
"""

from __future__ import annotations

import argparse
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--n-docs", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from jax.profiler import ProfileData
    from bench.harness import Bench, load_cell
    from bench.trace import find_xplane, reduce_file
    cell = load_cell(ROOT, args.workload)
    bench = Bench(ROOT, cell, args.seed,
                  shape_override={"n_docs": args.n_docs})
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        plan = bench.plan(args.seconds)
        bench.warm(plan)
        bench.window(plan, args.seconds, trace_dir=trace_dir)
        shutil.copy(find_xplane(trace_dir), args.out)
    finally:
        bench.close()
        shutil.rmtree(trace_dir, ignore_errors=True)
    with open(args.out, "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    for plane in pd.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            print(f"  line {line.name!r}: {len(evs)} events")
            for ev in evs[:4]:
                print(f"    {ev.name!r} start {ev.start_ns} dur "
                      f"{ev.duration_ns} stats {dict(ev.stats)}")
    t = reduce_file(args.out)
    print(f"reduced: window {t.window_s} s, busy {t.busy_s} s, "
          f"breakdown {t.breakdown()}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
