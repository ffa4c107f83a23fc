"""Prove the served retrieval path runs, exact, on one TPU chip.

    python3 chip_smoke.py [--seed 0] [--n-docs 2097152]

Runs ``repro.launch.smoke.run_smoke`` in this one process: the
``configs/bm25s.py`` deployment (2M documents, 200k vocabulary, lucene)
built from ``--seed``, served through ``ServingFrontend`` →
``DeviceRetriever`` / ``RetrievalEngine`` in every retrieval regime, every
board compared with the ``ScipyBM25`` oracle. The last line of standard
output is ``{"ok": true, "device": {...}}``; it is printed only when every
check passed. Without a TPU the script exits with status 2 before any
work, and any failed check exits non-zero.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--n-docs", type=int, default=None,
                    help="documents in the served corpus (default: the "
                         "deployment's 2,097,152)")
    ap.add_argument("--queries", type=int, default=128)
    args = ap.parse_args(argv)
    t0 = time.perf_counter()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found "
              f"{devices[0].platform}", file=sys.stderr)
        return 2

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.smoke import SmokeConfig, run_smoke

    print(f"setup compile cache {enable_compile_cache()}", flush=True)
    cfg = SmokeConfig(seed=args.seed, n_queries=args.queries)
    if args.n_docs is not None:
        cfg.n_docs = args.n_docs
    run_smoke(cfg)
    print(f"setup whole run took {time.perf_counter() - t0:.1f} s",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
