"""Production serving launcher (the paper's workload).

    PYTHONPATH=src python -m repro.launch.serve --docs 20000 --shards 4 \\
        --queries 100 --k 10 [--variant bm25+] [--deadline-ms 200]

Builds the sharded eager index (distributed build: global-stats pass +
per-shard scoring), starts the hedged retrieval engine, serves a query
stream and prints QPS / tail latency / degradation stats. Shards score
through the device scorer (``--scorer auto``: ``DeviceRetriever`` with
the cost planner); ``--scorer scipy`` serves from the host reference
scorer instead. ``--quorum 1.0`` (default) waits for every shard, so a
response is exact; a lower quorum lets shards that miss the deadline drop
out of the merge, and the summary counts how many shards answered.
``--straggle`` injects a slow shard to demonstrate deadline hedging.
"""

from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--docs", type=int, default=20_000)
    ap.add_argument("--vocab", type=int, default=20_000)
    ap.add_argument("--shards", type=int, default=4)
    ap.add_argument("--queries", type=int, default=100)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--variant", default="lucene")
    ap.add_argument("--k1", type=float, default=1.5)
    ap.add_argument("--b", type=float, default=0.75)
    ap.add_argument("--deadline-ms", type=float, default=500.0)
    ap.add_argument("--quorum", type=float, default=1.0)
    ap.add_argument("--scorer", default="auto",
                    choices=("auto", "blocked", "gathered", "pruned",
                             "scipy"))
    ap.add_argument("--straggle", action="store_true",
                    help="make shard 0 sleep 1s (hedging demo)")
    ap.add_argument("--rescale", type=int, default=None,
                    help="elastically re-shard to N after half the stream")
    args = ap.parse_args()

    import jax
    import numpy as np

    from ..core import BM25Params, build_sharded_indexes
    from ..data.corpus import zipf_corpus, zipf_queries
    from ..serve import RetrievalEngine
    from .compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    print(f"[serve] device {dev.platform} ({dev.device_kind}) x"
          f"{len(jax.devices())}, compile cache {enable_compile_cache()}")
    print(f"[serve] indexing {args.docs} docs "
          f"({args.variant}, k1={args.k1}, b={args.b}) "
          f"into {args.shards} shards...")
    t0 = time.time()
    corpus = zipf_corpus(args.docs, args.vocab, avg_len=80)
    params = BM25Params(method=args.variant, k1=args.k1, b=args.b)
    shards = build_sharded_indexes(corpus, args.vocab, args.shards,
                                   params=params)
    print(f"[serve] indexed in {time.time() - t0:.1f}s "
          f"({sum(s.nnz for s in shards) / 1e6:.2f}M postings)")

    delay = (lambda i: (lambda: 1.0) if i == 0 else None) \
        if args.straggle else None
    t0 = time.time()
    engine = RetrievalEngine(shards, k=args.k,
                             deadline_s=args.deadline_ms / 1e3,
                             quorum=args.quorum, delay=delay,
                             scorer=args.scorer)
    print(f"[serve] {args.scorer} scorer built and warmed in "
          f"{time.time() - t0:.1f}s")

    queries = zipf_queries(args.queries, args.vocab, q_len=5)
    lat, degraded, answered = [], 0, []
    t0 = time.time()
    for i, q in enumerate(queries):
        if args.rescale and i == len(queries) // 2:
            print(f"[serve] elastic re-shard -> {args.rescale}")
            engine.rescale(args.rescale)
        r = engine.retrieve(q)
        lat.append(r.latency_s)
        degraded += int(r.degraded)
        answered.append(r.shards_answered)
    dt = time.time() - t0
    lat = np.asarray(lat)
    print(f"[serve] {len(queries)} queries  {len(queries) / dt:.1f} QPS  "
          f"p50 {1e3 * np.percentile(lat, 50):.1f}ms  "
          f"p99 {1e3 * np.percentile(lat, 99):.1f}ms  "
          f"degraded {degraded}/{len(queries)}  shards answered "
          f"min {min(answered)} / {len(engine.runtimes)}")
    h = engine.health()
    print(f"[serve] health: {h['served']} responses, {h['degraded']} "
          f"degraded, shard ladder hops "
          f"{sum(s['degraded'] for s in h['shards'])}, faults "
          f"{h['faults'] or 'none'}")


if __name__ == "__main__":
    main()
