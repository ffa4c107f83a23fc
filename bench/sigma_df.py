"""Posting work per window across seeds, on the CPU, at full size.

    python3 bench/sigma_df.py [--seeds 12] [--chunk 64] [--chunks 24]
                              [config ...]

For each configuration (default: every file in ``bench/configs``) and
each seed, builds the corpus and a window of ``--chunks`` stratified
chunks of ``--chunk`` queries, exactly as a run does, and prints:

* ``sum_df/query`` - the mean over the window's queries of each query's
  sum of document frequencies (its postings);
* ``batch sum_df`` - the mean over chunks of the sum of document
  frequencies of the chunk's distinct tokens, the postings a batch of
  that chunk reads;
* the pow2 buckets those batch sums fall into.

Then the spread across seeds of each, as the contract measures spreads:
the distance between the first and third quartiles over the median.
Counts only; no JAX, no timing.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from bench.corpus import Shape, make_corpus, make_queries  # noqa: E402


def document_frequency(corpus) -> np.ndarray:
    doc = np.repeat(np.arange(corpus.n_docs, dtype=np.int64),
                    np.diff(corpus.offsets))
    key = np.unique(doc * corpus.n_vocab + corpus.tokens)
    return np.bincount(key % corpus.n_vocab, minlength=corpus.n_vocab)


def spread(values) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("configs", nargs="*")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2_147_483_700)
    ap.add_argument("--chunk", type=int, default=64)
    ap.add_argument("--chunks", type=int, default=24)
    args = ap.parse_args(argv)
    names = args.configs or sorted(
        f[:-5] for f in os.listdir(os.path.join(HERE, "configs"))
        if f.endswith(".json"))
    for name in names:
        with open(os.path.join(HERE, "configs", name + ".json")) as f:
            shape = Shape.from_config(json.load(f))
        per_query, per_batch = [], []
        for s in range(args.seeds):
            seed = args.first_seed + 7919 * s
            df = document_frequency(make_corpus(shape, seed))
            qs = make_queries(shape, seed, n_chunks=args.chunks,
                              chunk=args.chunk)
            per_query.append(float(np.mean([df[q].sum() for q in qs])))
            sums = [int(df[np.unique(np.concatenate(
                qs[c * args.chunk:(c + 1) * args.chunk]))].sum())
                for c in range(args.chunks)]
            per_batch.append(float(np.mean(sums)))
            buckets = sorted({1 << int(np.ceil(np.log2(x))) for x in sums})
            print(f"{name} seed {seed}: sum_df/query {per_query[-1]:.1f} "
                  f"batch sum_df {per_batch[-1]:.1f} (min {min(sums)} "
                  f"max {max(sums)}, pow2 buckets {buckets})", flush=True)
        print(f"{name}: {args.seeds} seeds, {args.chunks} chunks of "
              f"{args.chunk}: sum_df/query median "
              f"{statistics.median(per_query):.1f} spread "
              f"{spread(per_query):.5f}; batch sum_df median "
              f"{statistics.median(per_batch):.1f} spread "
              f"{spread(per_batch):.5f}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
