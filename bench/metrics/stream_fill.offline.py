"""Share of the fragment builder's flat posting stream that holds real
postings, in %: 100 x the window's summed ``sum_df`` over its summed
``stream_positions`` (the pow2 bucket of Σ df the builder is compiled
for), both program counters."""

from bench.spans import counter_sums


def read(ctx):
    sums = counter_sums(ctx, "sum_df", "stream_positions")
    if sums is None or not sums[1]:
        return None
    return 100.0 * sums[0] / sums[1]
