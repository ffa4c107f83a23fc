"""Fragment-table builds per batch on the device: the mean over the
window's batches of the program's ``frag_builds`` counter (1, plus one
for each fragment-bucket overflow that reran the build)."""

from bench.spans import counter_sums


def read(ctx):
    sums = counter_sums(ctx, "frag_builds")
    return None if sums is None else sums[0] / len(ctx.batches)
