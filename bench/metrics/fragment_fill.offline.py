"""Share of the scoring kernel's fragment slots that hold real fragments,
in %: 100 x the window's summed ``frags`` over its summed ``frag_slots``
(the pow2 fragment bucket the kernel's grid runs over), both program
counters."""

from bench.spans import counter_sums


def read(ctx):
    sums = counter_sums(ctx, "frags", "frag_slots")
    if sums is None or not sums[1]:
        return None
    return 100.0 * sums[0] / sums[1]
