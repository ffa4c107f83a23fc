"""Share of the HBM roofline reached by the whole retrieve step, in %.

Work: every posting of each batch's distinct query tokens is read once
from HBM, 8 bytes each (int32 document id, float32 score), counted by the
benchmark from the index's document frequencies, so it reads the same
whatever implements the step. Least time: those bytes over the chip's
HBM bandwidth (``bench/peaks.json``); the step is bound by bytes, not
operations (one add per posting). Time: device time of every op in the
traced window (fragment planning, scoring, top-k).
"""

POSTING_BYTES = 8


def read(ctx):
    if ctx.trace is None or not ctx.batches:
        return None
    t = ctx.trace.op_seconds()
    if t <= 0:
        return None
    least = (POSTING_BYTES * sum(b.sum_df for b in ctx.batches)
             / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * least / t
