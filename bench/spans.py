"""The program's own record of the window's batches, for the readers of
its spans and counters (``repro.obs``).

The retriever keeps one record per batch in a ring, oldest first; the
window's batches are the last ``len(ctx.batches)`` of them. A record
holds the spans of the host work done for its batch (name, parent, start
and end in ns on ``time.perf_counter_ns``) and the counters of the work it
asked of the device. A program without ``repro.obs`` has nothing to read.
"""

from __future__ import annotations


def window_records(ctx):
    """The records of the window's batches, in order, or None where the
    program keeps none or they are not the window's: each record's
    ``sum_df`` counter must equal that batch's ``RetrievalPlan.sum_df``."""
    try:
        from repro import obs
    except ImportError:
        return None
    n = len(ctx.batches)
    recs = obs.batches()[-n:] if n else []
    if not n or len(recs) < n:
        return None
    for rec, b in zip(recs, ctx.batches):
        if rec.counters.get("sum_df") != b.plan_sum_df:
            return None
    return recs


def counter_sums(ctx, *names):
    """Each named counter summed over the window's batches, or None where
    a record lacks one of them."""
    recs = window_records(ctx)
    if recs is None or any(name not in r.counters
                           for r in recs for name in names):
        return None
    return [sum(r.counters[name] for r in recs) for name in names]
