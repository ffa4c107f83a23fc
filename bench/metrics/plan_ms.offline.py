"""Host ms per batch in the retriever's planning: the mean over the
window's batches of the program's ``retriever.plan`` span (Σ df, the
block-max survivor estimate where it runs, the regime choice)."""

from bench.spans import window_records


def read(ctx):
    recs = window_records(ctx)
    if recs is None:
        return None
    return sum(r.total_ns("retriever.plan") for r in recs) / len(recs) / 1e6
