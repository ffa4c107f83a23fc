"""Device ms per query in the scoring kernels, whichever regime ran:
trace time of every ``bm25_*score*topk*`` Pallas kernel (resident gather,
its double-buffered and pruned forms, the blocked full scan) over the
queries answered."""


def read(ctx):
    if ctx.trace is None or not ctx.queries:
        return None
    s = ctx.trace.seconds(r"bm25_\w*score\w*topk")
    return 1e3 * s / ctx.queries if s > 0 else None
