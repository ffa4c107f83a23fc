"""Host ms per batch that the device waits for: the mean over the window's
batches of the program's ``retriever.pack`` and ``retriever.retrieve``
spans, less the spans in which the host waits on the device
(``fragments.overflow_wait``, ``board.wait``)."""

from bench.spans import window_records


def read(ctx):
    recs = window_records(ctx)
    if recs is None:
        return None
    host = [r.total_ns("retriever.pack") + r.total_ns("retriever.retrieve")
            - r.total_ns("fragments.overflow_wait")
            - r.total_ns("board.wait") for r in recs]
    return sum(host) / len(host) / 1e6
