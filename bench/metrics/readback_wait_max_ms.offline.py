"""The longest single host wait on a device read-back in the window, in
ms: the largest of the program's ``fragments.overflow_wait`` (fragment
count of the builder) and ``board.wait`` (the ``[B, k]`` board) spans."""

from bench.spans import window_records


def read(ctx):
    recs = window_records(ctx)
    if recs is None:
        return None
    waits = [s.ns for r in recs for s in r.spans
             if s.name in ("fragments.overflow_wait", "board.wait")]
    return max(waits) / 1e6 if waits else None
