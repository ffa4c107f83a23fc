"""Device ms per batch of device-side fragment planning: trace time of
every op of the ``build_fragment_table`` program over the window's
batches. Nothing to read where no batch planned its fragments on the
device."""


def read(ctx):
    if ctx.trace is None or not ctx.batches:
        return None
    s = ctx.trace.seconds(r"build_fragment_table")
    return 1e3 * s / len(ctx.batches) if s > 0 else None
