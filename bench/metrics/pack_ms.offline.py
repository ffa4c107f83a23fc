"""Host seconds the retriever spends packing one batch, in ms: the mean of
``PackedBatch.pack_s`` over the window's batches (the program's own timer
around ``DeviceRetriever.pack_batch``)."""


def read(ctx):
    packs = [b.pack_s for b in ctx.batches]
    return 1e3 * sum(packs) / len(packs) if packs else None
