"""Mean size of the batches the frontend formed over its ``max_batch``, in
%: ``health()["mean_batch"]`` of ``ServingFrontend`` for the window."""


def read(ctx):
    h = ctx.frontend
    if not h or not h.get("batches"):
        return None
    return 100.0 * h["mean_batch"] / h["max_batch"]
