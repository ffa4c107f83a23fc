"""How late the load generator sent, in ms: the 95th percentile over the
window's requests of send time minus due time (host clock)."""

import numpy as np


def read(ctx):
    if ctx.gen_lag_s is None or not len(ctx.gen_lag_s):
        return None
    return 1e3 * float(np.percentile(ctx.gen_lag_s, 95))
