"""95th percentile of how late the load generator submitted a request
(submit time minus due time)."""

import numpy as np


def read(ctx):
    lag = ctx.get("generator_lag_ms")
    if lag is None or len(lag) == 0:
        return None
    return float(np.percentile(lag, 95))
