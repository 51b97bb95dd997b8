"""generator_lag_p99_ms.online: how late the load generator woke for a
request that fell due while it was idle (99th percentile, host clock).
A starved generator reads high here, not as a fast server."""

import numpy as np


def read(ctx):
    lag = ctx.run.get("generator_lag_s")
    return None if lag is None or lag.size == 0 else float(np.percentile(lag, 99)) * 1e3
