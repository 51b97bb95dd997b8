"""latency_p50_ms: median over all requests due in the window, from the
time a request was due to the return of its verdict."""

import numpy as np


def read(ctx):
    lat = ctx.run.get("latency_s")
    return None if lat is None or lat.size == 0 else float(np.percentile(lat, 50)) * 1e3
