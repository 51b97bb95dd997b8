"""flush_ms.online: wall time per QWYCServer.flush call, host and device."""

import numpy as np


def read(ctx):
    w = ctx.run.get("flush_wall_s")
    return None if w is None or w.size == 0 else float(np.mean(w)) * 1e3
