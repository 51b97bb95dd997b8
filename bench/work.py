"""The plan's evaluated work, counted from the model's shapes.

Operations: every served row pays ``model_ops`` for each base model it
evaluated, i.e. its exit step.  Bytes: the least that has to cross HBM,
each served row's float32 features read once and its verdict and exit
step (8 bytes) written once, plus, per flush, the parameters of the
models that its deepest row reached.  Leaf, corner and feature re-reads
within a flush are not counted: a kernel can hold them on chip, and
counting them could put a sound kernel above its roofline.
"""

from __future__ import annotations

import numpy as np


def ops(ens, cfg: dict, exit_steps: np.ndarray) -> float:
    return float(np.sum(exit_steps, dtype=np.float64)) * ens.model_ops(cfg)


def hbm_bytes(ens, cfg: dict, features: int, exit_steps: np.ndarray, flush_max_exit) -> float:
    rows = float(exit_steps.size) * (4 * features + 8)
    params = float(np.sum(flush_max_exit, dtype=np.float64)) * ens.model_param_bytes(cfg)
    return rows + params


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The larger of the compute bound and the bandwidth bound on one chip."""
    return max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
