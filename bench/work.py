"""The plan's evaluated work, counted from the model's shapes.

Operations: every served row pays ``model_ops`` for each base model it
evaluated, up to its exit step.  Bytes: the least that has to cross HBM,
each served row's features (four bytes each, as the server holds them)
read once and its verdict and exit step (8 bytes) written once, plus, per
flush, the parameters of the models that its deepest row reached.  Leaf,
corner and feature re-reads within a flush are not counted: a kernel can
hold them on chip, and counting them could put a sound kernel above its
roofline.

A kind's ``model_ops(cfg)`` and ``model_param_bytes(cfg)`` give one number
for every base model alike, or a length-T array in original model order
where the models differ (a dense layer beside expert layers).  An array is
charged in plan ``order``: a row that exits at step ``k`` pays the first
``k`` models of the plan.
"""

from __future__ import annotations

import numpy as np


def _charged(per_model, order, steps) -> float:
    """Σ over ``steps`` of the work of the plan's first ``step`` models."""
    per = np.asarray(per_model, np.float64)
    if per.ndim == 0:
        return float(np.sum(steps, dtype=np.float64)) * per_model
    prefix = np.concatenate([[0.0], np.cumsum(per[np.asarray(order)])])
    return float(np.sum(prefix[np.asarray(steps, np.int64)]))


def ops(ens, cfg: dict, exit_steps: np.ndarray, order) -> float:
    return _charged(ens.model_ops(cfg), order, exit_steps)


def hbm_bytes(
    ens, cfg: dict, features: int, exit_steps: np.ndarray, flush_max_exit, order
) -> float:
    rows = float(exit_steps.size) * (4 * features + 8)
    return rows + _charged(ens.model_param_bytes(cfg), order, flush_max_exit)


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The larger of the compute bound and the bandwidth bound on one chip."""
    return max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
