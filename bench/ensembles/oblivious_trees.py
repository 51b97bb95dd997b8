"""Gradient-boosted oblivious trees (the paper's GBT ensembles).

An oblivious tree of depth ``d`` tests one (feature, threshold) pair per
level, shared across the level; the ``d`` test bits, most significant
first, index its ``2**d`` leaves.  Training is second-order boosting on
the logistic loss with quantile-binned greedy level search (a copy of the
recipe the paper's GBT cells use, kept here so the weights are the
benchmark's own).
"""

from __future__ import annotations

import functools

import numpy as np

ROW_BLOCK = 1024


def _fit_tree(x, grad, hess, bins, edges, depth, l2):
    n, d = bins.shape
    b = edges.shape[1]
    leaf = np.zeros(n, dtype=np.int64)
    feats, thrs = [], []
    for lev in range(depth):
        n_leaf = 1 << lev
        best = (-np.inf, 0, 0)
        for f in range(d):
            idx = leaf * b + bins[:, f]
            cg = np.bincount(idx, weights=grad, minlength=n_leaf * b).reshape(n_leaf, b)
            ch = np.bincount(idx, weights=hess, minlength=n_leaf * b).reshape(n_leaf, b)
            gl, hl = np.cumsum(cg, axis=1), np.cumsum(ch, axis=1)
            gr, hr = gl[:, -1:] - gl, hl[:, -1:] - hl
            gain = (gl**2 / (hl + l2) + gr**2 / (hr + l2)).sum(axis=0)
            k = int(np.argmax(gain[:-1]))
            if gain[k] > best[0]:
                best = (float(gain[k]), f, k)
        _, f, k = best
        feats.append(f)
        thrs.append(float(edges[f, k]))
        leaf = 2 * leaf + (bins[:, f] > k)
    n_leaves = 1 << depth
    gs = np.bincount(leaf, weights=grad, minlength=n_leaves)
    hs = np.bincount(leaf, weights=hess, minlength=n_leaves)
    return feats, thrs, gs / (hs + l2)


def train(cfg: dict, world) -> tuple[dict, float]:
    x = np.asarray(world.x_train, np.float64)
    y = np.asarray(world.y_train, np.float64)
    n, d = x.shape
    T, depth, n_bins = int(cfg["n_trees"]), int(cfg["depth"]), int(cfg["n_bins"])
    lr, l2 = float(cfg["learning_rate"]), float(cfg["l2"])
    qs = np.linspace(0, 1, n_bins + 1)[1:-1]
    edges = np.quantile(x, qs, axis=0).T
    edges = np.concatenate([edges, x.max(0)[:, None] + 1.0], axis=1)
    bins = np.empty((n, d), dtype=np.int16)
    for f in range(d):
        bins[:, f] = np.searchsorted(edges[f], x[:, f], side="left")
    bins = np.minimum(bins, n_bins - 1)
    p0 = np.clip(y.mean(), 1e-6, 1 - 1e-6)
    base = float(np.log(p0 / (1 - p0)))
    s = np.full(n, base)
    feats = np.zeros((T, depth), np.int32)
    thrs = np.zeros((T, depth), np.float32)
    leaves = np.zeros((T, 1 << depth), np.float32)
    for t in range(T):
        p = 1.0 / (1.0 + np.exp(-s))
        f_t, thr_t, val_t = _fit_tree(
            x, y - p, np.maximum(p * (1 - p), 1e-6), bins, edges, depth, l2
        )
        feats[t], thrs[t], leaves[t] = f_t, thr_t, lr * val_t
        leaf = np.zeros(n, dtype=np.int64)
        for j in range(depth):
            leaf = 2 * leaf + (x[:, f_t[j]] > thr_t[j])
        s = s + leaves[t][leaf]
    # the trees' scores exclude the prior logit, so the verdict threshold
    # on their sum is -base
    return {"feats": feats, "thrs": thrs, "leaves": leaves}, -base


@functools.cache
def _block_fn():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def block(feats, thrs, leaves, x):
        xs = x[:, feats]  # (B, T, depth)
        depth = feats.shape[1]
        weights = 2 ** jnp.arange(depth - 1, -1, -1, dtype=jnp.int32)
        idx = ((xs > thrs[None]).astype(jnp.int32) * weights).sum(-1)  # (B, T)
        return jnp.take_along_axis(leaves[None], idx[:, :, None], axis=2)[..., 0]

    return block


def scores(params: dict, x: np.ndarray) -> np.ndarray:
    """(N, T) float32 leaf values, computed in blocks of rows."""
    block = _block_fn()
    n = x.shape[0]
    out = []
    for i in range(0, n, ROW_BLOCK):
        xb = np.zeros((ROW_BLOCK, x.shape[1]), np.float32)
        xb[: min(ROW_BLOCK, n - i)] = x[i : i + ROW_BLOCK]
        out.append(np.asarray(block(params["feats"], params["thrs"], params["leaves"], xb)))
    return np.concatenate(out)[:n].astype(np.float32)


def lower_precision(params: dict) -> dict:
    """The control's weights: the leaves rounded to bfloat16, the step
    below the configuration's float32 that a faster kernel would take."""
    import jax.numpy as jnp

    q = np.asarray(jnp.asarray(params["leaves"], jnp.bfloat16).astype(jnp.float32))
    return {**params, "leaves": q}


def program_scorer(params: dict):
    from repro import api

    return api.TreeScorer(params["feats"], params["thrs"], params["leaves"])


def model_ops(cfg: dict) -> int:
    """Least operations of one tree on one row: ``depth`` compares, ``depth``
    shift-adds to build the leaf index, one add into the running score."""
    return 2 * int(cfg["depth"]) + 1


def model_param_bytes(cfg: dict) -> int:
    """One tree's float32/int32 parameters: ``depth`` feature ids and
    thresholds, ``2**depth`` leaves."""
    depth = int(cfg["depth"])
    return 4 * (2 * depth + (1 << depth))
