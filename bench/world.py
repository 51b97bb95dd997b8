"""The synthetic world a configuration's ensemble is trained on and served.

A copy of the paper-analogue generator (clustered features, a random
smooth decision function, CDF-squashed into [0, 1]), kept with the
benchmark so that the rows it serves and checks do not depend on the
program.  One call draws ``train_rows + pool_rows`` rows from the world
seed: the first ``train_rows`` train the ensemble and calibrate the plan,
the rest are the held-out pool that traffic draws requests from.  A kind
that brings its own rows (``world(cfg)`` in its ensemble module) returns
the same ``World``, possibly of integer rows.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class World:
    x_train: np.ndarray  # (train_rows, D): here float32 in [0, 1]
    y_train: np.ndarray  # (train_rows,) int64 labels
    pool: np.ndarray  # (pool_rows, D), like x_train, held out from training


def _sigmoid(z):
    return 1.0 / (1.0 + np.exp(-z))


def _nonlinear_logit(x, rng, hardness: float, n_terms: int = 12):
    d = x.shape[1]
    w = rng.normal(size=(n_terms, d)) / np.sqrt(d)
    b = rng.normal(size=n_terms)
    amp = rng.normal(size=n_terms)
    h = np.tanh(x @ w.T + b) @ amp
    pair = np.zeros(x.shape[0])
    for _ in range(min(6, d)):
        i, j = rng.integers(0, d, size=2)
        pair += rng.normal() * x[:, i] * x[:, j]
    z = h + pair
    z = (z - z.mean()) / (z.std() + 1e-9)
    return z / max(hardness, 1e-3)


def make_world(w: dict, train_rows: int, pool_rows: int) -> World:
    """``w`` holds the world's ``seed``, ``features``, ``pos_rate``,
    ``hardness`` and ``label_noise``."""
    rng = np.random.default_rng(int(w["seed"]))
    d, hardness = int(w["features"]), float(w["hardness"])
    n = train_rows + pool_rows
    centers = rng.normal(size=(3, d))
    comp = rng.integers(0, 3, size=n)
    x = centers[comp] + rng.normal(size=(n, d)) * rng.uniform(0.5, 1.5, size=d)
    z = _nonlinear_logit(x, rng, hardness)
    thr = np.quantile(z, 1.0 - float(w["pos_rate"]))
    p = _sigmoid((z - thr) / max(hardness, 1e-3) * 2.0)
    y = (rng.uniform(size=n) < p).astype(np.int64)
    flip = rng.uniform(size=n) < float(w["label_noise"])
    y = np.where(flip, 1 - y, y)
    x = _sigmoid((x - x.mean(0)) / (x.std(0) + 1e-9)).astype(np.float32)
    return World(x[:train_rows], y[:train_rows], np.ascontiguousarray(x[train_rows:]))
