"""Ensembles of lattices (interpolated look-up tables), trained jointly.

Lattice ``t`` reads ``S`` of the row's features, ``x_{f_t(0)} .. x_{f_t(S-1)}``,
and interpolates its ``2**S`` corner values ``theta_t`` multilinearly over
the unit hypercube:

    f_t(x) = sum_c theta_t[c] * prod_j (x_j if bit_j(c) else 1 - x_j),

where ``bit_j(c)`` is bit ``S-1-j`` of corner ``c`` (the first feature is
the most significant).  Joint training minimises the logistic loss of the
sum of all lattices with Adam, as the paper's rw2 Filter-and-Score
ensemble is trained.
"""

from __future__ import annotations

import functools

import numpy as np

ROW_BLOCK = 256


def _corner_weights(xs):
    """(..., S) features in [0, 1] -> (..., 2**S) multilinear weights."""
    import jax.numpy as jnp

    w = jnp.stack([1.0 - xs[..., 0], xs[..., 0]], axis=-1)
    for j in range(1, xs.shape[-1]):
        wj = jnp.stack([1.0 - xs[..., j], xs[..., j]], axis=-1)
        w = (w[..., :, None] * wj[..., None, :]).reshape(*w.shape[:-1], -1)
    return w


def _lattice_scores(theta, feats, x):
    xs = x[:, feats]  # (B, T, S)
    return (_corner_weights(xs) * theta[None]).sum(-1)  # (B, T)


def train(cfg: dict, world) -> tuple[dict, float]:
    import jax
    import jax.numpy as jnp

    T, S = int(cfg["n_lattices"]), int(cfg["lattice_features"])
    steps, batch, lr = int(cfg["train_steps"]), int(cfg["train_batch"]), float(cfg["learning_rate"])
    x = np.asarray(world.x_train, np.float32)
    y = np.asarray(world.y_train, np.float32)
    D = x.shape[1]
    rng = np.random.default_rng(int(cfg["weights_seed"]))
    feats = np.stack([rng.choice(D, size=S, replace=False) for _ in range(T)]).astype(np.int32)
    theta = (rng.normal(size=(T, 1 << S)) * 0.1).astype(np.float32)
    idx = rng.integers(0, x.shape[0], size=(steps, min(batch, x.shape[0]))).astype(np.int32)

    def loss(th, xb, yb):
        logit = _lattice_scores(th, feats, xb).sum(axis=1)
        return jnp.mean(jnp.logaddexp(0.0, -(2.0 * yb - 1.0) * logit))

    @jax.jit
    def fit(theta, x, y, idx):
        def step(i, carry):
            th, m, v = carry
            g = jax.grad(loss)(th, x[idx[i]], y[idx[i]])
            m = 0.9 * m + 0.1 * g
            v = 0.999 * v + 0.001 * g * g
            t = (i + 1).astype(jnp.float32)
            mh, vh = m / (1.0 - 0.9**t), v / (1.0 - 0.999**t)
            return th - lr * mh / (jnp.sqrt(vh) + 1e-8), m, v

        z = jnp.zeros_like(theta)
        return jax.lax.fori_loop(0, idx.shape[0], step, (theta, z, z))[0]

    with jax.default_matmul_precision("highest"):
        theta = np.asarray(fit(theta, x, y, idx), np.float32)
    return {"theta": theta, "feats": feats}, 0.0


@functools.cache
def _block_fn():
    import jax

    return jax.jit(_lattice_scores)


def scores(params: dict, x: np.ndarray) -> np.ndarray:
    """(N, T) float32 interpolated values, computed in blocks of rows."""
    import jax

    block = _block_fn()
    n = x.shape[0]
    out = []
    with jax.default_matmul_precision("highest"):
        for i in range(0, n, ROW_BLOCK):
            xb = np.zeros((ROW_BLOCK, x.shape[1]), np.float32)
            xb[: min(ROW_BLOCK, n - i)] = x[i : i + ROW_BLOCK]
            out.append(np.asarray(block(params["theta"], params["feats"], xb)))
    return np.concatenate(out)[:n].astype(np.float32)


def lower_precision(params: dict) -> dict:
    """The control's weights: the theta rounded to bfloat16, the step
    below the configuration's float32 that a faster kernel would take."""
    import jax.numpy as jnp

    q = np.asarray(jnp.asarray(params["theta"], jnp.bfloat16).astype(jnp.float32))
    return {**params, "theta": q}


def program_scorer(params: dict):
    from repro import api

    return api.LatticeScorer(params["theta"], params["feats"])


def model_ops(cfg: dict) -> int:
    """Least operations of one lattice on one row: contracting the
    ``2**S`` corners one feature at a time, ``v0 * (1 - x) + v1 * x``,
    costs 3 operations per output over ``2**(S-1) + ... + 1 = 2**S - 1``
    outputs, plus ``S`` for the ``1 - x`` terms and one add into the
    running score."""
    S = int(cfg["lattice_features"])
    return 3 * ((1 << S) - 1) + S + 1


def model_param_bytes(cfg: dict) -> int:
    """One lattice's parameters: ``S`` int32 feature ids and ``2**S``
    float32 corner values."""
    S = int(cfg["lattice_features"])
    return 4 * (S + (1 << S))
