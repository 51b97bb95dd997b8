"""The plain cascade: the plan's order and thresholds over plain scores.

A row's partial score is accumulated in float32, one base model at a time
in the plan's order.  At position ``t`` a live row exits negative when its
score is below ``eps_neg[t]``, else positive when above ``eps_pos[t]``; a
row that never exits is positive when its full score reaches ``beta``.
This is the QWYC cascade as the paper defines it, written with nothing of
the program.

Rounding: an implementation that computes each base model's score in a
different but sound order of float32 operations may cross a threshold
that the reference's score lies within rounding of.  Such rows are marked
``ambiguous`` rather than compared: a row is ambiguous when, at any
position it reaches, its score lies within ``rel_tol`` times the sum of
the absolute base-model scores it has accumulated of a threshold tested
there.  The bound is far above float32 rounding (about 1e-7 per
operation) and far below what computing in bfloat16 (about 4e-3 per
score) moves.
"""

from __future__ import annotations

import numpy as np

REL_TOL = 1e-5


def cascade(
    ordered: np.ndarray,
    eps_pos: np.ndarray,
    eps_neg: np.ndarray,
    beta: float,
    rel_tol: float = REL_TOL,
    dtype=np.float32,
):
    """(decisions, exit_step, ambiguous) for (N, T) scores in plan order,
    accumulated at ``dtype``."""
    f = np.asarray(ordered, dtype)
    n, T = f.shape
    ep = np.asarray(eps_pos, dtype)
    en = np.asarray(eps_neg, dtype)
    g = np.zeros(n, dtype)
    mass = np.zeros(n, np.float64)
    live = np.ones(n, bool)
    dec = np.zeros(n, bool)
    ex = np.full(n, T, np.int64)
    amb = np.zeros(n, bool)
    with np.errstate(invalid="ignore"):
        for t in range(T):
            g = np.where(live, g + f[:, t], g)
            mass += np.abs(f[:, t])
            tol = rel_tol * mass
            neg = live & (g < en[t])
            pos = live & (g > ep[t]) & ~neg
            near = (np.abs(g - en[t]) <= tol) | (np.abs(g - ep[t]) <= tol)
            amb |= live & near
            out = neg | pos
            dec |= pos
            ex[out] = t + 1
            live &= ~out
        b = dtype(beta)
        dec[live] = g[live] >= b
        amb |= live & (np.abs(g - b) <= rel_tol * mass)
    return dec, ex, amb


def full_decisions(scores: np.ndarray, beta: float) -> np.ndarray:
    """The full ensemble's verdicts, summed in float64."""
    return np.asarray(scores, np.float64).sum(axis=1) >= beta
