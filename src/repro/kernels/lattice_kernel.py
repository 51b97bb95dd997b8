"""Pallas TPU kernel: multilinear lattice interpolation (base-model eval).

The paper's real-world ensembles are lattices — interpolated look-up tables.
A lattice over S features evaluates as a contraction of its (2,)*S parameter
tensor with per-dimension [1-x_j, x_j] vectors.  The TPU-native formulation
used here builds the (block_n, 2**S) corner-weight matrix in VMEM (pure
VPU): corner c's weight is the product, over features j in order, of x_j
or 1 - x_j as bit j of c (MSB-first) says — the same products, in the same
order, as S interleaved doublings — and finishes with an elementwise
(block_n, 2**S) * theta product summed by repeated halving (a fixed
pairwise order, so XLA and Mosaic, CPU and TPU all produce the same bits)
instead of the gather-heavy GPU formulation.

Feature subsets are per-lattice column ids; like the tree kernel they
arrive as one-hot (S, D) masks built outside the kernel.  Grid, blocking
and the live-count guard are the tree kernel's (``model_block_call``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.interpret import resolve_interpret
from repro.kernels.tree_kernel import (
    feature_masks,
    model_block_call,
    select_feature,
)

DEFAULT_BLOCK_N = 256

__all__ = ["lattice_scores_pallas", "lattice_column", "halving_sum"]


def halving_sum(v):
    """Sum over the last (power-of-two) axis by repeated halving, keeping
    it as a length-1 axis.  The pairwise order is spelled out, so every
    backend — XLA or Mosaic, CPU or TPU — adds the same pairs."""
    while v.shape[-1] > 1:
        h = v.shape[-1] // 2
        v = v[..., :h] + v[..., h:]
    return v


def lattice_column(x, masks, theta):
    """One lattice for every row of ``x`` (n, D) -> (n, 1) scores.

    ``masks[j]`` is feature j's (1 or n, D) column mask and ``theta`` the
    (1 or n, 2**S) parameter row (shared or per lane, as in
    ``tree_column``)."""
    S = len(masks)
    corner = jax.lax.broadcasted_iota(jnp.int32, (1, theta.shape[1]), 1)
    w = jnp.ones((x.shape[0], theta.shape[1]), x.dtype)
    for j, mask in enumerate(masks):
        xj = select_feature(x, mask)
        bit = ((corner >> (S - 1 - j)) & 1) != 0
        w = w * jnp.where(bit, xj, 1.0 - xj)
    return halving_sum(w * theta)


def _lattice_kernel(nv_ref, x_ref, fm_ref, theta_ref, out_ref, *, S: int):
    bn, tc = out_ref.shape
    block_start = pl.program_id(0) * bn

    # live-count block guard (DESIGN.md §5): blocks past the compacted
    # live rows skip the interpolation and emit zeros.
    @pl.when(block_start >= nv_ref[0])
    def _skip():
        out_ref[...] = jnp.zeros((bn, tc), dtype=out_ref.dtype)

    @pl.when(block_start < nv_ref[0])
    def _eval():
        x, fm, theta = x_ref[...], fm_ref[...], theta_ref[...]
        for t in range(tc):
            r = t * S
            out_ref[:, t:t + 1] = lattice_column(
                x,
                [fm[r + j:r + j + 1, :] != 0 for j in range(S)],
                theta[t:t + 1, :],
            )


def lattice_scores_pallas(
    theta: jax.Array,
    feats: jax.Array,
    x: jax.Array,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool | None = None,
    t0: int = 0,
    t1: int | None = None,
    rows: jax.Array | None = None,
    n_valid: jax.Array | None = None,
) -> jax.Array:
    """Evaluate lattices [t0, t1) on N examples -> (N, t1 - t0) scores.

    theta: (T, 2**S) float; feats: (T, S) int32; x: (N, D) in [0, 1].

    ``t0``/``t1`` restrict the model axis to one cascade chunk (only those
    lattices' theta blocks are DMA'd) and ``rows`` gathers surviving
    examples before blocking — the lazy chunked execution hooks of
    DESIGN.md §4.  ``n_valid`` (traced scalar) makes row-blocks past the
    live count skip compute and emit zeros — the device executor's
    fixed-capacity hook (DESIGN.md §5).  Defaults preserve the eager
    full-matrix behaviour.  ``interpret=None`` runs compiled on an
    accelerator and interpreted where ``x`` lives on the CPU.
    """
    return _lattice_scores(
        theta, feats, x, rows, n_valid, block_n=block_n,
        interpret=resolve_interpret(interpret, x), t0=t0, t1=t1,
    )


@functools.partial(
    jax.jit, static_argnames=("block_n", "interpret", "t0", "t1")
)
def _lattice_scores(theta, feats, x, rows, n_valid, *, block_n, interpret,
                    t0, t1):
    T, p = theta.shape
    S = feats.shape[1]
    assert p == 1 << S
    if t1 is None:
        t1 = T
    assert 0 <= t0 < t1 <= T
    if rows is not None:
        x = jnp.take(x, jnp.asarray(rows, dtype=jnp.int32), axis=0)
    feats = feats[t0:t1].astype(jnp.int32)
    return model_block_call(
        functools.partial(_lattice_kernel, S=S),
        x,
        [theta[t0:t1].astype(x.dtype)],
        feature_masks(feats, x.shape[1]),
        block_n=block_n, n_valid=n_valid, dtype=x.dtype, interpret=interpret,
    )
