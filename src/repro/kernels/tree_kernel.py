"""Pallas TPU kernel: oblivious-forest evaluation (GBT base models).

An oblivious tree evaluates as: compute a ``depth``-bit leaf index from
(feature > threshold) comparisons, then look the value up in a 2**depth LUT.
GPU implementations gather; the TPU-native form here computes the index with
VPU compares and replaces both gathers — the feature column and the leaf —
with exact one-hot selects (a single nonzero term per row, so the result is
the selected value bit for bit).

Feature ids become one-hot (depth, D) masks built outside the kernel, so
the kernel body only ever broadcasts along one axis at a time (what Mosaic
lowers on a v5e).  Grid: (ceil(N / block_n), ceil(T / tc)) over row blocks
and blocks of ``tc`` trees; x block (block_n, D) re-used across the tree
blocks; per-tree params arrive as whole (tc, ...) blocks; the output block
(block_n, tc) of a (T / tc, N, tc) array holds one score column per tree.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.interpret import resolve_interpret

DEFAULT_BLOCK_N = 256
#: trees (or lattices) per grid step: the kernel body unrolls this many
MODEL_BLOCK = 32

__all__ = [
    "gbt_scores_pallas",
    "feature_masks",
    "select_feature",
    "tree_column",
    "model_blocks",
    "model_block_call",
]


def model_blocks(tk: int) -> tuple[int, int]:
    """(models per grid step, grid steps) covering ``tk`` models."""
    tc = min(tk, MODEL_BLOCK)
    return tc, -(-tk // tc)


def feature_masks(feats: jax.Array, d: int) -> jax.Array:
    """(..., k) feature ids -> (..., k, d) int32 one-hot column masks."""
    return (feats[..., None] == jnp.arange(d, dtype=jnp.int32)).astype(jnp.int32)


def select_feature(x, mask):
    """x[:, f] as an (n, 1) column, ``mask`` the (1 or n, D) one-hot of f.
    Exact: every other term of the lane sum is zero."""
    return jnp.sum(jnp.where(mask, x, 0.0), axis=1, keepdims=True)


def tree_column(x, masks, thrs, leaves):
    """One oblivious tree for every row of ``x`` (n, D) -> (n, 1) scores.

    ``masks[k]`` is level k's (1 or n, D) feature mask, ``thrs[k]`` its
    (1 or n, 1) threshold, ``leaves`` the (1 or n, 2**depth) leaf table:
    rows of 1 are shared by every lane (a stage-uniform block), rows of n
    are per lane (the streaming lanes).  The index is built MSB-first,
    matching the training layout."""
    idx = jnp.zeros((x.shape[0], 1), jnp.int32)
    for mask, thr in zip(masks, thrs):
        bit = select_feature(x, mask) > thr
        idx = 2 * idx + bit.astype(jnp.int32)
    cols = jax.lax.broadcasted_iota(jnp.int32, (x.shape[0], leaves.shape[1]), 1)
    return jnp.sum(jnp.where(cols == idx, leaves, 0.0), axis=1, keepdims=True)


def _tree_kernel(nv_ref, x_ref, fm_ref, thrs_ref, leaves_ref, out_ref, *,
                 depth: int):
    bn, tc = out_ref.shape
    block_start = pl.program_id(0) * bn

    # live-count block guard: callers that keep live rows compacted at the
    # front of a fixed-capacity buffer (the device executor) pass n_valid;
    # whole row-blocks past the live count skip the tree walk and emit
    # zeros, so per-stage compute tracks survivors even at static shapes.
    @pl.when(block_start >= nv_ref[0])
    def _skip():
        out_ref[...] = jnp.zeros((bn, tc), dtype=out_ref.dtype)

    @pl.when(block_start < nv_ref[0])
    def _eval():
        x, fm = x_ref[...], fm_ref[...]
        thrs, leaves = thrs_ref[...], leaves_ref[...]
        for t in range(tc):
            r = t * depth
            out_ref[:, t:t + 1] = tree_column(
                x,
                [fm[r + k:r + k + 1, :] != 0 for k in range(depth)],
                [thrs[t:t + 1, k:k + 1] for k in range(depth)],
                leaves[t:t + 1, :],
            )


def model_block_call(kernel, x, params, masks, *, block_n, n_valid, dtype,
                     interpret):
    """Shared launcher of the tree and lattice score kernels.

    ``params`` are (tk, ...) per-model arrays and ``masks`` the
    (tk, k, D) feature masks; models are padded to whole blocks of
    ``tc`` (zero params score exactly 0 and are sliced off), rows to
    whole ``block_n`` blocks.  Returns (n, tk) scores."""
    tk = params[0].shape[0]
    tc, n_tc = model_blocks(tk)
    pad_t = n_tc * tc - tk

    def blocked(a):  # (tk, ..., X) -> (n_tc, tc * ..., X)
        a = jnp.pad(a, ((0, pad_t),) + ((0, 0),) * (a.ndim - 1))
        return a.reshape(n_tc, -1, a.shape[-1])

    n, d = x.shape
    n_pad = -n % block_n
    if n_pad:
        x = jnp.pad(x, ((0, n_pad), (0, 0)))
    np_total = x.shape[0]
    nv = jnp.full(
        (1,), np_total if n_valid is None else n_valid, dtype=jnp.int32
    )
    operands = [blocked(masks)] + [blocked(p) for p in params]

    def model_spec(a):
        return pl.BlockSpec((None,) + a.shape[1:], lambda i, j, nv: (j, 0, 0))

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(np_total // block_n, n_tc),
            in_specs=[pl.BlockSpec((block_n, d), lambda i, j, nv: (i, 0))]
            + [model_spec(a) for a in operands],
            out_specs=pl.BlockSpec(
                (None, block_n, tc), lambda i, j, nv: (j, i, 0)
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((n_tc, np_total, tc), dtype),
        interpret=interpret,
    )(nv, x, *operands)
    return out.transpose(1, 0, 2).reshape(np_total, n_tc * tc)[:n, :tk]


def gbt_scores_pallas(
    feats: jax.Array,
    thrs: jax.Array,
    leaves: jax.Array,
    x: jax.Array,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool | None = None,
    t0: int = 0,
    t1: int | None = None,
    rows: jax.Array | None = None,
    n_valid: jax.Array | None = None,
) -> jax.Array:
    """Evaluate trees [t0, t1) on N examples -> (N, t1 - t0) scores.

    Lazy chunked execution hooks (DESIGN.md §4): ``t0``/``t1`` restrict the
    model axis to one cascade chunk — the grid shrinks to that chunk's
    tree blocks and only those trees' parameters are DMA'd; ``rows`` (int
    indices) gathers the surviving examples before blocking, so the kernel
    never touches retired rows.  ``n_valid`` (traced scalar, DESIGN.md §5)
    rides in as a scalar-prefetch argument: row-blocks at or past the live
    count skip the tree walk and emit zeros — the device executor keeps
    survivors compacted at the front of a fixed-capacity buffer, so this
    makes per-stage compute track the live count at static shapes.
    Defaults preserve the eager full-matrix behaviour (all T trees, all
    rows, every block evaluated).  ``interpret=None`` runs compiled on an
    accelerator and interpreted where ``x`` lives on the CPU.
    """
    return _gbt_scores(
        feats, thrs, leaves, x, rows, n_valid, block_n=block_n,
        interpret=resolve_interpret(interpret, x), t0=t0, t1=t1,
    )


@functools.partial(
    jax.jit, static_argnames=("block_n", "interpret", "t0", "t1")
)
def _gbt_scores(feats, thrs, leaves, x, rows, n_valid, *, block_n, interpret,
                t0, t1):
    T, depth = feats.shape
    assert leaves.shape[1] == 1 << depth
    if t1 is None:
        t1 = T
    assert 0 <= t0 < t1 <= T
    if rows is not None:
        x = jnp.take(x, jnp.asarray(rows, dtype=jnp.int32), axis=0)
    dt = leaves.dtype
    feats = feats[t0:t1].astype(jnp.int32)
    return model_block_call(
        functools.partial(_tree_kernel, depth=depth),
        x.astype(dt),
        [thrs[t0:t1].astype(dt), leaves[t0:t1]],
        feature_masks(feats, x.shape[1]),
        block_n=block_n, n_valid=n_valid, dtype=dt, interpret=interpret,
    )
