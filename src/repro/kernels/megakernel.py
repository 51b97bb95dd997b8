"""Fused Pallas stage-step megakernel with quantized param slabs.

One cascade stage step of the device executors used to be three-plus
passes over the survivor buffer: the score kernel writes a (cap, W)
scores intermediate, the chunk/lane decide kernel reads it back, and the
cumsum-prefix compaction makes another full pass — every pass a round
trip through HBM on real hardware (the memory-movement tax ROADMAP item
5 names).  This module fuses the whole step into ONE kernel per row
block:

* **slab select by scalar prefetch.**  The stage index rides in as a
  scalar-prefetch operand (``pltpu.PrefetchScalarGridSpec``), and the
  BlockSpec index_maps of every per-stage operand — the quantized param
  slab, the threshold rows, the int8 scale — select their block by the
  prefetched stage VALUE.  Pallas's pipeline machinery multiple-buffers
  BlockSpec blocks, so the next block's slab DMA overlaps the current
  block's compute (the double-buffered slab prefetch).
* **score + decide in VMEM.**  Inside the kernel the W base models of
  the stage are walked unrolled: variant-specific scoring (matrix
  column select at the stage's ``t0`` offset, and the tree / lattice
  kernels' own ``tree_column`` / ``lattice_column``) feeds straight
  into the shared ``threshold_step`` semantics from ``cascade_kernel``
  — the same single source of truth every other decide uses.  The
  compaction prefix over the surviving lanes is one cumsum outside the
  kernel (Mosaic has no in-kernel cumsum).
* **quantized param slabs.**  ``ParamSlabs`` stores the cascade-ordered
  per-stage parameter stacks at ``f32``, ``bf16`` (the default for
  quantized storage) or ``int8`` (per-slab scale, one f32 scalar per
  stage).  Only ADDITIVE payloads are quantized — tree leaves, lattice
  theta, matrix score entries.  Tree split thresholds and feature ids
  stay exact: quantizing a threshold can flip a discrete leaf choice,
  which makes the score error unbounded; quantizing a leaf bounds it by
  the leaf's own rounding error.  Accumulation is always f32 in-kernel.

**Tolerance oracle.**  Quantization error composes additively along the
cascade walk: if position t's payload error is at most ``eps_position[t]``
then a row that ran ``k`` positions has ``|g_mk - g_oracle| <=
sum(eps_position[:k])`` plus an f32 accumulation term of ``k`` ulps.
``tolerance_bound`` computes that per-row bound and ``check_parity``
enforces the full contract (decisions and exit steps EQUAL, g within the
bound) — exact (bound 0 + ulps) for f32 slabs and for fixtures whose
payloads are already representable on the quantization grid.  The bound
for the lattice variant relies on the corner weights being a convex
combination (inputs in the unit cube); for the matrix variant the
payload is only known at ``prepare`` time, so ``matrix_eps_position``
derives the per-position bound from the prepared operand.

Billing is untouched by any of this: the block-billed counters
(``scores_computed``, stages, traces, critical blocks) are functions of
the exit trajectory and the block geometry only, and the megakernel
runs the identical trajectory at the identical block size — asserted
bit-identical against the multi-kernel path by ``tests/test_megakernel``
and the CI perf gate.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.cascade_kernel import threshold_step
from repro.kernels.interpret import resolve_interpret
from repro.kernels.lattice_kernel import lattice_column
from repro.kernels.tree_kernel import feature_masks, select_feature, tree_column

__all__ = [
    "ParamSlabs",
    "build_matrix_slabs",
    "build_tree_slabs",
    "build_lattice_slabs",
    "matrix_eps_position",
    "tolerance_bound",
    "check_parity",
    "gather_lane_slabs",
    "mega_stage_pallas",
    "mega_lane_pallas",
    "QUANTS",
]

QUANTS = ("f32", "bf16", "int8")

F32_EPS = float(np.finfo(np.float32).eps)


# ---------------------------------------------------------------------------
# quantized slab storage
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ParamSlabs:
    """Cascade-ordered, stage-stacked, quantized parameter slabs.

    ``data`` maps slab names to (S, W, ...) arrays — one uniform-width
    slab per stage, zero-padded on the model axis (padded models score
    exactly 0.0, which the ±inf threshold padding keeps inert, so no
    column-validity mask is needed in-kernel).  ``scale`` is the (S, 1)
    f32 per-slab dequantization scale (ones unless ``quant == "int8"``).
    ``eps_position`` is the (T,) per-cascade-position max-abs payload
    quantization error feeding ``tolerance_bound``.  ``x_dtype`` is the
    storage dtype the executor casts the PREPARED operand to (matrix
    variant only — its payload is the prepared score matrix itself;
    None = leave the operand alone).
    """

    variant: str  # "matrix" | "tree" | "lattice"
    quant: str  # "f32" | "bf16" | "int8"
    data: dict
    scale: jax.Array  # (S, 1) float32
    eps_position: np.ndarray  # (T,) float64
    W: int
    S: int
    x_dtype: Any = None


def _quantize_slab(vals: np.ndarray, quant: str):
    """Quantize one stage's (w, ...) payload slab with a single scale.

    Returns (stored array, scale, per-model max-abs error).  The error is
    computed EXACTLY (f64 round trip through the storage grid) at build
    time — it is the tolerance oracle's raw material, not an estimate.
    """
    v64 = np.asarray(vals, np.float64)
    v32 = v64.astype(np.float32)
    if quant == "f32":
        q, scale, deq = v32, 1.0, v32.astype(np.float64)
    elif quant == "bf16":
        q = jnp.asarray(v32, jnp.bfloat16)
        deq = np.asarray(q, np.float32).astype(np.float64)
        scale = 1.0
    elif quant == "int8":
        m = float(np.max(np.abs(v32))) if v32.size else 0.0
        scale = m / 127.0 if m > 0.0 else 1.0
        q = np.clip(np.round(v32 / scale), -127, 127).astype(np.int8)
        deq = q.astype(np.float64) * scale
    else:
        raise ValueError(f"quant must be one of {QUANTS}, got {quant!r}")
    err = np.abs(v64 - deq)
    eps = (
        err.reshape(v64.shape[0], -1).max(axis=1)
        if v64.size
        else np.zeros(v64.shape[0])
    )
    return q, scale, eps


def _stack_stages(dplan, per_stage_payload, quant, aux: dict | None = None):
    """Shared slab assembly: quantize each stage's payload with its own
    scale, stack to (S, W, ...), and spread the per-model errors back to
    cascade positions.  ``aux`` arrays (exact params like tree
    thresholds) are stacked unquantized."""
    S, W, T = dplan.S, dplan.W, dplan.plan.T
    payloads, scales = [], np.ones(S, np.float32)
    eps_position = np.zeros(T, np.float64)
    for s, (t0, t1) in enumerate(dplan.plan.stages):
        w = t1 - t0
        raw = per_stage_payload(t0, t1)  # (w, ...)
        q, scale, eps = _quantize_slab(raw, quant)
        pad = [(0, W - w)] + [(0, 0)] * (raw.ndim - 1)
        payloads.append(np.pad(np.asarray(q), pad))
        scales[s] = scale
        eps_position[t0:t1] = eps
    data = {"payload": jnp.asarray(np.stack(payloads))}
    for name, arr in (aux or {}).items():
        stacked = []
        for s, (t0, t1) in enumerate(dplan.plan.stages):
            sl = np.asarray(arr[t0:t1])
            pad = [(0, W - sl.shape[0])] + [(0, 0)] * (sl.ndim - 1)
            stacked.append(np.pad(sl, pad))
        data[name] = jnp.asarray(np.stack(stacked))
    return data, jnp.asarray(scales.reshape(S, 1)), eps_position


def build_matrix_slabs(dplan, quant: str = "bf16") -> ParamSlabs:
    """Matrix-variant slabs: the payload is the PREPARED (n, T_pad) score
    matrix itself, so there is nothing to stack — the slab record just
    carries the storage dtype the executor casts the operand to.  int8 is
    not supported here (the payload only exists at prepare time, after
    the per-slab scales would have to be frozen); use bf16."""
    if quant not in QUANTS:
        raise ValueError(f"quant must be one of {QUANTS}, got {quant!r}")
    if quant == "int8":
        raise ValueError(
            "matrix slabs support f32/bf16 only: the payload is the "
            "prepared score matrix, built after per-slab int8 scales "
            "would need to be frozen"
        )
    S = dplan.S
    return ParamSlabs(
        variant="matrix",
        quant=quant,
        # tree/lattice slabs are zero-padded past each stage's true width,
        # but the matrix "slab" is the live operand — column t0+j of a
        # narrow stage is the NEXT stage's real score.  The kernel masks
        # with the true width instead.
        data={"widths": jnp.asarray(dplan.widths.reshape(S, 1), jnp.int32)},
        scale=jnp.ones((S, 1), jnp.float32),
        # operand-dependent; derive the real bound from the prepared
        # operand with matrix_eps_position (zeros == exact, the f32 case)
        eps_position=np.zeros(dplan.plan.T, np.float64),
        W=dplan.W,
        S=S,
        x_dtype=jnp.float32 if quant == "f32" else jnp.bfloat16,
    )


def build_tree_slabs(
    dplan, feats_ordered, thrs_ordered, leaves_ordered, quant: str = "bf16"
) -> ParamSlabs:
    """Oblivious-tree slabs: LEAVES are the quantized payload; split
    thresholds and feature ids stay exact (quantizing a threshold flips
    discrete leaf selection — unbounded error; quantizing a leaf bounds
    the score error by the leaf's own rounding error)."""
    leaves = np.asarray(leaves_ordered)
    data, scale, eps_position = _stack_stages(
        dplan,
        lambda t0, t1: leaves[t0:t1],
        quant,
        aux={
            "feats": np.asarray(feats_ordered, np.int32),
            "thrs": np.asarray(thrs_ordered, np.float32),
        },
    )
    return ParamSlabs(
        variant="tree",
        quant=quant,
        data=data,
        scale=scale,
        eps_position=eps_position,
        W=dplan.W,
        S=dplan.S,
    )


def build_lattice_slabs(
    dplan, theta_ordered, feats_ordered, quant: str = "bf16"
) -> ParamSlabs:
    """Lattice slabs: THETA is the quantized payload; feature ids stay
    exact.  The corner weights are a convex combination for inputs in
    the unit cube, so the per-model score error is bounded by the
    per-model max-abs theta error — the eps_position entries."""
    theta = np.asarray(theta_ordered)
    data, scale, eps_position = _stack_stages(
        dplan,
        lambda t0, t1: theta[t0:t1],
        quant,
        aux={"feats": np.asarray(feats_ordered, np.int32)},
    )
    return ParamSlabs(
        variant="lattice",
        quant=quant,
        data=data,
        scale=scale,
        eps_position=eps_position,
        W=dplan.W,
        S=dplan.S,
    )


def matrix_eps_position(ordered: np.ndarray, quant: str) -> np.ndarray:
    """(T,) per-position payload error for the matrix variant, derived
    from the actual cascade-ordered score matrix the executor will cast
    to the storage dtype."""
    v64 = np.asarray(ordered, np.float64)
    v32 = v64.astype(np.float32)
    if quant == "f32":
        deq = v32.astype(np.float64)
    elif quant == "bf16":
        deq = np.asarray(
            jnp.asarray(v32, jnp.bfloat16), np.float32
        ).astype(np.float64)
    else:
        raise ValueError(f"matrix slabs support f32/bf16 only, got {quant!r}")
    return np.abs(v64 - deq).max(axis=0)


def gather_lane_slabs(slabs: ParamSlabs, stage: jax.Array) -> dict:
    """Per-LANE slab gather for the streaming (mixed-stage) kernel: each
    lane pulls ITS stage's slab row from the stacked QUANTIZED arrays —
    the gathered bytes shrink with the storage dtype.  Returns the
    per-lane dict plus the per-lane (cap, 1) scale."""
    out = {k: jnp.take(v, stage, axis=0) for k, v in slabs.data.items()}
    out["scale"] = jnp.take(slabs.scale, stage, axis=0)
    return out


# ---------------------------------------------------------------------------
# tolerance oracle
# ---------------------------------------------------------------------------


def tolerance_bound(
    eps_position, exit_step, g_scale: float = 1.0
) -> np.ndarray:
    """Per-row |g_mk - g_oracle| bound after each row's own walk.

    ``exit_step`` is the 1-based count of cascade positions the row
    executed (an ``ExecutorResult.exit_step``; never-exited rows report
    T).  The bound is the cumulative per-position payload quantization
    error over those positions plus a documented f32-accumulation term
    of one ulp (relative to ``g_scale``, a magnitude scale for the
    partial sums — default 1.0) per executed position.  Zero everywhere
    (up to the ulp term) for f32 slabs and for payloads already
    representable on the quantization grid.
    """
    eps = np.asarray(eps_position, np.float64)
    steps = np.clip(np.asarray(exit_step, np.int64), 0, eps.shape[0])
    cum = np.concatenate([[0.0], np.cumsum(eps)])
    return cum[steps] + steps * F32_EPS * float(g_scale)


def check_parity(oracle, result, eps_position, g_scale: float = 1.0) -> dict:
    """Enforce the megakernel parity contract against an oracle run.

    ``oracle``/``result`` are duck-typed results (``decisions``,
    ``exit_step``, ``g_final`` — ``ExecutorResult`` and ``StreamResult``
    both qualify).  Decisions and exit steps must be EQUAL (the fixtures
    this certifies keep every threshold margin wider than the bound);
    ``g_final`` must agree within ``tolerance_bound``.  Raises
    AssertionError naming the first violating rows; returns a small
    report dict on success.
    """
    dec_a = np.asarray(oracle.decisions).astype(bool)
    dec_b = np.asarray(result.decisions).astype(bool)
    ex_a = np.asarray(oracle.exit_step, np.int64)
    ex_b = np.asarray(result.exit_step, np.int64)
    if dec_a.shape != dec_b.shape:
        raise AssertionError(
            f"result shape mismatch: {dec_a.shape} vs {dec_b.shape}"
        )
    if not np.array_equal(ex_a, ex_b):
        rows = np.flatnonzero(ex_a != ex_b)[:8]
        raise AssertionError(
            f"exit_step mismatch on {rows.size}+ rows (first {rows.tolist()}): "
            "the quantization error crossed a threshold margin — this "
            "fixture cannot be certified by the tolerance oracle"
        )
    if not np.array_equal(dec_a, dec_b):
        rows = np.flatnonzero(dec_a != dec_b)[:8]
        raise AssertionError(
            f"decision mismatch on rows {rows.tolist()}"
        )
    g_a = np.asarray(oracle.g_final, np.float64)
    g_b = np.asarray(result.g_final, np.float64)
    bound = tolerance_bound(eps_position, ex_a, g_scale)
    err = np.abs(g_a - g_b)
    bad = err > bound
    if bad.any():
        rows = np.flatnonzero(bad)[:8]
        raise AssertionError(
            f"g_final outside tolerance on rows {rows.tolist()}: "
            f"err {err[rows].tolist()} > bound {bound[rows].tolist()}"
        )
    return {
        "rows": int(err.size),
        "max_err": float(err.max(initial=0.0)),
        "max_bound": float(bound.max(initial=0.0)),
        "exact": bool((err == 0.0).all()),
    }


# ---------------------------------------------------------------------------
# in-kernel scoring (shared by the batch and lane kernels)
# ---------------------------------------------------------------------------


def _stage_scorer(variant, quant, x, params, scale, t0, *, lane_mode,
                  levels, width):
    """``score_j(j) -> (bn, 1)`` f32 scores of the stage's model j.

    Batch mode: every lane of the block runs the same stage, so params
    are stage blocks with one row per model — (W * levels, D) one-hot
    feature masks, (W, levels) thresholds, (W, width) payload — and
    ``scale`` is the stage's (1, 1) dequantization scale.  Lane mode:
    each lane carries its own stage's params, flattened to (bn, W *
    levels) feature ids / thresholds and a (bn, W * width) payload, with
    a (bn, 1) scale.  ``levels`` is the tree depth or the lattice's
    feature count, ``width`` the leaf-table or theta width.  Scoring goes
    through ``tree_column`` / ``lattice_column`` — the functions
    ``gbt_scores_pallas`` / ``lattice_scores_pallas`` use — so the fused
    and multi-kernel paths compute every score with the same operations.
    """
    if variant == "matrix":
        (widths,) = params
        x = x.astype(jnp.float32)
        if lane_mode:  # x is each lane's own (bn, W) stage slab

            def score_j(j):
                return jnp.where(j < widths, x[:, j:j + 1], 0.0)
        else:  # x is the whole (bn, T_pad) operand: column t0 + j
            cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)

            def score_j(j):
                col = select_feature(x, cols == t0 + j)
                return jnp.where(j < widths, col, 0.0)

        return score_j

    feats, payload = params[0], params[-1]
    if lane_mode:
        cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)

        def masks(j):
            r = j * levels
            return [cols == feats[:, r + k:r + k + 1] for k in range(levels)]

        def row(a, j, w):
            return a[:, j * w:(j + 1) * w]
    else:

        def masks(j):
            r = j * levels
            return [feats[r + k:r + k + 1, :] != 0 for k in range(levels)]

        def row(a, j, w):
            return a[j:j + 1, :]

    def payload_row(j):
        p = row(payload, j, width).astype(jnp.float32)
        return p * scale if quant == "int8" else p

    if variant == "tree":
        thrs = params[1]

        def score_j(j):
            th = row(thrs, j, levels)
            return tree_column(
                x, masks(j), [th[:, k:k + 1] for k in range(levels)],
                payload_row(j),
            )
    else:  # lattice

        def score_j(j):
            return lattice_column(x, masks(j), payload_row(j))

    return score_j


def _walk(score_j, ep, en, g0, nv, block_start, W):
    """The fused inner step: unrolled threshold walk over the stage's W
    models (``threshold_step`` semantics, relative 1-based exits).
    ``ep``/``en`` are (1, W) stage rows or (bn, W) per-lane rows."""
    bn = g0.shape[0]
    lane = block_start + jax.lax.broadcasted_iota(jnp.int32, (bn, 1), 0)
    g = g0.astype(jnp.float32)
    active = lane < nv
    dec = jnp.zeros((bn, 1), jnp.bool_)
    ex = jnp.zeros((bn, 1), jnp.int32)
    for j in range(W):
        g, active, dec, ex = threshold_step(
            g, active, dec, ex, score_j(j), ep[:, j:j + 1], en[:, j:j + 1],
            j + 1,
        )
    return g, active, dec, ex


def _mega_kernel(
    *refs, variant: str, quant: str, W: int, lane_mode: bool, levels: int,
    width: int,
):
    """One survivor block, one stage step: score the W models, then
    threshold-decide.  Blocks past the live count write inert outputs and
    compute nothing — the same block-guard billing semantics as the
    multi-kernel path's score kernels.

    Batch mode (stage-uniform blocks) takes the scalar-prefetched stage,
    its first cascade position ``t0`` and the live count, and selects
    every per-stage operand by the stage VALUE in its BlockSpec index
    map, so Pallas's pipeline prefetches the next stage slab.  Lane mode
    (mixed-stage blocks, the streaming refill) takes only the live count:
    every per-stage quantity arrives pre-gathered per lane."""
    if lane_mode:
        (nv_ref, g0_ref, x_ref, *rest) = refs
        t0 = None
    else:
        (_, t0_ref, nv_ref, g0_ref, x_ref, *rest) = refs
        t0 = t0_ref[0]
    *param_refs, scale_ref, ep_ref, en_ref, g_ref, act_ref, dec_ref, ex_ref = rest
    bn = g0_ref.shape[0]
    block_start = pl.program_id(0) * bn
    nv = nv_ref[0]

    def write(g, active, dec, ex):
        g_ref[...] = g
        act_ref[...] = active.astype(jnp.int32)
        dec_ref[...] = dec.astype(jnp.int32)
        ex_ref[...] = ex

    @pl.when(block_start >= nv)
    def _skip():
        zero = jnp.zeros((bn, 1), jnp.int32)
        write(g0_ref[...].astype(jnp.float32), zero, zero, zero)

    @pl.when(block_start < nv)
    def _compute():
        score_j = _stage_scorer(
            variant, quant, x_ref[...], [r[...] for r in param_refs],
            scale_ref[...], t0, lane_mode=lane_mode, levels=levels,
            width=width,
        )
        write(*_walk(
            score_j, ep_ref[...], en_ref[...], g0_ref[...], nv, block_start, W
        ))


def _levels_width(slabs: ParamSlabs) -> tuple[int, int]:
    """(levels, payload width) of a tree/lattice slab; (0, 0) for matrix."""
    if slabs.variant == "matrix":
        return 0, 0
    return int(slabs.data["feats"].shape[-1]), int(slabs.data["payload"].shape[-1])


def _launch(slabs, scalars, operands, specs, cap, bn, *, lane_mode,
            interpret):
    """Run the fused kernel over ``cap`` lanes in blocks of ``bn`` and
    return its (g, active, decided_pos, exit_rel) lanes, each (cap,)."""
    levels, width = _levels_width(slabs)
    capp = operands[0].shape[0]
    out_spec = pl.BlockSpec((bn, 1), lambda i, *_: (i, 0))
    outs = pl.pallas_call(
        functools.partial(
            _mega_kernel, variant=slabs.variant, quant=slabs.quant,
            W=slabs.W, lane_mode=lane_mode, levels=levels, width=width,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(capp // bn,),
            in_specs=specs,
            out_specs=[out_spec] * 4,
        ),
        out_shape=[jax.ShapeDtypeStruct((capp, 1), jnp.float32)]
        + [jax.ShapeDtypeStruct((capp, 1), jnp.int32)] * 3,
        interpret=resolve_interpret(interpret, operands[0]),
    )(*scalars, *operands)
    return tuple(o[:cap, 0] for o in outs)


def _pack(keep, cap):
    """Cumsum-prefix compaction of the surviving lanes: each kept lane's
    front-packed destination, or ``cap`` (out of bounds, dropped)."""
    pos = jnp.cumsum(keep.astype(jnp.int32)) - 1
    return jnp.where(keep, pos, cap), keep.sum(dtype=jnp.int32)


def _pad_rows(a, pad):
    return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)) if pad else a


# ---------------------------------------------------------------------------
# the batch megakernel (stage-uniform blocks)
# ---------------------------------------------------------------------------


def mega_stage_pallas(
    slabs: ParamSlabs,
    x: jax.Array,
    g0: jax.Array,
    stage: jax.Array,
    t0: jax.Array,
    n_valid: jax.Array,
    eps_pos: jax.Array,
    eps_neg: jax.Array,
    *,
    block_n: int,
    interpret: bool | None = None,
):
    """One fused cascade stage step over a survivor buffer.

    ``x`` is the gathered operand for the buffer's rows — the (cap,
    T_pad) prepared score matrix for the matrix variant (already cast to
    the slab storage dtype), the (cap, d) feature rows otherwise.
    ``stage``/``t0``/``n_valid`` are traced scalars; ``eps_pos``/
    ``eps_neg`` the full (S, W) threshold tables (the kernel selects the
    stage's row by scalar prefetch, same as the param slab).

    Returns ``(g, active i32, decided_pos i32, exit_rel i32, pack, n_keep)``
    each (cap,): exits are RELATIVE 1-based (caller rebases by t0), and
    ``pack`` holds each surviving lane's front-packed destination (or
    ``cap`` — out of bounds, dropped) ready for the executor's scatter.
    """
    cap = g0.shape[0]
    bn = min(block_n, cap) if cap else block_n
    pad = -cap % bn
    capp = cap + pad
    S, W = slabs.S, slabs.W
    i32 = jnp.int32
    scalars = (
        jnp.full((1,), stage, i32),
        jnp.full((1,), t0, i32),
        jnp.full((1,), jnp.minimum(jnp.asarray(n_valid, i32), i32(cap))),
    )

    def row(a):  # per-row-block operands
        return pl.BlockSpec((bn, a.shape[1]), lambda i, s, t0, nv: (i, 0))

    def slab(a):  # per-stage operands, selected by the prefetched stage
        return pl.BlockSpec((None,) + a.shape[1:], lambda i, s, t0, nv: (s[0], 0, 0))

    x = _pad_rows(x, pad)
    operands = [_pad_rows(g0, pad).reshape(capp, 1), x]
    if slabs.variant == "matrix":
        params = [slabs.data["widths"].reshape(S, 1, 1)]
    else:
        f = slabs.data["feats"]
        params = [feature_masks(f, x.shape[1]).reshape(S, -1, x.shape[1])]
        if slabs.variant == "tree":
            params.append(slabs.data["thrs"])
        params.append(slabs.data["payload"])
    params += [
        slabs.scale.reshape(S, 1, 1),
        jnp.asarray(eps_pos).reshape(S, 1, W),
        jnp.asarray(eps_neg).reshape(S, 1, W),
    ]
    g, act, dec, ex = _launch(
        slabs, scalars, operands + params,
        [row(a) for a in operands] + [slab(a) for a in params],
        cap, bn, lane_mode=False, interpret=interpret,
    )
    keep = act.astype(bool)  # batch keep == still-active
    return (g, act, dec, ex) + _pack(keep, cap)


# ---------------------------------------------------------------------------
# the lane megakernel (mixed-stage blocks, streaming admission)
# ---------------------------------------------------------------------------


def mega_lane_pallas(
    slabs: ParamSlabs,
    x: jax.Array,
    lane_data: dict,
    g0: jax.Array,
    eps_pos_lane: jax.Array,
    eps_neg_lane: jax.Array,
    stop: jax.Array,
    n_valid: jax.Array,
    *,
    block_n: int,
    interpret: bool | None = None,
):
    """One fused MIXED-stage step for the streaming executors.

    ``x``: per-lane pre-sliced (cap, W) scores for the matrix variant
    (storage dtype), the (cap, d) feature rows otherwise.  ``lane_data``:
    ``gather_lane_slabs`` output — per-lane (cap, W, ...) quantized
    slabs plus the (cap, 1) scale (for matrix: the per-lane (cap, 1)
    true stage widths, used to mask overhang columns).  ``eps_pos_lane``/``eps_neg_lane``: the
    (cap, W) per-lane threshold rows.  ``stop``: (cap,) bool/int, 1 on a
    lane running its LAST stage (excluded from the survivor prefix: it
    retires this step whether it exits or runs out).

    Same return contract as ``mega_stage_pallas``.
    """
    cap = g0.shape[0]
    bn = min(block_n, cap) if cap else block_n
    pad = -cap % bn
    capp = cap + pad
    i32 = jnp.int32
    scalars = (
        jnp.full((1,), jnp.minimum(jnp.asarray(n_valid, i32), i32(cap))),
    )
    scale = lane_data.get(
        "scale", jnp.take(slabs.scale, jnp.zeros(cap, i32), axis=0)
    )
    if slabs.variant == "matrix":
        params = [lane_data["widths"]]
    else:
        params = [lane_data["feats"]]
        if slabs.variant == "tree":
            params.append(lane_data["thrs"])
        params.append(lane_data["payload"])
    operands = [
        _pad_rows(a.reshape(cap, -1), pad)
        for a in [g0, x, *params, scale, eps_pos_lane, eps_neg_lane]
    ]
    g, act, dec, ex = _launch(
        slabs, scalars, operands,
        [pl.BlockSpec((bn, a.shape[1]), lambda i, nv: (i, 0)) for a in operands],
        cap, bn, lane_mode=True, interpret=interpret,
    )
    # survivors advance a stage; lanes on their last stage retire
    keep = act.astype(bool) & (jnp.asarray(stop).astype(i32) == 0)
    return (g, act, dec, ex) + _pack(keep, cap)
