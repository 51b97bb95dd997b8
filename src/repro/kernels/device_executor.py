"""On-device cascade executor: the whole stage loop as ONE jit'd program.

``core.executor.ChunkedExecutor`` made the paper's early-exit savings real
in score-count terms, but its stage loop lives on the host: every stage
pays a device->host sync (the decide outputs are converted to numpy), a
host-side survivor compaction (``nonzero`` + ``take``) and a fresh gather
upload for the next stage's producer call.  Under heavy traffic that
orchestration — not scoring — dominates wall-clock latency, the failure
mode the query-level interleaved-traversal literature warns about
(Lucchese et al. 2020; Busolin et al. 2021 — PAPERS.md).

``DeviceExecutor`` runs the entire ``CascadePlan`` inside one
``jax.jit``-compiled ``lax.while_loop`` over stages, with zero per-stage
host round-trips (DESIGN.md §5):

* **Fixed-capacity survivor buffers.**  The active row-index set lives in
  a ``(cap,)`` buffer (``cap`` = batch padded to ``block_n``), survivors
  packed at the front and the live count carried as data, not shape — so
  every stage of every batch runs the SAME traced program: exactly one
  trace per (N, T, chunk_t), asserted by ``DeviceExecutor.traces``.
* **On-device compaction.**  The host path's ``nonzero`` + ``take`` is
  replaced by a cumsum-prefix scatter: ``pos = cumsum(keep) - 1`` ranks
  the survivors (stable — relative order preserved, same guarantee the
  host executor gives), and a masked scatter packs them to the front.
  Retired lanes scatter to index ``cap`` which is out of bounds and
  dropped (``mode="drop"``).
* **Fused stage body.**  Score production (tree/lattice Pallas kernels on
  a ``dynamic_slice``'d slab of cascade-ordered params + row gather) and
  the ``cascade_chunk_pallas`` decide run back-to-back inside the loop
  body.  Stage start ``t0`` is a traced scalar; the decide kernel runs at
  relative positions and the exit steps are rebased outside it.
* **Early exit.**  The ``while_loop`` condition is
  ``(s < S) & (n_active > 0)`` — the program quits as soon as every row
  has exited, the whole-batch analogue of the paper's per-example quit.

Stages are uniformized to the plan's maximum width ``W`` (the lead stage
and the final partial stage are narrower): padded columns carry
wide-open thresholds (+/-inf) and zeroed scores, so they can never
change a partial sum or trigger an exit.  Semantics are therefore
bit-identical to ``core.qwyc.evaluate_cascade`` — asserted per backend
and mode in ``tests/test_executor.py`` / ``tests/test_serving.py``.

**Streaming admission (DESIGN.md §8).**  ``run`` drains one batch: every
lane starts at stage 0 together, and as rows exit the tail of the
cascade runs with the survivor buffers mostly empty — exactly the
per-query skew the query-level early-exit literature measures (Lucchese
et al. 2020; Busolin et al. 2021).  ``run_stream`` closes that gap with
continuous batching: pending rows wait in a device-resident **admission
ring** (ids + arrival steps, arrival order), and after each stage's
cumsum-prefix compaction the open slots at the back of the front-packed
buffers are refilled from the ring.  Admitted rows enter at cascade
stage 0 while veterans continue mid-cascade, so the single loop counter
is replaced by a **per-lane stage index**: the score slab, the threshold
slab and the column-validity mask are gathered per lane from the
``DevicePlan`` stage tables, and the decide runs through
``cascade_lane_pallas`` (per-row thresholds, relative exit steps rebased
by each lane's own stage start).  The same +/-inf threshold padding that
makes uniformized stages inert makes mixed-stage blocks safe, so each
row's decisions and exit steps stay bit-identical to the host oracle —
asserted in ``tests/test_streaming.py``.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro import tracing
from repro.core.executor import CascadePlan, ChunkStat, ExecutorResult
from repro.kernels import megakernel as mk
from repro.kernels.cascade_kernel import (
    cascade_chunk_pallas,
    cascade_group_pallas,
    cascade_lane_pallas,
)
from repro.kernels.interpret import resolve_interpret
from repro.kernels.lattice_kernel import halving_sum, lattice_scores_pallas
from repro.kernels.tree_kernel import gbt_scores_pallas
from repro.testing import faults


class WaveFailure(RuntimeError):
    """A device wave (one ``run``/``run_stream`` launch) failed at
    runtime.  Both on-device executors normalize launch-time failures —
    injected faults and real XLA runtime errors alike — to this one
    type, so the degradation ladder has a single retryable signal.
    Shape/argument errors (``ValueError``/``TypeError``) pass through
    untouched: those are caller bugs, not transient faults."""


class DeviceProgramError(Exception):
    """The compiler refused a device program: lowering or compiling it
    failed.  Deliberately NOT a ``RuntimeError``: the degradation ladder
    retries and falls only on runtime faults, so a program that cannot
    be built for its device stops the caller instead of being answered
    quietly by a lower rung."""


def _signature(a):
    if hasattr(a, "shape") and hasattr(a, "dtype"):
        return (tuple(a.shape), str(a.dtype))
    return type(a).__name__


def compile_program(compiled: set, jitted, *args, static: int = 0) -> None:
    """Lower and compile ``jitted`` for ``args`` OUTSIDE the wave fault
    contract, once per argument signature (the first ``static`` args are
    static and keyed by value).  Errors propagate: a ``ValueError`` /
    ``TypeError`` as it is, anything else as ``DeviceProgramError``
    chained to it.  The launch that follows inside ``launch_wave`` reuses
    the executable jit cached here, so only runtime faults reach it."""
    key = tuple(
        a if i < static else _signature(a) for i, a in enumerate(args)
    )
    if key in compiled:
        return
    try:
        with TraceAnnotation(tracing.COMPILE):
            jitted.lower(*args).compile()
    except (ValueError, TypeError):
        raise
    except Exception as e:
        raise DeviceProgramError(
            f"device program failed to compile: {type(e).__name__}: {e}"
        ) from e
    compiled.add(key)


def launch_wave(executor_name: str, fn):
    """Run one device-program launch under the wave fault contract."""
    try:
        faults.on_wave(executor_name)
        return fn()
    except faults.FaultInjected as e:
        raise WaveFailure(str(e)) from e
    except (ValueError, TypeError):
        raise
    except Exception as e:  # XLA runtime failures (device loss, OOM, ...)
        raise WaveFailure(
            f"{executor_name} wave failed: {type(e).__name__}: {e}"
        ) from e


def pad_rows(x, cap: int):
    """``x`` with zero rows appended up to ``cap`` rows.  A host array is
    padded on the host (``numpy``), so it crosses to the device once at
    full capacity and no device program is built per row count; a device
    array is padded on the device."""
    if not isinstance(x, jax.Array):
        x = np.asarray(x)
    if x.shape[0] >= cap:
        return x
    pad = ((0, cap - x.shape[0]),) + ((0, 0),) * (x.ndim - 1)
    return jnp.pad(x, pad) if isinstance(x, jax.Array) else np.pad(x, pad)


def check_batch_finite(batch, n: int) -> None:
    """Reject non-finite rows before they reach a device program.

    The serving quarantine guard normally catches these at admission;
    this executor-level check (``check_finite=True``) is the belt for
    callers that feed executors directly.  Raises ``ValueError`` (not
    retryable — a poisoned batch won't heal with backoff) naming the
    offending rows.
    """
    arr = np.asarray(batch)[:n]
    if not np.issubdtype(arr.dtype, np.floating):
        return
    finite = np.isfinite(arr)
    bad = ~(finite if arr.ndim == 1 else finite.all(axis=tuple(range(1, arr.ndim))))
    if bad.any():
        rows = np.flatnonzero(bad)
        head = ", ".join(map(str, rows[:8]))
        more = f", ... ({rows.size} total)" if rows.size > 8 else ""
        raise ValueError(
            f"non-finite values in batch rows [{head}{more}]; quarantine "
            "poisoned rows before submission (see DESIGN.md §10)"
        )

__all__ = [
    "DeviceProgramError",
    "DevicePlan",
    "BoundScorer",
    "StreamResult",
    "GroupedResult",
    "GroupedStreamResult",
    "DeviceExecutor",
    "group_topk_rows",
    "lane_block",
    "matrix_stage_scorer",
    "pad_rows",
    "tree_stage_scorer",
    "lattice_stage_scorer",
    "stream_occupancy",
]

DEFAULT_BLOCK_N = 64


@dataclasses.dataclass(frozen=True)
class DevicePlan:
    """A ``CascadePlan`` lowered to static-shape stage arrays.

    All stages are padded to the maximum stage width ``W`` so the loop
    body is shape-uniform; padded columns get wide-open thresholds and a
    False ``col_valid`` (their scores are zeroed), so they are inert.
    """

    plan: CascadePlan
    stage_t0: np.ndarray  # (S,) int32 — first cascade position per stage
    widths: np.ndarray  # (S,) int32 — true (unpadded) stage widths
    eps_pos: np.ndarray  # (S, W) float32, +inf on padded columns
    eps_neg: np.ndarray  # (S, W) float32, -inf on padded columns
    col_valid: np.ndarray  # (S, W) bool
    W: int  # uniform stage width
    T_pad: int  # model-axis pad target: every [t0, t0 + W) slab is in range
    # param-slab storage dtype for the fused megakernel path ("f32" |
    # "bf16" | "int8"): the default scorer factories build their
    # ParamSlabs at this quant.  f32 is the default because it keeps the
    # megakernel bit-identical to the multi-kernel path (and hence
    # auto-selected — see DeviceExecutor); bf16/int8 are the opt-in
    # quantized storage modes, certified by the tolerance oracle.
    quant: str = "f32"

    @property
    def S(self) -> int:
        return int(self.stage_t0.shape[0])

    @classmethod
    def from_plan(cls, plan: CascadePlan, quant: str = "f32") -> "DevicePlan":
        stages = plan.stages
        S = len(stages)
        W = max(t1 - t0 for t0, t1 in stages)
        stage_t0 = np.array([t0 for t0, _ in stages], dtype=np.int32)
        widths = np.array([t1 - t0 for t0, t1 in stages], dtype=np.int32)
        eps_pos = np.full((S, W), np.inf, dtype=np.float32)
        eps_neg = np.full((S, W), -np.inf, dtype=np.float32)
        col_valid = np.zeros((S, W), dtype=bool)
        for s, (t0, t1) in enumerate(stages):
            w = t1 - t0
            eps_pos[s, :w] = plan.eps_pos[t0:t1].astype(np.float32)
            eps_neg[s, :w] = plan.eps_neg[t0:t1].astype(np.float32)
            col_valid[s, :w] = True
        if quant not in mk.QUANTS:
            raise ValueError(f"quant must be one of {mk.QUANTS}, got {quant!r}")
        return cls(
            plan=plan,
            stage_t0=stage_t0,
            widths=widths,
            eps_pos=eps_pos,
            eps_neg=eps_neg,
            col_valid=col_valid,
            W=W,
            T_pad=int(stage_t0.max()) + W,
            quant=quant,
        )


@dataclasses.dataclass(frozen=True)
class BoundScorer:
    """The plan-bound, traceable form of the ``repro.api`` ``StageScorer``
    protocol — what the executors actually call.

    The one protocol method, shared by ChunkedExecutor (via
    ``repro.api.scorers.host_producer``), DeviceExecutor,
    ShardedDeviceExecutor and the streaming lanes (DESIGN.md §11):

        ``stage(state, t0, t1, rows, x, n_valid) -> (scores, state)``

    ``state`` is a per-row pytree matching ``state_spec`` with a leading
    capacity axis; the executors carry it through the survivor buffers and
    repack it with the SAME cumsum-prefix compaction as the row ids.  A
    row's state at its FIRST stage (``t0 == 0``) is undefined — stateful
    scorers must initialize it from the prepared operand there (streaming
    admission drops rookies into recycled lanes mid-loop).  Stateless
    scorers declare ``state_spec = ()`` and the state threading compiles
    away to the exact pre-state program (billing stays byte-identical).

    Stateless implementations provide ``fn``/``lane_fn`` and get
    ``stage``/``lane_stage`` for free; stateful ones provide
    ``stage_fn``/``lane_stage_fn`` directly:

    ``fn(x, rows, t0, n_valid) -> (cap, W)``: scores of cascade positions
    [t0, t0 + W) for the given (fixed-capacity, front-packed) row buffer.
    ``t0`` and ``n_valid`` are TRACED scalars — implementations
    ``dynamic_slice`` their cascade-ordered parameter slabs rather than
    specializing on ``t0``, and may use ``n_valid`` (live rows are
    compacted at the front) to skip whole row-blocks past the live count
    (the Pallas kernels' block guard).
    ``prepare(batch) -> x``: one host-side call per batch producing the
    operand ``stage`` closes the loop over (params stay baked into the
    trace; only ``x`` streams through).
    ``block_n``: the scorer's OWN kernel row-block size — the granularity
    its block guard really computes at, which the executor uses for
    ``scores_computed`` billing (None = exact producer; billed at the
    executor's block size).  A stateful scorer's ``stage_fn`` loops over
    blocks of ``block_n`` lanes where they tile the lane buffer and runs
    one block of the whole buffer where they do not (``lane_block``);
    ``row_block`` gives the executors that granularity.
    ``lane_fn`` / ``lane_stage_fn``: the per-lane-stage variant for the
    streaming executors — same signature with ``t0_lane`` a (cap,) vector
    of per-lane cascade starts (admission refill mixes stage-0 rookies
    with mid-cascade veterans in one buffer, DESIGN.md §8).  Scorers
    without one cannot serve ``run_stream`` on the multi-kernel fallback
    path.
    ``slabs`` (optional): the scorer's params as quantized, stage-stacked
    ``megakernel.ParamSlabs`` — present on the stateless built-ins and
    the ticket into the fused stage-step megakernel (DESIGN.md §9);
    ``fn``/``lane_fn`` stay as the multi-kernel fallback and parity
    oracle.  Stateful scorers carry none (the megakernel has no state
    lane), so the fused path can never silently engage for them.
    ``state_spec``: pytree of ``jax.ShapeDtypeStruct`` with PER-ROW
    shapes (no capacity axis); ``()`` declares a stateless scorer.
    ``weights``: a stateful scorer's parameters, handed to ``stage_fn``
    and ``lane_stage_fn`` as their first argument.  DeviceExecutor passes
    them to its programs as arguments, so a model's layers are never
    baked into a compiled program as constants.
    ``model_partition`` (optional): the 2-D-mesh ticket (DESIGN.md §13).
    ``model_partition(model_shards) -> (mparams, col_fn)`` where
    ``mparams`` is a pytree of stage-stacked slab slices with a LEADING
    model-shard axis (leaf shapes ``(M, S, w_local, ...)``, built with
    ``launch.shardings.stage_column_slices``) and
    ``col_fn(local_mparams, x, rows, s, t0, c0, n_valid) -> (cap,
    w_local)`` scores ONLY cascade columns [t0 + c0, t0 + c0 + w_local)
    of stage ``s`` from this shard's slab slice (``local_mparams`` =
    ``mparams`` with the leading axis stripped; ``s``/``t0``/``c0``
    traced scalars).  Scorers without one cannot run at
    ``model_shards > 1``.
    """

    fn: Callable | None
    prepare: Callable
    width: int
    block_n: int | None = None
    lane_fn: Callable | None = None
    slabs: mk.ParamSlabs | None = None
    state_spec: object = ()
    stage_fn: Callable | None = None
    lane_stage_fn: Callable | None = None
    model_partition: Callable | None = None
    weights: object = ()

    @property
    def stateful(self) -> bool:
        return len(jax.tree_util.tree_leaves(self.state_spec)) > 0

    @property
    def has_lanes(self) -> bool:
        return self.lane_fn is not None or self.lane_stage_fn is not None

    def init_state(self, cap: int):
        """Zero state buffers at capacity ``cap`` (leading axis added to
        every ``state_spec`` leaf).  ``()`` for stateless scorers — the
        executors' state threading then adds no leaves to their carries."""
        return jax.tree_util.tree_map(
            lambda sd: jnp.zeros((cap,) + tuple(sd.shape), sd.dtype),
            self.state_spec,
        )

    def row_block(self, default: int, cap: int) -> int:
        """The row block a stage over ``cap`` lanes computes at, for
        ``scores_computed``: ``block_n``, else the executor's ``default``;
        a stateful scorer's ``lane_block`` of it."""
        bn = self.block_n or default
        return lane_block(bn, cap) if self.stateful else bn

    def stage(self, state, t0, t1, rows, x, n_valid):
        """The protocol: scores for cascade positions [t0, t1) of the
        buffer's rows, plus the carried-forward state."""
        if self.stage_fn is not None:
            return self.stage_fn(self.weights, state, t0, t1, rows, x, n_valid)
        return self.fn(x, rows, t0, n_valid), state

    def lane_stage(self, state, t0_lane, rows, x, n_valid):
        """Per-lane-stage protocol variant (streaming admission)."""
        if self.lane_stage_fn is not None:
            return self.lane_stage_fn(self.weights, state, t0_lane, rows, x, n_valid)
        return self.lane_fn(x, rows, t0_lane, n_valid), state


def lane_block(bn: int, cap: int) -> int:
    """Lanes per block of a stage that loops over the front-packed live
    prefix of a ``cap``-lane buffer in blocks of ``bn``: ``bn`` where it
    tiles the buffer, else one block of all ``cap`` lanes."""
    return bn if cap % bn == 0 else cap


def repack_state(state, state_new, pack):
    """Front-pack a survivor-state pytree with the compaction's ``pack``
    indices: surviving lanes' updated state lands at its packed position,
    retired lanes scatter out of bounds and drop, vacated lanes zero.
    The no-op for stateless scorers (empty pytree, zero leaves)."""
    return jax.tree_util.tree_map(
        lambda b, v: jnp.zeros_like(b).at[pack].set(v, mode="drop"),
        state,
        state_new,
    )


def matrix_stage_scorer(
    dplan: DevicePlan, quant: str | None = None
) -> BoundScorer:
    """Scorer over a precomputed cascade-ORDERED (n, T) matrix.

    The device-loop analogue of ``core.executor.matrix_producer`` — used
    by tests/oracles and by the server's eager ``score_fn`` fallback
    (scoring stays eager; control flow still moves on device).
    ``quant`` overrides the plan's slab storage dtype (None = the plan's
    ``dplan.quant``).
    """
    W, T, T_pad = dplan.W, dplan.plan.T, dplan.T_pad
    slabs = mk.build_matrix_slabs(dplan, quant=quant or dplan.quant)

    def prepare(ordered: np.ndarray) -> jax.Array:
        F = jnp.asarray(ordered, dtype=jnp.float32)
        assert F.shape[1] == T
        return jnp.pad(F, ((0, 0), (0, T_pad - T)))

    def fn(x: jax.Array, rows: jax.Array, t0: jax.Array, n_valid) -> jax.Array:
        xr = jnp.take(x, rows, axis=0)  # OOB (trash) indices clamp
        return jax.lax.dynamic_slice(xr, (0, t0), (xr.shape[0], W))

    def lane_fn(x, rows, t0_lane, n_valid):
        # per-lane slab: lane i reads columns [t0_lane[i], t0_lane[i] + W)
        # — always in range because x is padded to T_pad
        xr = jnp.take(x, rows, axis=0)
        idx = t0_lane[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]
        return jnp.take_along_axis(xr, idx, axis=1)

    def model_partition(model_shards: int):
        from repro.launch.shardings import split_columns

        w_l, w_g = split_columns(W, model_shards)

        def col_fn(mp, x, rows, s, t0, c0, n_valid):
            xr = jnp.take(x, rows, axis=0)
            # x is padded to T_pad = max(t0) + W; a shard whose slice
            # only partially overlaps the stage would otherwise have
            # dynamic_slice CLAMP t0 + c0 and silently shift in-range
            # columns — pad to max(t0) + w_g so every start is in range
            xr = jnp.pad(xr, ((0, 0), (0, w_g - W)))
            return jax.lax.dynamic_slice(xr, (0, t0 + c0), (xr.shape[0], w_l))

        # the "slab" here IS the operand matrix (data-sharded already):
        # nothing to split, every model shard just reads its own columns
        return (), col_fn

    return BoundScorer(
        fn=fn, prepare=prepare, width=W, lane_fn=lane_fn, slabs=slabs,
        model_partition=model_partition,
    )


def tree_stage_scorer(
    dplan: DevicePlan,
    feats_ordered: np.ndarray,
    thrs_ordered: np.ndarray,
    leaves_ordered: np.ndarray,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool | None = None,
    quant: str | None = None,
) -> BoundScorer:
    """Oblivious-forest scorer: per stage, ``dynamic_slice`` the (W, ...)
    slab of cascade-ordered stacked tree params and run the Pallas tree
    kernel on the gathered survivor rows.  Padded models have zero leaves
    (inert even before the executor masks their columns).  ``quant``
    overrides the plan's slab storage dtype for the megakernel path."""
    W, T_pad = dplan.W, dplan.T_pad
    T, depth = np.asarray(feats_ordered).shape
    n_leaves = np.asarray(leaves_ordered).shape[1]
    slabs = mk.build_tree_slabs(
        dplan, feats_ordered, thrs_ordered, leaves_ordered,
        quant=quant or dplan.quant,
    )
    pad = ((0, T_pad - T), (0, 0))
    feats_p = jnp.asarray(np.pad(np.asarray(feats_ordered), pad))
    thrs_p = jnp.asarray(np.pad(np.asarray(thrs_ordered), pad))
    leaves_p = jnp.asarray(np.pad(np.asarray(leaves_ordered), pad))

    def prepare(x: np.ndarray) -> jax.Array:
        return jnp.asarray(x, dtype=jnp.float32)

    def fn(x: jax.Array, rows: jax.Array, t0: jax.Array, n_valid) -> jax.Array:
        f = jax.lax.dynamic_slice(feats_p, (t0, 0), (W, depth))
        th = jax.lax.dynamic_slice(thrs_p, (t0, 0), (W, depth))
        lv = jax.lax.dynamic_slice(leaves_p, (t0, 0), (W, n_leaves))
        return gbt_scores_pallas(
            f, th, lv, x, block_n=block_n, interpret=interpret, rows=rows,
            n_valid=n_valid,
        )

    def lane_fn(x, rows, t0_lane, n_valid):
        # per-lane slab gather: lane i walks trees [t0_lane[i], +W).  Tree
        # scoring is a pure leaf SELECT (compare -> index -> lookup), so
        # this jnp formulation is bit-identical to the Pallas kernel's
        # onehot @ LUT — same comparisons at the same dtype, same leaf.
        xr = jnp.take(x, rows, axis=0).astype(leaves_p.dtype)  # (cap, d)
        pos = t0_lane[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]
        f = jnp.take(feats_p, pos, axis=0)  # (cap, W, depth)
        th = jnp.take(thrs_p, pos, axis=0).astype(leaves_p.dtype)
        lv = jnp.take(leaves_p, pos, axis=0)  # (cap, W, n_leaves)
        idx = jnp.zeros(pos.shape, dtype=jnp.int32)
        for j in range(depth):
            xj = jnp.take_along_axis(xr, f[:, :, j], axis=1)  # (cap, W)
            idx = 2 * idx + (xj > th[:, :, j]).astype(jnp.int32)
        return jnp.take_along_axis(lv, idx[:, :, None], axis=2)[:, :, 0]

    def model_partition(model_shards: int):
        from repro.launch.shardings import split_columns, stage_column_slices

        w_l, w_g = split_columns(W, model_shards)
        t0s = dplan.stage_t0
        mparams = {
            "feats": stage_column_slices(feats_ordered, t0s, w_l, w_g),
            "thrs": stage_column_slices(thrs_ordered, t0s, w_l, w_g),
            "leaves": stage_column_slices(leaves_ordered, t0s, w_l, w_g),
        }

        def col_fn(mp, x, rows, s, t0, c0, n_valid):
            # tree scoring is per-column independent, so running the
            # kernel on the (w_l, ...) slice gives bit-identical columns
            f = jax.lax.dynamic_index_in_dim(mp["feats"], s, 0, keepdims=False)
            th = jax.lax.dynamic_index_in_dim(mp["thrs"], s, 0, keepdims=False)
            lv = jax.lax.dynamic_index_in_dim(mp["leaves"], s, 0, keepdims=False)
            return gbt_scores_pallas(
                f, th, lv, x, block_n=block_n, interpret=interpret, rows=rows,
                n_valid=n_valid,
            )

        return mparams, col_fn

    return BoundScorer(
        fn=fn, prepare=prepare, width=W, block_n=block_n, lane_fn=lane_fn,
        slabs=slabs, model_partition=model_partition,
    )


def lattice_stage_scorer(
    dplan: DevicePlan,
    theta_ordered: np.ndarray,
    feats_ordered: np.ndarray,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool | None = None,
    quant: str | None = None,
) -> BoundScorer:
    """Lattice scorer: same slab scheme as ``tree_stage_scorer`` over the
    cascade-ordered (theta, feats) stacks."""
    W, T_pad = dplan.W, dplan.T_pad
    T, S_feats = np.asarray(feats_ordered).shape
    p = np.asarray(theta_ordered).shape[1]
    slabs = mk.build_lattice_slabs(
        dplan, theta_ordered, feats_ordered, quant=quant or dplan.quant
    )
    theta_p = jnp.asarray(np.pad(np.asarray(theta_ordered), ((0, T_pad - T), (0, 0))))
    feats_p = jnp.asarray(np.pad(np.asarray(feats_ordered), ((0, T_pad - T), (0, 0))))

    def prepare(x: np.ndarray) -> jax.Array:
        return jnp.asarray(x, dtype=jnp.float32)

    def fn(x: jax.Array, rows: jax.Array, t0: jax.Array, n_valid) -> jax.Array:
        th = jax.lax.dynamic_slice(theta_p, (t0, 0), (W, p))
        f = jax.lax.dynamic_slice(feats_p, (t0, 0), (W, S_feats))
        return lattice_scores_pallas(
            th, f, x, block_n=block_n, interpret=interpret, rows=rows,
            n_valid=n_valid,
        )

    def lane_fn(x, rows, t0_lane, n_valid):
        # per-lane slab gather + interleaved-doubling corner weights (the
        # kernel's products in the kernel's order), finished with the
        # same (2**S,) contraction per lane
        xr = jnp.take(x, rows, axis=0)  # (cap, d)
        cap = xr.shape[0]
        pos = t0_lane[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]
        th = jnp.take(theta_p, pos, axis=0).astype(xr.dtype)  # (cap, W, p)
        f = jnp.take(feats_p, pos, axis=0).astype(jnp.int32)  # (cap, W, S)
        w = jnp.ones((cap, W, 1), dtype=xr.dtype)
        for j in range(S_feats):
            xj = jnp.take_along_axis(xr, f[:, :, j], axis=1)[:, :, None]
            w = jnp.stack([w * (1.0 - xj), w * xj], axis=-1).reshape(
                cap, W, -1
            )
        # halving-sum contraction (NOT einsum/dot): the pairwise order of
        # the lattice kernels, keeping every f32 path bit-identical
        return halving_sum(w * th)[..., 0]

    def model_partition(model_shards: int):
        from repro.launch.shardings import split_columns, stage_column_slices

        w_l, w_g = split_columns(W, model_shards)
        t0s = dplan.stage_t0
        mparams = {
            "theta": stage_column_slices(theta_ordered, t0s, w_l, w_g),
            "feats": stage_column_slices(feats_ordered, t0s, w_l, w_g),
        }

        def col_fn(mp, x, rows, s, t0, c0, n_valid):
            th = jax.lax.dynamic_index_in_dim(mp["theta"], s, 0, keepdims=False)
            f = jax.lax.dynamic_index_in_dim(mp["feats"], s, 0, keepdims=False)
            return lattice_scores_pallas(
                th, f, x, block_n=block_n, interpret=interpret, rows=rows,
                n_valid=n_valid,
            )

        return mparams, col_fn

    return BoundScorer(
        fn=fn, prepare=prepare, width=W, block_n=block_n, lane_fn=lane_fn,
        slabs=slabs, model_partition=model_partition,
    )


@dataclasses.dataclass
class StreamResult:
    """Result of a streaming (continuous-batching) run, DESIGN.md §8.

    Per-row results mirror ``ExecutorResult``; the streaming-specific
    fields are the loop-step timeline: ``admit_step[i]`` is the loop step
    at which row i left the admission ring for a survivor slot,
    ``done_step[i]`` the step at which its decision was recorded, and
    ``occupancy[s]`` the live slot count at step s (reconstructed
    host-side from admit/done — a lane is live at every step in
    [admit, done]).  Latency in steps is ``done_step - arrival + 1``.
    ``chunk_stats`` stays empty (stages are mixed per step); billing uses
    the same block-guard accounting as the batch path, applied to the
    per-step live count.
    """

    decisions: np.ndarray  # (n,) bool
    exit_step: np.ndarray  # (n,) int64, 1-based; T if never exited
    g_final: np.ndarray  # (n,) float32
    admit_step: np.ndarray  # (n,) int64 — loop step of slot admission
    done_step: np.ndarray  # (n,) int64 — loop step of the decision
    steps_run: int  # total loop steps executed
    occupancy: np.ndarray  # (steps_run,) int64 live slots per step
    capacity: int  # survivor-slot capacity (occupancy denominator)
    scores_computed: int
    scores_possible: int
    chunk_stats: list = dataclasses.field(default_factory=list)

    @property
    def mean_occupancy(self) -> float:
        """Mean live-slot fraction over the run's loop steps."""
        if self.steps_run == 0:
            return 0.0
        return float(self.occupancy.mean()) / max(self.capacity, 1)

    @property
    def latency_steps(self) -> np.ndarray:
        """Admission wait + service, in loop steps (admission-relative:
        callers add their own queue wait before the ring)."""
        return self.done_step - self.admit_step + 1


def stream_occupancy(
    admit_step: np.ndarray, done_step: np.ndarray, steps_run: int
) -> np.ndarray:
    """(steps_run,) live-slot count per loop step from the admit/done
    timeline: a row occupies its slot (and is scored) at every step in
    [admit, done].  Shared by the executors' billing, the streaming
    benchmark and the tests."""
    occ = np.zeros(steps_run + 1, dtype=np.int64)
    if steps_run == 0 or admit_step.size == 0:
        return occ[:steps_run]
    np.add.at(occ, admit_step, 1)
    np.add.at(occ, done_step + 1, -1)
    return np.cumsum(occ[:steps_run])


@dataclasses.dataclass
class GroupedResult:
    """One ranked verdict per query group (DESIGN.md §12).

    ``verdicts`` (G, k) are flat GLOBAL document row ids in rank order,
    -1 past the group's size.  ``exit_stage`` is 1-based; ``S`` for
    groups that ran the full cascade.  ``margin`` is the top-k stability
    margin at decision time.  ``chunk_stats`` counts GROUPS in/exited
    per stage; ``scores_computed`` is group-quantized block billing,
    ``scores_possible`` is real documents x T.
    """

    verdicts: np.ndarray  # (G, k) int32
    exit_stage: np.ndarray  # (G,) int64
    margin: np.ndarray  # (G,) float32
    chunk_stats: list[ChunkStat]
    scores_computed: int
    scores_possible: int


@dataclasses.dataclass
class GroupedStreamResult:
    """Streaming (continuous-batching) grouped run: ``GroupedResult``
    per-group fields plus the slot timeline of ``StreamResult``, at
    GROUP granularity (``occupancy`` counts live group slots; billing
    multiplies by the bucket width before block-quantizing)."""

    verdicts: np.ndarray  # (G, k) int32
    exit_stage: np.ndarray  # (G,) int64
    margin: np.ndarray  # (G,) float32
    admit_step: np.ndarray  # (G,) int64
    done_step: np.ndarray  # (G,) int64
    steps_run: int
    occupancy: np.ndarray  # (steps_run,) int64 live group slots per step
    capacity_groups: int
    scores_computed: int
    scores_possible: int

    @property
    def mean_occupancy(self) -> float:
        if self.steps_run == 0:
            return 0.0
        return float(self.occupancy.mean()) / max(self.capacity_groups, 1)

    @property
    def latency_steps(self) -> np.ndarray:
        return self.done_step - self.admit_step + 1


def group_topk_rows(g, valid, rows, k: int):
    """Per-group top-k GLOBAL document ids via segment reductions.

    ``g``/``valid``/``rows`` are the (G, B) bucket-layout buffers; the
    group axis is the segment axis.  k unrolled passes of
    ``jax.ops.segment_max`` pick each group's current best lane, with
    the first-hit tie-break (lowest flat lane index — a
    ``segment_sum``-prefix rank, matching ``ranking.plan.topk_margin``'s
    numpy cumsum exactly) consuming one lane per pass.  Returns (G, k)
    int32 document ids, -1 where the group has fewer than k documents.
    """
    G, B = g.shape
    L = G * B
    seg = jnp.repeat(jnp.arange(G, dtype=jnp.int32), B)
    vflat = valid.reshape(L).astype(bool)
    work = jnp.where(vflat, g.reshape(L), -jnp.inf)
    rows_flat = rows.reshape(L).astype(jnp.int32)
    avail = vflat
    outs = []
    for _ in range(k):
        masked = jnp.where(avail, work, -jnp.inf)
        cur = jax.ops.segment_max(masked, seg, num_segments=G)  # (G,)
        hit = avail & (masked == jnp.take(cur, seg))
        hit_i = hit.astype(jnp.int32)
        # rank each hit within its segment: a flat cumsum minus the
        # segment's exclusive prefix of hit counts — rank 0 is the
        # lowest-lane hit, the tie winner
        seg_tot = jax.ops.segment_sum(hit_i, seg, num_segments=G)
        seg_before = jnp.take(jnp.cumsum(seg_tot) - seg_tot, seg)
        before_me = jnp.cumsum(hit_i) - hit_i - seg_before
        first = hit & (before_me == 0)
        pick = jnp.where(first, rows_flat, -1)
        # exactly one non-(-1) candidate per group (or none, exhausted)
        outs.append(jax.ops.segment_max(pick, seg, num_segments=G))
        avail = avail & ~first
    return jnp.stack(outs, axis=1).astype(jnp.int32)


class DeviceExecutor:
    """Runs a ``CascadePlan`` as one compiled device program.

    The host ``ChunkedExecutor`` stays as the semantics oracle and the
    escape hatch for arbitrary (host-side) producer injection; this class
    is the serving fast path.  ``traces`` counts jit traces — the static
    fixed-capacity design keeps it at 1 per (N, T, chunk_t), which
    ``tests/test_executor.py`` asserts.

    Billing: an executed stage computes ``ceil(n_in / block_n) * block_n``
    rows of its W-wide slab — the score kernels' live-count block guard
    skips row-blocks past the compacted survivors, so even at static
    shapes per-stage compute (and the bill) tracks the live count at
    block granularity, exactly like the host path's ``bill_block``
    accounting.  ``benchmarks/bench_device_executor.py`` measures both
    this and wall-clock.

    ``megakernel`` selects the fused stage-step path (DESIGN.md §9): one
    Pallas kernel per stage does slab gather + scoring + threshold decide,
    instead of the score kernel / decide kernel sequence.  ``None`` (default) auto-
    enables it when the scorer carries f32 ``ParamSlabs`` — bit-identical
    results AND billing, so it is the default device scorer path for
    factory-built scorers; quantized (bf16/int8) slabs must be requested
    explicitly (``megakernel=True``) because their results are certified
    by the tolerance oracle, not bit equality.  ``False`` forces the
    multi-kernel path (the fallback and parity oracle).
    """

    def __init__(
        self,
        plan: CascadePlan | DevicePlan,
        scorer: BoundScorer,
        block_n: int = DEFAULT_BLOCK_N,
        interpret: bool | None = None,
        megakernel: bool | None = None,
        check_finite: bool = False,
    ):
        self.dplan = plan if isinstance(plan, DevicePlan) else DevicePlan.from_plan(plan)
        if scorer.width != self.dplan.W:
            raise ValueError(
                f"scorer width {scorer.width} != plan stage width {self.dplan.W}"
            )
        if megakernel is None:
            megakernel = scorer.slabs is not None and scorer.slabs.quant == "f32"
        if megakernel and scorer.stateful:
            raise ValueError(
                "megakernel=True is incompatible with a stateful scorer "
                "(non-empty state_spec): the fused stage step has no "
                "survivor-state carry.  Use the multi-kernel path "
                "(megakernel=False / the auto default)."
            )
        if megakernel and scorer.slabs is None:
            raise ValueError(
                "megakernel=True needs a scorer with ParamSlabs (factory-"
                "built scorers carry them; custom scorers fall back to the "
                "multi-kernel path)"
            )
        self.megakernel = bool(megakernel)
        self.scorer = scorer
        self.check_finite = bool(check_finite)
        self.block_n = max(1, int(block_n))
        self.interpret = resolve_interpret(interpret)
        self.traces = 0
        self._compiled: set = set()  # argument signatures compiled so far
        self._jit = jax.jit(self._weighed(self._program))
        self._stream_jit = jax.jit(
            self._weighed(self._stream_program), static_argnums=(0,)
        )
        # grouped (ranking) programs: k is static — verdict extraction
        # unrolls k segment-max passes
        self._grouped_jit = jax.jit(
            self._weighed(self._grouped_program), static_argnums=(0,)
        )
        self._grouped_stream_jit = jax.jit(
            self._weighed(self._grouped_stream_program), static_argnums=(0, 1)
        )

    def _weighed(self, program):
        """``program`` taking the scorer's ``weights`` as one more, last
        argument: while it is traced the scorer reads the traced weights,
        so they reach the device as arguments, not as constants."""

        def run(*args):
            *args, weights = args
            bound = self.scorer
            self.scorer = dataclasses.replace(bound, weights=weights)
            try:
                return program(*args)
            finally:
                self.scorer = bound

        run.__name__ = run.__qualname__ = program.__name__  # the program's trace name
        return run

    def _bn_bill(self) -> int:
        """The kernel row-block granularity billing runs at — the
        scorer's own block size when it has one.  The megakernel runs at
        the SAME granularity, which is what keeps its billed counters
        bit-identical to the multi-kernel path."""
        return self.scorer.block_n or self.block_n

    def _cast_operand(self, x):
        """Matrix-variant quantized storage: the payload IS the prepared
        operand, so the executor casts it once per run (bf16 halves the
        survivor buffer's HBM footprint; accumulation stays f32
        in-kernel).  No-op for every other configuration."""
        sl = self.scorer.slabs
        if (
            self.megakernel
            and sl is not None
            and sl.x_dtype is not None
            and x.dtype != sl.x_dtype
        ):
            return x.astype(sl.x_dtype)
        return x

    def _cap(self, n: int) -> int:
        b = self.block_n
        return -(-max(n, 1) // b) * b

    def _program(self, x, rows_init, n0):
        self.traces += 1  # trace-time side effect, read by the trace tests
        dp = self.dplan
        S, W, T = dp.S, dp.W, dp.plan.T
        cap = rows_init.shape[0]
        stage_t0 = jnp.asarray(dp.stage_t0)
        eps_pos = jnp.asarray(dp.eps_pos)
        eps_neg = jnp.asarray(dp.eps_neg)
        col_valid = jnp.asarray(dp.col_valid)
        lane = jnp.arange(cap, dtype=jnp.int32)

        # a stage's ops are compaction, but for its scoring and decide
        # kernels (the inner scope)
        @jax.named_scope(tracing.COMPACT)
        def body(carry):
            # stage semantics mirrored by ShardedDeviceExecutor._per_shard
            # (scatter targets differ: buffer rows here, global ids there)
            # — a semantics change here must be replayed there; the
            # parity tests in tests/test_sharded.py catch a skew
            s, rows, n_active, g, dec, ex, n_in_log, state = carry
            n_in_log = n_in_log.at[s].set(n_active)
            t0 = stage_t0[s]
            g_rows = jnp.take(g, rows, axis=0)  # trash indices clamp
            if self.megakernel:
                # ONE fused kernel: slab select by prefetched stage,
                # score + decide — the survivor buffer makes one round
                # trip, and the pack positions come back ready to
                # scatter (DESIGN.md §9)
                xr = jnp.take(x, rows, axis=0)  # trash indices clamp
                with jax.named_scope(tracing.SCORE_DECIDE):
                    g_new, active, dpos, ex_rel, pack, n_keep = (
                        mk.mega_stage_pallas(
                            self.scorer.slabs, xr, g_rows, s, t0, n_active,
                            eps_pos, eps_neg,
                            block_n=self._bn_bill(),
                            interpret=self.interpret,
                        )
                    )
                state_new = state  # megakernel path is stateless-only
            else:
                # multi-kernel fallback (the parity oracle): score the
                # survivor buffer, then decide.  The scorer may skip
                # whole blocks past n_active (survivors are front-
                # packed); padded columns are zeroed so they cannot move
                # a partial sum.  Stateful scorers return the carried
                # per-lane state alongside the scores.
                with jax.named_scope(tracing.SCORE_DECIDE):
                    scores, state_new = self.scorer.stage(
                        state, t0, t0 + W, rows, x, n_active
                    )
                    scores = jnp.where(col_valid[s][None, :], scores, 0.0)
                    g_new, active, dpos, ex_rel = cascade_chunk_pallas(
                        g_rows,
                        scores,
                        eps_pos[s],
                        eps_neg[s],
                        0,
                        block_n=self.block_n,
                        interpret=self.interpret,
                        n_valid=n_active,
                    )
                # cumsum-prefix compaction: rank survivors (stable) and
                # pack them at the front of the fixed-capacity buffer
                keep = active.astype(bool) & (lane < n_active)
                pos = jnp.cumsum(keep.astype(jnp.int32)) - 1
                pack = jnp.where(keep, pos, cap)
                n_keep = keep.sum(dtype=jnp.int32)
            lane_valid = lane < n_active
            newly = lane_valid & (ex_rel > 0)
            # scatter exits by absolute row index; retired/padding lanes
            # aim at index cap, which is out of bounds and dropped
            scat = jnp.where(newly, rows, cap)
            dec = dec.at[scat].set(dpos.astype(bool), mode="drop")
            ex = ex.at[scat].set(ex_rel + t0, mode="drop")
            g = g.at[jnp.where(lane_valid, rows, cap)].set(g_new, mode="drop")
            rows = (
                jnp.full((cap,), cap, dtype=jnp.int32)
                .at[pack]
                .set(rows, mode="drop")
            )
            # the survivor-state pytree is compacted with the SAME pack
            # indices as the rows buffer (a no-op for stateless scorers:
            # the tree is empty, so no carry leaves are added)
            state = repack_state(state, state_new, pack)
            return (s + 1, rows, n_keep, g, dec, ex, n_in_log, state)

        def cond(carry):
            s, _, n_active, _, _, _, _, _ = carry
            # quit when you can: stop as soon as every row has exited
            return (s < S) & (n_active > 0)

        init = (
            jnp.int32(0),
            rows_init,
            jnp.asarray(n0, dtype=jnp.int32),
            jnp.zeros((cap,), dtype=jnp.float32),
            jnp.zeros((cap,), dtype=jnp.bool_),
            jnp.full((cap,), T, dtype=jnp.int32),
            jnp.zeros((S,), dtype=jnp.int32),
            self.scorer.init_state(cap),
        )
        s_f, rows_f, n_f, g, dec, ex, n_in_log, _ = jax.lax.while_loop(
            cond, body, init
        )
        # rows that never exited: classified by the full ensemble score
        with jax.named_scope(tracing.FINALIZE):
            lane_valid = lane < n_f
            dec = dec.at[jnp.where(lane_valid, rows_f, cap)].set(
                jnp.take(g, rows_f, axis=0) >= jnp.float32(self.dplan.plan.beta),
                mode="drop",
            )
            # one int32 buffer out, so the host reads it in one transfer
            # (unpacked by _unpack)
            return jnp.concatenate([
                dec.astype(jnp.int32),
                ex,
                jax.lax.bitcast_convert_type(g, jnp.int32),
                jnp.stack([s_f, n_f]),
                n_in_log,
            ])

    @staticmethod
    def _unpack(buf: np.ndarray, cap: int, n: int):
        """``_program``'s packed results -> (dec, ex, g, s_f, n_f,
        n_in_log), each row array cut to the batch's ``n`` rows."""
        dec = buf[:n].astype(bool)
        ex = buf[cap : cap + n].astype(np.int64)
        g = buf[2 * cap : 2 * cap + n].view(np.float32)
        s_f, n_f = int(buf[3 * cap]), int(buf[3 * cap + 1])
        return dec, ex, g, s_f, n_f, buf[3 * cap + 2 :]

    def run(
        self,
        batch,
        n: int,
        row_order=None,
        capacity: int | None = None,
        prepared: bool = False,
    ) -> ExecutorResult:
        """Execute the cascade for ``n`` rows of ``batch`` on device.

        ``batch`` is whatever the scorer's ``prepare`` consumes (feature
        matrix for the tree/lattice scorers, a cascade-ordered score
        matrix for the matrix scorer).  ``row_order`` is the initial
        active-set ordering (the sorted backend's sort permutation): a
        host ``(n,)`` ordering, or the ``(cap,)`` int32 rows buffer
        already built on the device (the ordering, then ``cap`` on every
        lane past ``n``), which the stage loop takes without a read.
        Results always come back scattered to absolute row indices, in
        one blocking read.
        ``capacity`` pins the buffer size: a caller flushing variable
        batch sizes (the server's final partial flush) passes its max
        batch size so every flush reuses the one compiled trace.
        ``prepared=True`` means ``batch`` is ALREADY the scorer-prepared
        operand (a caller that needed it earlier, e.g. for a sort key,
        avoids a second prepare + upload).
        """
        plan = self.dplan.plan
        T = plan.T
        if n == 0:
            return ExecutorResult(
                decisions=np.zeros(0, dtype=bool),
                exit_step=np.zeros(0, dtype=np.int64),
                g_final=np.zeros(0, dtype=np.float32),
                chunk_stats=[],
                scores_computed=0,
                scores_possible=0,
            )
        with TraceAnnotation(tracing.RUN_DISPATCH):
            if self.check_finite:
                check_batch_finite(batch, n)
            cap = self._cap(max(n, capacity or 0))
            if not prepared:
                batch = self.scorer.prepare(pad_rows(batch, cap))
            x = pad_rows(self._cast_operand(batch), cap)
            if isinstance(row_order, jax.Array):
                if row_order.shape != (cap,) or row_order.dtype != jnp.int32:
                    raise ValueError(
                        f"a device row_order is the ({cap},) int32 rows "
                        f"buffer, got {row_order.shape} {row_order.dtype}"
                    )
                rows_init = row_order
            else:
                rows = (
                    np.arange(n, dtype=np.int32)
                    if row_order is None
                    else np.asarray(row_order, dtype=np.int32)
                )
                assert rows.shape == (n,)
                rows_init = np.full(cap, cap, dtype=np.int32)
                rows_init[:n] = rows
                rows_init = jnp.asarray(rows_init)
            args = (x, rows_init, n, self.scorer.weights)
            compile_program(self._compiled, self._jit, *args)
            out = launch_wave("device", lambda: self._jit(*args))
        with TraceAnnotation(tracing.RUN_FETCH):
            # one blocking read of the packed results
            dec, ex, g, s_f, n_f, n_in_log = self._unpack(np.asarray(out), cap, n)
        with TraceAnnotation(tracing.RUN_STATS):
            stages = plan.stages
            # bill at the SCORER's row block (the granularity its block
            # guard, or a stateful scorer's block loop, really computes
            # at), not the executor's buffer block
            bn, W = self.scorer.row_block(self.block_n, cap), self.dplan.W
            chunk_stats = []
            for s in range(s_f):
                n_in = int(n_in_log[s])
                n_next = int(n_in_log[s + 1]) if s + 1 < s_f else n_f
                # block-guard billing: the scorer computed the live blocks
                # of the W-wide slab, not the whole capacity
                chunk_stats.append(
                    ChunkStat(
                        t0=stages[s][0],
                        t1=stages[s][1],
                        n_in=n_in,
                        n_exited=n_in - n_next,
                        scores_computed=-(-n_in // bn) * bn * W,
                    )
                )
        return ExecutorResult(
            decisions=dec,
            exit_step=ex,
            g_final=g,
            chunk_stats=chunk_stats,
            scores_computed=sum(c.scores_computed for c in chunk_stats),
            scores_possible=n * T,
            device_reads=1,
        )

    # -- streaming admission (continuous batching, DESIGN.md §8) --------

    def _stream_program(self, cap, x, ring_ids, arrivals, n_pending):
        self.traces += 1  # trace-time side effect, read by the trace tests
        dp = self.dplan
        S, W, T = dp.S, dp.W, dp.plan.T
        R = ring_ids.shape[0]  # ring capacity == output size; R = trash id
        stage_t0 = jnp.asarray(dp.stage_t0)
        eps_pos = jnp.asarray(dp.eps_pos)
        eps_neg = jnp.asarray(dp.eps_neg)
        col_valid = jnp.asarray(dp.col_valid)
        beta = jnp.float32(dp.plan.beta)
        lane = jnp.arange(cap, dtype=jnp.int32)
        ridx = jnp.arange(R, dtype=jnp.int32)

        def body(carry):
            (step, rows, stage, g, n_live, head,
             dec, ex, gout, admit, done, state) = carry
            # admission refill: open slots at the BACK of the front-packed
            # buffers take the next pending rows whose arrival step has
            # come (arrivals are nondecreasing — the ring is the server's
            # arrival-order queue), entering at cascade stage 0
            arrived = jnp.sum(
                (ridx >= head) & (ridx < n_pending) & (arrivals <= step),
                dtype=jnp.int32,
            )
            k = jnp.minimum(cap - n_live, arrived)
            src = jnp.clip(head + (lane - n_live), 0, R - 1)
            is_new = (lane >= n_live) & (lane < n_live + k)
            rows = jnp.where(is_new, jnp.take(ring_ids, src), rows)
            stage = jnp.where(is_new, 0, stage)
            g = jnp.where(is_new, 0.0, g)
            admit = admit.at[jnp.where(is_new, rows, R)].set(
                step, mode="drop"
            )
            n_live = n_live + k
            head = head + k
            # mixed-stage fused stage: every per-stage quantity of the
            # batch body (slab start, thresholds, column validity) is
            # gathered per LANE from the DevicePlan stage tables
            t0_lane = jnp.take(stage_t0, stage)
            stop = stage >= S - 1  # lanes running their LAST stage
            if self.megakernel:
                # ONE fused mixed-stage kernel: per-lane slab gather at
                # the QUANTIZED storage dtype, then score + decide +
                # compaction prefix in a single pass (DESIGN.md §9).
                # Lanes on their last stage are excluded from the
                # survivor prefix inside the kernel (the stop input).
                slabs = self.scorer.slabs
                if slabs.variant == "matrix":
                    xr = jnp.take(x, rows, axis=0)
                    idx = (
                        t0_lane[:, None]
                        + jnp.arange(W, dtype=jnp.int32)[None, :]
                    )
                    x_in = jnp.take_along_axis(xr, idx, axis=1)
                else:
                    x_in = jnp.take(x, rows, axis=0)
                g_new, active, dpos, ex_rel, pack, n_keep = (
                    mk.mega_lane_pallas(
                        slabs, x_in, mk.gather_lane_slabs(slabs, stage),
                        g,
                        jnp.take(eps_pos, stage, axis=0),
                        jnp.take(eps_neg, stage, axis=0),
                        stop, n_live,
                        block_n=self._bn_bill(),
                        interpret=self.interpret,
                    )
                )
                active_b = active.astype(bool)
                lane_valid = lane < n_live
                state_new = state  # megakernel path is stateless-only
            else:
                # rookies admitted above sit at stage 0: the t0==0 contract
                # (BoundScorer docs) makes the scorer (re)initialize their
                # lane state from the prepared operand, so the zero-filled
                # slots left by compaction are never read as real state
                scores, state_new = self.scorer.lane_stage(
                    state, t0_lane, rows, x, n_live
                )
                scores = jnp.where(
                    jnp.take(col_valid, stage, axis=0), scores, 0.0
                )
                g_new, active, dpos, ex_rel = cascade_lane_pallas(
                    g,
                    scores,
                    jnp.take(eps_pos, stage, axis=0),
                    jnp.take(eps_neg, stage, axis=0),
                    block_n=self.block_n,
                    interpret=self.interpret,
                    n_valid=n_live,
                )
                active_b = active.astype(bool)
                lane_valid = lane < n_live
                # cumsum-prefix compaction (veterans advance one stage);
                # the freed back slots are the NEXT step's refill targets
                keep = lane_valid & active_b & ~stop
                pos = jnp.cumsum(keep.astype(jnp.int32)) - 1
                pack = jnp.where(keep, pos, cap)
                n_keep = keep.sum(dtype=jnp.int32)
            newly = lane_valid & (ex_rel > 0)
            # lanes that finished the cascade without exiting: classified
            # by the full ensemble score, same as the batch epilogue
            ran_out = lane_valid & active_b & stop
            fin = newly | ran_out
            dec_val = jnp.where(newly, dpos.astype(bool), g_new >= beta)
            ex_val = jnp.where(newly, ex_rel + t0_lane, T)
            scat = jnp.where(fin, rows, R)
            dec = dec.at[scat].set(dec_val, mode="drop")
            ex = ex.at[scat].set(ex_val, mode="drop")
            gout = gout.at[scat].set(g_new, mode="drop")
            done = done.at[scat].set(step, mode="drop")
            rows = (
                jnp.full((cap,), R, dtype=jnp.int32)
                .at[pack]
                .set(rows, mode="drop")
            )
            stage = (
                jnp.zeros((cap,), dtype=jnp.int32)
                .at[pack]
                .set(stage + 1, mode="drop")
            )
            g = (
                jnp.zeros((cap,), dtype=jnp.float32)
                .at[pack]
                .set(g_new, mode="drop")
            )
            state = repack_state(state, state_new, pack)
            return (
                step + 1, rows, stage, g,
                n_keep, head,
                dec, ex, gout, admit, done, state,
            )

        def cond(carry):
            _, _, _, _, n_live, head = carry[:6]
            # quit when you can, stream-wide: no live lanes AND an empty
            # ring.  (Live-free steps with pending future arrivals idle at
            # block-guard cost zero.)
            return (n_live > 0) | (head < n_pending)

        init = (
            jnp.int32(0),
            jnp.full((cap,), R, dtype=jnp.int32),
            jnp.zeros((cap,), dtype=jnp.int32),
            jnp.zeros((cap,), dtype=jnp.float32),
            jnp.int32(0),
            jnp.int32(0),
            jnp.zeros((R,), dtype=jnp.bool_),
            jnp.full((R,), T, dtype=jnp.int32),
            jnp.zeros((R,), dtype=jnp.float32),
            jnp.zeros((R,), dtype=jnp.int32),
            jnp.zeros((R,), dtype=jnp.int32),
            self.scorer.init_state(cap),
        )
        (s_f, _, _, _, _, _, dec, ex, gout, admit, done, _) = (
            jax.lax.while_loop(cond, body, init)
        )
        return dec, ex, gout, admit, done, s_f

    def run_stream(
        self,
        batch,
        n: int,
        arrivals=None,
        capacity: int | None = None,
        ring_capacity: int | None = None,
        prepared: bool = False,
    ) -> StreamResult:
        """Continuously stream ``n`` rows through the survivor buffers.

        ``arrivals`` (optional, (n,) nondecreasing ints) gates admission:
        row i cannot be admitted before loop step ``arrivals[i]`` — the
        on-device replay of a request arrival trace (None = everyone is
        already waiting).  ``capacity`` pins the survivor-slot count (the
        concurrency, block-padded; default: all ``n`` rows at once, which
        degenerates to the batch path plus refill plumbing) and
        ``ring_capacity`` pins the admission-ring size (default ``n``) —
        a server passes both fixed so every wave reuses ONE compiled
        trace per (cap, T, chunk_t).  ``prepared=True`` means ``batch``
        is already the scorer-prepared operand.
        """
        plan = self.dplan.plan
        T = plan.T
        if not self.scorer.has_lanes and not self.megakernel:
            raise ValueError(
                "run_stream needs a scorer with per-lane stage scoring "
                "(lane_fn or lane_stage_fn) on the multi-kernel path; "
                "this scorer only supports batch stages"
            )
        if n == 0:
            return StreamResult(
                decisions=np.zeros(0, dtype=bool),
                exit_step=np.zeros(0, dtype=np.int64),
                g_final=np.zeros(0, dtype=np.float32),
                admit_step=np.zeros(0, dtype=np.int64),
                done_step=np.zeros(0, dtype=np.int64),
                steps_run=0,
                occupancy=np.zeros(0, dtype=np.int64),
                capacity=self._cap(capacity or 1),
                scores_computed=0,
                scores_possible=0,
            )
        if self.check_finite:
            check_batch_finite(batch, n)
        cap = self._cap(capacity or n)
        R = max(n, int(ring_capacity or n))
        x = self._cast_operand(batch if prepared else self.scorer.prepare(batch))
        if x.shape[0] < R:
            x = jnp.pad(x, ((0, R - x.shape[0]),) + ((0, 0),) * (x.ndim - 1))
        ring_ids = np.full(R, R, dtype=np.int32)
        ring_ids[:n] = np.arange(n, dtype=np.int32)
        arr = (
            np.zeros(n, dtype=np.int32)
            if arrivals is None
            else np.asarray(arrivals, dtype=np.int32)
        )
        assert arr.shape == (n,)
        assert (np.diff(arr) >= 0).all(), "arrivals must be nondecreasing"
        arr_pad = np.zeros(R, dtype=np.int32)
        arr_pad[:n] = arr
        args = (
            cap, x, jnp.asarray(ring_ids), jnp.asarray(arr_pad), n,
            self.scorer.weights,
        )
        compile_program(self._compiled, self._stream_jit, *args, static=1)
        dec, ex, gout, admit, done, s_f = launch_wave(
            "device", lambda: self._stream_jit(*args)
        )
        steps_run = int(s_f)
        admit = np.asarray(admit, dtype=np.int64)[:n]
        done = np.asarray(done, dtype=np.int64)[:n]
        occ = stream_occupancy(admit, done, steps_run)
        # block-guard billing per loop step, same accounting as the batch
        # path: the live lanes are front-packed, so a guarded kernel
        # computes ceil(live / block) blocks of the W-wide slab
        bn, W = self.scorer.row_block(self.block_n, cap), self.dplan.W
        scores_computed = int(((-(-occ // bn)) * bn * W).sum())
        return StreamResult(
            decisions=np.asarray(dec)[:n].astype(bool),
            exit_step=np.asarray(ex, dtype=np.int64)[:n],
            g_final=np.asarray(gout)[:n],
            admit_step=admit,
            done_step=done,
            steps_run=steps_run,
            occupancy=occ,
            capacity=cap,
            scores_computed=scores_computed,
            scores_possible=n * T,
        )

    # -- grouped (ranking) decide: one verdict per query group ----------

    def _cap_groups(self, n_groups: int, capacity_groups: int | None) -> int:
        from repro.kernels.cascade_kernel import DEFAULT_BLOCK_G

        bg = DEFAULT_BLOCK_G
        n = max(n_groups, capacity_groups or 0, 1)
        return -(-n // bg) * bg

    def _grouped_program(self, k, x, gids_init, rows_init, valid_init, n0, eps_g):
        """Batch grouped cascade: the ``_program`` stage loop with the
        row decide swapped for the GROUP decide (DESIGN.md §12).

        Buffers are (cap_g, B) bucket-layout rectangles — a group is B
        contiguous lanes, exits as a unit, and compaction front-packs
        whole groups (lane order inside a group never changes).  Scores
        accumulate per COLUMN sequentially, the same f32 add order as
        the host oracle, so margin-infinity verdicts are bit-identical
        to ``ranking.host.full_cascade_topk``.  Grouped decides always
        run the multi-kernel path (scorer stage + ``cascade_group_pallas``);
        the fused megakernel has no group semantics.
        """
        self.traces += 1  # trace-time side effect, read by the trace tests
        dp = self.dplan
        S, W = dp.S, dp.W
        cap_g, B = rows_init.shape
        L = cap_g * B
        stage_t0 = jnp.asarray(dp.stage_t0)
        col_valid = jnp.asarray(dp.col_valid)
        eps_g = jnp.asarray(eps_g, dtype=jnp.float32)
        grp = jnp.arange(cap_g, dtype=jnp.int32)
        lane_b = jnp.arange(B, dtype=jnp.int32)

        def body(carry):
            (s, gids, rows2d, valid2d, n_active, g2d,
             verd, exst, marg, n_in_log, state) = carry
            n_in_log = n_in_log.at[s].set(n_active)
            t0 = stage_t0[s]
            rows_flat = rows2d.reshape(L)
            # active groups are front-packed, so live lanes are exactly
            # the first n_active * B — the scorers' block guard still
            # skips retired blocks
            scores, state_new = self.scorer.stage(
                state, t0, t0 + W, rows_flat, x, n_active * B
            )
            scores = jnp.where(col_valid[s][None, :], scores, 0.0)
            scores = jnp.where(valid2d.reshape(L, 1) != 0, scores, 0.0)
            # per-column sequential accumulate: the one f32 add order,
            # shared with the host oracle (bit-parity contract)
            g_flat = g2d.reshape(L)
            for j in range(W):
                g_flat = g_flat + scores[:, j]
            g_new = g_flat.reshape(cap_g, B)
            margin, exit_g = cascade_group_pallas(
                g_new,
                valid2d,
                jnp.broadcast_to(eps_g[s], (cap_g,)),
                k,
                interpret=self.interpret,
                n_live=n_active,
            )
            exit_b = exit_g.astype(bool)  # live-gated inside the kernel
            verdict = group_topk_rows(g_new, valid2d, rows2d, k)
            scat = jnp.where(exit_b, gids, cap_g)
            verd = verd.at[scat].set(verdict, mode="drop")
            exst = exst.at[scat].set(s + 1, mode="drop")
            marg = marg.at[scat].set(margin, mode="drop")
            # whole-GROUP cumsum-prefix compaction: survivors keep their
            # B-lane rectangle; state repacks at lane granularity with
            # the group pack expanded to its lanes
            keep = (grp < n_active) & ~exit_b
            pos = jnp.cumsum(keep.astype(jnp.int32)) - 1
            packg = jnp.where(keep, pos, cap_g)
            n_keep = keep.sum(dtype=jnp.int32)
            gids = (
                jnp.full((cap_g,), cap_g, dtype=jnp.int32)
                .at[packg].set(gids, mode="drop")
            )
            rows2d = (
                jnp.zeros((cap_g, B), dtype=jnp.int32)
                .at[packg].set(rows2d, mode="drop")
            )
            valid2d = (
                jnp.zeros((cap_g, B), dtype=jnp.int32)
                .at[packg].set(valid2d, mode="drop")
            )
            g2d = (
                jnp.zeros((cap_g, B), dtype=jnp.float32)
                .at[packg].set(g_new, mode="drop")
            )
            lane_pack = jnp.where(
                keep[:, None], packg[:, None] * B + lane_b[None, :], L
            ).reshape(L)
            state = repack_state(state, state_new, lane_pack)
            return (
                s + 1, gids, rows2d, valid2d, n_keep, g2d,
                verd, exst, marg, n_in_log, state,
            )

        def cond(carry):
            s, _, _, _, n_active = carry[:5]
            # quit when you can: stop once every group has exited
            return (s < S) & (n_active > 0)

        init = (
            jnp.int32(0),
            gids_init,
            rows_init,
            valid_init,
            jnp.asarray(n0, dtype=jnp.int32),
            jnp.zeros((cap_g, B), dtype=jnp.float32),
            jnp.full((cap_g, k), -1, dtype=jnp.int32),
            jnp.full((cap_g,), S, dtype=jnp.int32),
            jnp.full((cap_g,), jnp.inf, dtype=jnp.float32),
            jnp.zeros((S,), dtype=jnp.int32),
            self.scorer.init_state(L),
        )
        (s_f, gids, rows2d, valid2d, n_f, g2d,
         verd, exst, marg, n_in_log, _) = jax.lax.while_loop(cond, body, init)
        # ran-out groups carry the exact full-cascade ranking; reuse the
        # group kernel at eps = +inf just for its margins
        margin_f, _ = cascade_group_pallas(
            g2d,
            valid2d,
            jnp.full((cap_g,), jnp.inf, dtype=jnp.float32),
            k,
            interpret=self.interpret,
            n_live=n_f,
        )
        verdict_f = group_topk_rows(g2d, valid2d, rows2d, k)
        scat = jnp.where(grp < n_f, gids, cap_g)
        verd = verd.at[scat].set(verdict_f, mode="drop")
        exst = exst.at[scat].set(S, mode="drop")
        marg = marg.at[scat].set(margin_f, mode="drop")
        return verd, exst, marg, s_f, n_f, n_in_log

    def run_grouped(
        self,
        batch,
        group_rows,
        group_valid,
        n_groups: int,
        eps_g,
        k: int,
        capacity_groups: int | None = None,
        prepared: bool = False,
    ) -> GroupedResult:
        """Execute the grouped cascade for ``n_groups`` bucket-laid-out
        query groups on device.

        ``group_rows`` (G, B) holds each group's flat GLOBAL document
        rows into ``batch`` (padding lanes in-bounds but masked),
        ``group_valid`` (G, B) the real-lane mask, ``eps_g`` (S,) the
        per-stage margin thresholds, ``k`` the (static) ranking depth.
        One bucket width B per call — variable widths go through the
        bucketing admission layer, one launch (and one compiled trace)
        per bucket shape.  ``capacity_groups`` pins the group-slot
        capacity so partial flushes reuse the trace.
        """
        plan = self.dplan.plan
        T = plan.T
        group_rows = np.asarray(group_rows, dtype=np.int32)
        group_valid = np.asarray(group_valid)
        if group_rows.ndim != 2 or group_rows.shape != group_valid.shape:
            raise ValueError(
                f"group_rows/group_valid must be matching (G, B) arrays, "
                f"got {group_rows.shape} / {group_valid.shape}"
            )
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        n_docs_real = int(np.asarray(group_valid[:n_groups]).sum())
        if n_groups == 0:
            return GroupedResult(
                verdicts=np.zeros((0, k), dtype=np.int32),
                exit_stage=np.zeros(0, dtype=np.int64),
                margin=np.zeros(0, dtype=np.float32),
                chunk_stats=[],
                scores_computed=0,
                scores_possible=0,
            )
        if self.check_finite:
            check_batch_finite(batch, np.asarray(batch).shape[0])
        B = group_rows.shape[1]
        cap_g = self._cap_groups(n_groups, capacity_groups)
        x = self._cast_operand(batch if prepared else self.scorer.prepare(batch))
        gids = np.full(cap_g, cap_g, dtype=np.int32)
        gids[:n_groups] = np.arange(n_groups, dtype=np.int32)
        rows_init = np.zeros((cap_g, B), dtype=np.int32)
        rows_init[:n_groups] = group_rows[:n_groups]
        valid_init = np.zeros((cap_g, B), dtype=np.int32)
        valid_init[:n_groups] = group_valid[:n_groups].astype(np.int32)
        args = (
            int(k),
            x,
            jnp.asarray(gids),
            jnp.asarray(rows_init),
            jnp.asarray(valid_init),
            n_groups,
            jnp.asarray(eps_g, dtype=jnp.float32),
            self.scorer.weights,
        )
        compile_program(self._compiled, self._grouped_jit, *args, static=1)
        verd, exst, marg, s_f, n_f, n_in_log = launch_wave(
            "device", lambda: self._grouped_jit(*args)
        )
        s_f, n_f = int(s_f), int(n_f)
        n_in_log = np.asarray(n_in_log)
        stages = plan.stages
        bn, W = self.scorer.block_n or self.block_n, self.dplan.W
        chunk_stats = []
        for s in range(s_f):
            n_in = int(n_in_log[s])
            n_next = int(n_in_log[s + 1]) if s + 1 < s_f else n_f
            # group-quantized block billing: a stage scores the full
            # B-lane rectangle of every live group, block-guarded
            chunk_stats.append(
                ChunkStat(
                    t0=stages[s][0],
                    t1=stages[s][1],
                    n_in=n_in,
                    n_exited=n_in - n_next,
                    scores_computed=-(-(n_in * B) // bn) * bn * W,
                )
            )
        return GroupedResult(
            verdicts=np.asarray(verd)[:n_groups],
            exit_stage=np.asarray(exst, dtype=np.int64)[:n_groups],
            margin=np.asarray(marg)[:n_groups],
            chunk_stats=chunk_stats,
            scores_computed=sum(c.scores_computed for c in chunk_stats),
            scores_possible=n_docs_real * T,
        )

    def _grouped_stream_program(
        self, cap_g, k, x, ring_gids, ring_rows, ring_valid, arrivals,
        n_pending, eps_g,
    ):
        """Streaming grouped cascade: the ``_stream_program`` admission
        ring at GROUP-slot granularity.  Each slot is one B-lane group
        rectangle with its own stage index; freed slots refill from the
        ring in arrival order (a pending group occupies exactly one
        slot, so slot-granular refill IS group-granular refill)."""
        self.traces += 1  # trace-time side effect, read by the trace tests
        dp = self.dplan
        S, W, T = dp.S, dp.W, dp.plan.T
        Rg, B = ring_rows.shape  # ring capacity == output size; Rg = trash id
        L = cap_g * B
        stage_t0 = jnp.asarray(dp.stage_t0)
        col_valid = jnp.asarray(dp.col_valid)
        eps_g_arr = jnp.asarray(eps_g, dtype=jnp.float32)
        slot = jnp.arange(cap_g, dtype=jnp.int32)
        ridx = jnp.arange(Rg, dtype=jnp.int32)
        lane_b = jnp.arange(B, dtype=jnp.int32)

        def body(carry):
            (step, gids, rows2d, valid2d, stage, g2d, n_live, head,
             verd, exst, marg, admit, done, state) = carry
            arrived = jnp.sum(
                (ridx >= head) & (ridx < n_pending) & (arrivals <= step),
                dtype=jnp.int32,
            )
            kadm = jnp.minimum(cap_g - n_live, arrived)
            src = jnp.clip(head + (slot - n_live), 0, Rg - 1)
            is_new = (slot >= n_live) & (slot < n_live + kadm)
            gids = jnp.where(is_new, jnp.take(ring_gids, src), gids)
            rows2d = jnp.where(
                is_new[:, None], jnp.take(ring_rows, src, axis=0), rows2d
            )
            valid2d = jnp.where(
                is_new[:, None], jnp.take(ring_valid, src, axis=0), valid2d
            )
            stage = jnp.where(is_new, 0, stage)
            g2d = jnp.where(is_new[:, None], 0.0, g2d)
            admit = admit.at[jnp.where(is_new, gids, Rg)].set(step, mode="drop")
            n_live = n_live + kadm
            head = head + kadm
            # mixed-stage scoring: per-slot stage gathered to per-lane
            t0_slot = jnp.take(stage_t0, stage)
            t0_lane = jnp.repeat(t0_slot, B)
            stop = stage >= S - 1
            scores, state_new = self.scorer.lane_stage(
                state, t0_lane, rows2d.reshape(L), x, n_live * B
            )
            colmask = jnp.repeat(
                jnp.take(col_valid, stage, axis=0), B, axis=0
            )  # (L, W): each slot's stage columns, per lane
            scores = jnp.where(colmask, scores, 0.0)
            scores = jnp.where(valid2d.reshape(L, 1) != 0, scores, 0.0)
            g_flat = g2d.reshape(L)
            for j in range(W):
                g_flat = g_flat + scores[:, j]
            g_new = g_flat.reshape(cap_g, B)
            margin, exit_g = cascade_group_pallas(
                g_new,
                valid2d,
                jnp.take(eps_g_arr, stage),
                k,
                interpret=self.interpret,
                n_live=n_live,
            )
            exit_b = exit_g.astype(bool)
            slot_live = slot < n_live
            ran_out = slot_live & ~exit_b & stop
            fin = (slot_live & exit_b) | ran_out
            verdict = group_topk_rows(g_new, valid2d, rows2d, k)
            exst_val = jnp.where(exit_b, stage + 1, S)
            scat = jnp.where(fin, gids, Rg)
            verd = verd.at[scat].set(verdict, mode="drop")
            exst = exst.at[scat].set(exst_val, mode="drop")
            marg = marg.at[scat].set(margin, mode="drop")
            done = done.at[scat].set(step, mode="drop")
            keep = slot_live & ~exit_b & ~stop
            pos = jnp.cumsum(keep.astype(jnp.int32)) - 1
            packg = jnp.where(keep, pos, cap_g)
            n_keep = keep.sum(dtype=jnp.int32)
            gids = (
                jnp.full((cap_g,), Rg, dtype=jnp.int32)
                .at[packg].set(gids, mode="drop")
            )
            rows2d = (
                jnp.zeros((cap_g, B), dtype=jnp.int32)
                .at[packg].set(rows2d, mode="drop")
            )
            valid2d = (
                jnp.zeros((cap_g, B), dtype=jnp.int32)
                .at[packg].set(valid2d, mode="drop")
            )
            stage = (
                jnp.zeros((cap_g,), dtype=jnp.int32)
                .at[packg].set(stage + 1, mode="drop")
            )
            g2d = (
                jnp.zeros((cap_g, B), dtype=jnp.float32)
                .at[packg].set(g_new, mode="drop")
            )
            lane_pack = jnp.where(
                keep[:, None], packg[:, None] * B + lane_b[None, :], L
            ).reshape(L)
            state = repack_state(state, state_new, lane_pack)
            return (
                step + 1, gids, rows2d, valid2d, stage, g2d,
                n_keep, head,
                verd, exst, marg, admit, done, state,
            )

        def cond(carry):
            n_live, head = carry[6], carry[7]
            return (n_live > 0) | (head < n_pending)

        init = (
            jnp.int32(0),
            jnp.full((cap_g,), Rg, dtype=jnp.int32),
            jnp.zeros((cap_g, B), dtype=jnp.int32),
            jnp.zeros((cap_g, B), dtype=jnp.int32),
            jnp.zeros((cap_g,), dtype=jnp.int32),
            jnp.zeros((cap_g, B), dtype=jnp.float32),
            jnp.int32(0),
            jnp.int32(0),
            jnp.full((Rg, k), -1, dtype=jnp.int32),
            jnp.full((Rg,), S, dtype=jnp.int32),
            jnp.full((Rg,), jnp.inf, dtype=jnp.float32),
            jnp.zeros((Rg,), dtype=jnp.int32),
            jnp.zeros((Rg,), dtype=jnp.int32),
            self.scorer.init_state(L),
        )
        out = jax.lax.while_loop(cond, body, init)
        (s_f, _, _, _, _, _, _, _, verd, exst, marg, admit, done, _) = out
        return verd, exst, marg, admit, done, s_f

    def run_stream_grouped(
        self,
        batch,
        group_rows,
        group_valid,
        n_groups: int,
        eps_g,
        k: int,
        arrivals=None,
        capacity_groups: int | None = None,
        ring_capacity: int | None = None,
        prepared: bool = False,
    ) -> GroupedStreamResult:
        """Continuously stream query groups through group-slot buffers.

        The grouped analogue of ``run_stream``: groups wait in an
        arrival-order admission ring and refill freed GROUP slots (B
        lanes each) mid-cascade; per-slot stage indices mix rookies with
        veterans, each decided by its own stage's margin threshold
        through the same ``cascade_group_pallas`` kernel as the batch
        path.  One bucket width B per executor run.
        """
        plan = self.dplan.plan
        T = plan.T
        if not self.scorer.has_lanes:
            raise ValueError(
                "run_stream_grouped needs a scorer with per-lane stage "
                "scoring (lane_fn or lane_stage_fn)"
            )
        group_rows = np.asarray(group_rows, dtype=np.int32)
        group_valid = np.asarray(group_valid)
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        n_docs_real = int(np.asarray(group_valid[:n_groups]).sum())
        if n_groups == 0:
            return GroupedStreamResult(
                verdicts=np.zeros((0, k), dtype=np.int32),
                exit_stage=np.zeros(0, dtype=np.int64),
                margin=np.zeros(0, dtype=np.float32),
                admit_step=np.zeros(0, dtype=np.int64),
                done_step=np.zeros(0, dtype=np.int64),
                steps_run=0,
                occupancy=np.zeros(0, dtype=np.int64),
                capacity_groups=self._cap_groups(1, capacity_groups),
                scores_computed=0,
                scores_possible=0,
            )
        if self.check_finite:
            check_batch_finite(batch, np.asarray(batch).shape[0])
        B = group_rows.shape[1]
        cap_g = self._cap_groups(capacity_groups or n_groups, capacity_groups)
        Rg = max(n_groups, int(ring_capacity or n_groups))
        x = self._cast_operand(batch if prepared else self.scorer.prepare(batch))
        ring_gids = np.full(Rg, Rg, dtype=np.int32)
        ring_gids[:n_groups] = np.arange(n_groups, dtype=np.int32)
        ring_rows = np.zeros((Rg, B), dtype=np.int32)
        ring_rows[:n_groups] = group_rows[:n_groups]
        ring_valid = np.zeros((Rg, B), dtype=np.int32)
        ring_valid[:n_groups] = group_valid[:n_groups].astype(np.int32)
        arr = (
            np.zeros(n_groups, dtype=np.int32)
            if arrivals is None
            else np.asarray(arrivals, dtype=np.int32)
        )
        assert arr.shape == (n_groups,)
        assert (np.diff(arr) >= 0).all(), "arrivals must be nondecreasing"
        arr_pad = np.zeros(Rg, dtype=np.int32)
        arr_pad[:n_groups] = arr
        args = (
            cap_g,
            int(k),
            x,
            jnp.asarray(ring_gids),
            jnp.asarray(ring_rows),
            jnp.asarray(ring_valid),
            jnp.asarray(arr_pad),
            n_groups,
            jnp.asarray(eps_g, dtype=jnp.float32),
            self.scorer.weights,
        )
        compile_program(self._compiled, self._grouped_stream_jit, *args, static=2)
        verd, exst, marg, admit, done, s_f = launch_wave(
            "device", lambda: self._grouped_stream_jit(*args)
        )
        steps_run = int(s_f)
        admit = np.asarray(admit, dtype=np.int64)[:n_groups]
        done = np.asarray(done, dtype=np.int64)[:n_groups]
        occ = stream_occupancy(admit, done, steps_run)
        # group-quantized block billing per loop step: live group slots
        # score their full B-lane rectangles, block-guarded
        bn, W = self.scorer.block_n or self.block_n, self.dplan.W
        scores_computed = int(((-(-(occ * B) // bn)) * bn * W).sum())
        return GroupedStreamResult(
            verdicts=np.asarray(verd)[:n_groups],
            exit_stage=np.asarray(exst, dtype=np.int64)[:n_groups],
            margin=np.asarray(marg)[:n_groups],
            admit_step=admit,
            done_step=done,
            steps_run=steps_run,
            occupancy=occ,
            capacity_groups=cap_g,
            scores_computed=scores_computed,
            scores_possible=n_docs_real * T,
        )
