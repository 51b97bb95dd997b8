"""Sharded data-parallel cascade executor: the device stage loop under
``shard_map`` over a mesh's ``"data"`` axis.

``kernels/device_executor.py`` fused the whole ``CascadePlan`` into one
jit'd ``lax.while_loop`` on a single device, but its per-stage row gather
and O(cap) bookkeeping scale with the full batch capacity — the
batch >= 4096 gather-scaling wall recorded in EXPERIMENTS.md.  The serving
north star (heavy traffic, many chips) needs the batch axis split over
devices, with each device paying only for ITS rows.

``ShardedDeviceExecutor`` runs the same stage loop data-parallel
(DESIGN.md §6):

* **Per-shard survivor buffers.**  The global microbatch is split into
  ``shards`` contiguous slices of the (possibly sorted) row order.  Each
  shard carries its own front-packed survivor state — operand rows
  ``xbuf``, partial sums ``gbuf``, global row ids ``idbuf`` — and runs
  scoring, decide and cumsum-prefix compaction entirely locally: there are
  NO cross-shard gathers or scatters on the hot path.
* **psum'd global early exit.**  The ``while_loop`` condition reads a
  replicated total live count (``lax.psum`` of the per-shard counts,
  computed once per stage in the body), so the whole mesh quits the moment
  every row everywhere has exited.  A shard that empties early keeps
  stepping, but its score kernels' live-count block guard (``n_valid=0``)
  skips all compute — it idles at block granularity, not at batch cost.
* **Survivor rebalancing (beyond-paper, opt-in).**  Contiguous slices of a
  sorted order drain unevenly: easy-row shards empty while hard-row shards
  stay full, and stage latency is the SLOWEST shard's.  With
  ``rebalance=True``, whenever occupancy skews past ``rebalance_ratio``
  AND the skew is worth at least one kernel row-block, the shards
  ``all_gather`` their survivor buffers, repack them globally (stable:
  shard-major front-packed order) and re-split evenly — an all-to-all-style
  repack that costs one collective and only fires when triggered
  (``lax.cond``).  Row ids travel with the data, so results still scatter
  to absolute row indices.
* **Exactly-once result scatter.**  Each shard accumulates exits into
  global-size (cap_g,) output arrays at the rows' ids; a row lives on
  exactly one shard at any stage, so every id is written exactly once
  across the mesh and a final ``psum`` assembles the batch.
* **2-D ``("data", "model")`` mesh (DESIGN.md §13, opt-in).**  On a mesh
  carrying a ``"model"`` axis of size M > 1, every stage's param slab is
  split into M contiguous column slices
  (``launch.shardings.stage_column_slices`` via the scorer's
  ``model_partition`` hook), each model shard scores ONLY its
  ``w_local = ceil(W/M)`` columns, and a single ``lax.psum`` over
  ``"model"`` — the one collective the stage step gains — reassembles
  the full (cap_l, W) score block bit-exactly (disjoint column support,
  zeros elsewhere; adding exact zeros preserves f32 bits).  Everything
  downstream of the psum (decide, compaction, admission, rebalance,
  result scatter) is replicated across model shards and collective-free
  over ``"model"``: survivor buffers stay strictly local to ``"data"``
  shards.  ``model_shards=1`` takes the untouched 1-D program — traces,
  billing and bits are byte-identical to a mesh with no model axis.

Semantics are bit-identical to ``DeviceExecutor`` and the host
``ChunkedExecutor`` (per-row compute is lane-local in every kernel, so
shard placement cannot change a score, a partial sum, or an exit) —
asserted at shards 1/2/4, both modes, in ``tests/test_sharded.py``.
One jit trace per (N, T, chunk_t, shards), same fixed-capacity argument
as the single-device executor.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import PartitionSpec as P

from repro import tracing
from repro.core.executor import CascadePlan, ChunkStat, ExecutorResult
from repro.kernels import megakernel as mk
from repro.kernels.cascade_kernel import (
    cascade_chunk_pallas,
    cascade_group_pallas,
    cascade_lane_pallas,
)
from repro.kernels.device_executor import (
    DEFAULT_BLOCK_N,
    BoundScorer,
    DevicePlan,
    GroupedResult,
    StreamResult,
    WaveFailure,  # noqa: F401 — re-export: sharded waves raise the same type
    check_batch_finite,
    compile_program,
    group_topk_rows,
    launch_wave,
    pad_rows,
    repack_state,
    stream_occupancy,
)

from repro.kernels.interpret import platform_interpret
from repro.launch.shardings import model_stacked_shardings, split_columns

__all__ = ["ShardedDeviceExecutor", "critical_blocks"]

DATA_AXIS = "data"
MODEL_AXIS = "model"


def critical_blocks(per_shard_n_in: np.ndarray, block_n: int) -> int:
    """Sharded latency proxy over a ``last_run_info["per_shard_n_in"]``
    (shards, stages) occupancy log: a stage is as slow as its fullest
    shard, so sum the per-stage MAX over shards of live kernel
    row-blocks.  The single accounting shared by the sharded benchmark,
    the CI perf gate and the test suite."""
    occ = np.asarray(per_shard_n_in)
    if occ.size == 0:
        return 0
    return int(sum((-(-occ[:, s] // block_n)).max() for s in range(occ.shape[1])))


class ShardedDeviceExecutor:
    """Runs a ``CascadePlan`` as one compiled program per shard of a mesh.

    Drop-in for ``DeviceExecutor`` (same ``run`` signature, same
    ``ExecutorResult``, same ``traces`` accounting) with the batch split
    over ``mesh``'s ``"data"`` axis.  ``rebalance`` enables the skew-
    triggered survivor repack; ``rebalance_ratio`` is the occupancy-skew
    trigger (max shard count > ratio x balanced count, in addition to the
    at-least-one-row-block savings guard).

    After every ``run`` the per-shard accounting lands in
    ``last_run_info``: per-shard per-stage occupancy, per-shard billed
    scores, stages executed, and which stages triggered a rebalance —
    the raw material for ``benchmarks/bench_sharded.py``.
    """

    def __init__(
        self,
        plan: CascadePlan | DevicePlan,
        scorer: BoundScorer,
        mesh: jax.sharding.Mesh,
        block_n: int = DEFAULT_BLOCK_N,
        interpret: bool | None = None,
        rebalance: bool = False,
        rebalance_ratio: float = 1.25,
        megakernel: bool | None = None,
        check_finite: bool = False,
    ):
        self.dplan = plan if isinstance(plan, DevicePlan) else DevicePlan.from_plan(plan)
        if scorer.width != self.dplan.W:
            raise ValueError(
                f"scorer width {scorer.width} != plan stage width {self.dplan.W}"
            )
        if DATA_AXIS not in mesh.axis_names:
            raise ValueError(
                f"mesh must carry a {DATA_AXIS!r} axis; got {mesh.axis_names}"
            )
        self.shards = int(mesh.shape[DATA_AXIS])
        self.model_shards = int(dict(mesh.shape).get(MODEL_AXIS, 1))
        # same auto policy as DeviceExecutor: fused stage-step megakernel
        # by default when the scorer carries f32 slabs (bit-identical),
        # explicit opt-in for quantized slabs (tolerance-oracle parity).
        # The 2-D path has no fused stage step (the megakernel has no
        # model-axis psum seam), so auto turns it off there.
        if megakernel is None:
            megakernel = (
                self.model_shards == 1
                and scorer.slabs is not None
                and scorer.slabs.quant == "f32"
            )
        if megakernel and scorer.slabs is None:
            raise ValueError(
                "megakernel=True needs a scorer with ParamSlabs (factory-"
                "built scorers carry them; custom scorers fall back to the "
                "multi-kernel path)"
            )
        if megakernel and scorer.stateful:
            raise ValueError(
                "megakernel=True is incompatible with a stateful scorer "
                "(non-empty state_spec): the fused stage step has no "
                "survivor-state carry.  Use the multi-kernel path "
                "(megakernel=False / the auto default)."
            )
        if self.model_shards > 1:
            mesh_desc = (
                f"{self.shards}x{self.model_shards} ({DATA_AXIS!r}, "
                f"{MODEL_AXIS!r}) mesh"
            )
            if megakernel:
                raise ValueError(
                    f"megakernel=True is unavailable on a {mesh_desc}: the "
                    "fused stage step has no model-axis psum seam.  Use the "
                    "multi-kernel path (megakernel=None/False) or "
                    "model_shards=1."
                )
            if scorer.stateful:
                raise ValueError(
                    f"a {mesh_desc} cannot carry a stateful scorer "
                    "(non-empty state_spec): per-row state would need the "
                    "model-axis collective the 2-D path reserves for the "
                    "score psum.  Use model_shards=1."
                )
            if scorer.model_partition is None:
                raise ValueError(
                    f"a {mesh_desc} needs a scorer with a model_partition "
                    "hook (factory-built scorers carry one; custom scorers "
                    "must split their stage slabs into contiguous column "
                    "slices — see BoundScorer.model_partition)"
                )
            if self.model_shards > self.dplan.W:
                raise ValueError(
                    f"{mesh_desc} has more model shards than the plan's "
                    f"stage width W={self.dplan.W}: a stage slab splits "
                    f"into at most W contiguous column slices "
                    f"(compile with model_shards <= {self.dplan.W})"
                )
        self.megakernel = bool(megakernel)
        self.scorer = scorer
        self.check_finite = bool(check_finite)
        self.mesh = mesh
        self.block_n = max(1, int(block_n))
        # kernels run interpreted exactly where the mesh's devices are CPUs
        self.interpret = (
            platform_interpret(mesh.devices.flat[0].platform)
            if interpret is None
            else bool(interpret)
        )
        self._compiled: set = set()  # argument signatures compiled so far
        self.rebalance = bool(rebalance)
        self.rebalance_ratio = float(rebalance_ratio)
        self.traces = 0
        self.last_run_info: dict | None = None
        if self.model_shards > 1:
            self._w_local, self._w_global = split_columns(
                self.dplan.W, self.model_shards
            )
            mparams, self._col_fn = scorer.model_partition(self.model_shards)
            if jax.tree_util.tree_leaves(mparams):
                # one slab slice per model shard, placed at construction:
                # the per-device param memory genuinely shrinks by ~M
                mparams = jax.device_put(
                    mparams, model_stacked_shardings(mparams, mesh)
                )
            self._mparams = mparams
            self._jit = jax.jit(self._program2d)
        else:
            self._jit = jax.jit(self._program)
        self._stream_jit = jax.jit(self._stream_program, static_argnums=(0,))
        # grouped (ranking) program: k is static — verdict extraction
        # unrolls k segment-max passes per shard
        self._grouped_jit = jax.jit(self._grouped_program, static_argnums=(0,))

    def _cap_local(self, n: int) -> int:
        """Per-shard buffer capacity: the balanced share, block-padded."""
        per = -(-max(n, 1) // self.shards)
        return -(-per // self.block_n) * self.block_n

    def _cap(self, n: int) -> int:
        """Global padded capacity (``shards`` x the per-shard capacity)."""
        return self.shards * self._cap_local(n)

    def _cast_operand(self, x):
        """Matrix-variant quantized storage (see
        ``DeviceExecutor._cast_operand``): cast the prepared operand to
        the slab storage dtype once per run."""
        sl = self.scorer.slabs
        if (
            self.megakernel
            and sl is not None
            and sl.x_dtype is not None
            and x.dtype != sl.x_dtype
        ):
            return x.astype(sl.x_dtype)
        return x

    # -- the per-shard program ------------------------------------------

    def _per_shard(self, xbuf, idbuf, n_live, mparams=None):
        """One shard's view: identical loop body to ``DeviceExecutor``,
        plus the psum'd exit total and the optional rebalance step.

        ``xbuf``/``idbuf``/``n_live`` arrive with a leading length-1 shard
        axis (shard_map splits the mesh axis); outputs keep it so every
        out_spec is sharded over ``"data"`` (no replicated out_specs —
        ``check_vma=False`` friendly).

        On a 2-D mesh (``model_shards > 1``) the SAME body runs with two
        changes, both resolved at trace time so the 1-D trace is
        untouched: score production goes through the scorer's
        ``model_partition`` column slice + one psum over ``"model"``
        (``mparams`` carries this shard's slab slice, leading length-1
        model axis), and outputs gain a second leading length-1 axis so
        every out_spec can be ``P("data", "model")``.
        """
        dp = self.dplan
        S, W, T = dp.S, dp.W, dp.plan.T
        shards = self.shards
        two_d = self.model_shards > 1
        xbuf = xbuf[0]
        idbuf = idbuf[0]
        n_live = n_live[0]
        if two_d:
            mp = jax.tree_util.tree_map(lambda a: a[0], mparams)
            c0 = jax.lax.axis_index(MODEL_AXIS) * self._w_local
        cap_l = idbuf.shape[0]
        cap_g = shards * cap_l  # == the trash/sentinel id
        stage_t0 = jnp.asarray(dp.stage_t0)
        eps_pos = jnp.asarray(dp.eps_pos)
        eps_neg = jnp.asarray(dp.eps_neg)
        col_valid = jnp.asarray(dp.col_valid)
        lane = jnp.arange(cap_l, dtype=jnp.int32)
        bn_bill = self.scorer.row_block(self.block_n, cap_l)

        def _rebalance(xbuf, state, gbuf, idbuf, n_live, counts, total):
            """All-gather the survivor buffers, repack globally (stable,
            shard-major), re-split evenly.  Ids ride along, so ownership
            moves but result scatter is unaffected.  The survivor-state
            pytree is bundled with the operand payload: its per-lane
            leaves migrate shards with their rows (a no-op for stateless
            scorers — the tree is empty)."""
            k = jax.lax.axis_index(DATA_AXIS)
            valid = (
                jnp.arange(cap_l, dtype=jnp.int32)[None, :] < counts[:, None]
            ).reshape(cap_g)
            pos = jnp.cumsum(valid.astype(jnp.int32)) - 1
            scat = jnp.where(valid, pos, cap_g)
            base, rem = total // shards, total % shards
            start = k * base + jnp.minimum(k, rem)
            cnt = base + (k < rem).astype(jnp.int32)

            def migrate(buf):
                # gather -> global stable repack -> even re-split, one
                # per-lane leaf at a time (operand and state alike)
                with jax.named_scope(tracing.COLLECTIVE):
                    flat = jax.lax.all_gather(buf, DATA_AXIS).reshape(
                        (cap_g,) + buf.shape[1:]
                    )
                packed = (
                    jnp.zeros_like(flat).at[scat].set(flat, mode="drop")
                )
                return jax.lax.dynamic_slice(
                    packed,
                    (start,) + (0,) * (packed.ndim - 1),
                    (cap_l,) + packed.shape[1:],
                )

            xbuf = migrate(xbuf)
            state = jax.tree_util.tree_map(migrate, state)
            gbuf = migrate(gbuf)
            with jax.named_scope(tracing.COLLECTIVE):
                flat_id = jax.lax.all_gather(idbuf, DATA_AXIS).reshape(cap_g)
            packed_id = (
                jnp.full((cap_g,), cap_g, dtype=jnp.int32)
                .at[scat]
                .set(flat_id, mode="drop")
            )
            idbuf = jax.lax.dynamic_slice(packed_id, (start,), (cap_l,))
            return xbuf, state, gbuf, idbuf, cnt

        # a stage's ops are compaction, but for its scoring and decide
        # kernels and its collectives (the inner scopes)
        @jax.named_scope(tracing.COMPACT)
        def body(carry):
            # fused stage semantics mirror DeviceExecutor._program's body
            # (score -> mask -> decide -> exit scatter -> cumsum-prefix
            # compaction), with the scatter retargeted from buffer rows to
            # global ids — a semantics change there must be replayed here
            # (the cross-executor parity tests in tests/test_sharded.py
            # catch a skew)
            (s, xbuf, gbuf, idbuf, n_live, total, dec, ex, gout,
             n_in_log, reb_log, state) = carry
            n_in_log = n_in_log.at[s].set(n_live)
            t0 = stage_t0[s]
            if self.megakernel:
                # ONE fused kernel over the shard-local survivor buffer
                # (which IS the gathered operand here — identity gather),
                # same contract as DeviceExecutor's batch branch
                with jax.named_scope(tracing.SCORE_DECIDE):
                    g_new, active, dpos, ex_rel, pack, n_keep = (
                        mk.mega_stage_pallas(
                            self.scorer.slabs, xbuf, gbuf, s, t0, n_live,
                            eps_pos, eps_neg,
                            block_n=bn_bill,
                            interpret=self.interpret,
                        )
                    )
                state_new = state  # megakernel path is stateless-only
            else:
                with jax.named_scope(tracing.SCORE_DECIDE):
                    if two_d:
                        # each model shard scores ONLY its contiguous column
                        # slice [c0, c0 + w_local) of stage s, scatters it
                        # into a zeroed (cap_l, w_global) block, and ONE psum
                        # over "model" — the single collective this stage
                        # step gains — reassembles the full block bit-exactly
                        # (disjoint column support; adding exact zeros
                        # preserves f32 bits)
                        scores_l = self._col_fn(mp, xbuf, lane, s, t0, c0, n_live)
                        block = jax.lax.dynamic_update_slice(
                            jnp.zeros((cap_l, self._w_global), dtype=jnp.float32),
                            scores_l.astype(jnp.float32),
                            (jnp.int32(0), c0),
                        )
                        with jax.named_scope(tracing.COLLECTIVE):
                            scores = jax.lax.psum(block, MODEL_AXIS)[:, :W]
                        state_new = state  # 2-D path is stateless-only
                    else:
                        # the survivor buffer IS the row set, so the scorer's
                        # gather is the identity over cap_l local rows (never
                        # the global batch)
                        scores, state_new = self.scorer.stage(
                            state, t0, t0 + W, lane, xbuf, n_live
                        )
                    scores = jnp.where(col_valid[s][None, :], scores, 0.0)
                    g_new, active, dpos, ex_rel = cascade_chunk_pallas(
                        gbuf,
                        scores,
                        eps_pos[s],
                        eps_neg[s],
                        0,
                        block_n=self.block_n,
                        interpret=self.interpret,
                        n_valid=n_live,
                    )
                # cumsum-prefix compaction, local to the shard
                keep = active.astype(bool) & (lane < n_live)
                pos = jnp.cumsum(keep.astype(jnp.int32)) - 1
                pack = jnp.where(keep, pos, cap_l)
                n_keep = keep.sum(dtype=jnp.int32)
            lane_valid = lane < n_live
            newly = lane_valid & (ex_rel > 0)
            # exactly-once exit scatter: ids of retired/padding lanes aim
            # at cap_g, out of bounds of the (cap_g,) accumulators
            scat = jnp.where(newly, idbuf, cap_g)
            dec = dec.at[scat].set(dpos, mode="drop")
            ex = ex.at[scat].set(ex_rel + t0, mode="drop")
            gout = gout.at[scat].set(g_new, mode="drop")
            xbuf = jnp.zeros_like(xbuf).at[pack].set(xbuf, mode="drop")
            gbuf = jnp.zeros_like(gbuf).at[pack].set(g_new, mode="drop")
            idbuf = (
                jnp.full((cap_l,), cap_g, dtype=jnp.int32)
                .at[pack]
                .set(idbuf, mode="drop")
            )
            state = repack_state(state, state_new, pack)
            n_live = n_keep
            # occupancy census: one small all_gather per stage drives both
            # the replicated exit total and the rebalance trigger
            with jax.named_scope(tracing.COLLECTIVE):
                counts = jax.lax.all_gather(n_live, DATA_AXIS)
            total = counts.sum(dtype=jnp.int32)
            if self.rebalance:
                balanced = -(-total // shards)
                worth_a_block = (
                    -(-counts.max() // bn_bill) > -(-balanced // bn_bill)
                )
                skewed = (
                    counts.max().astype(jnp.float32) * shards
                    > self.rebalance_ratio * total.astype(jnp.float32)
                )
                trigger = (total > 0) & worth_a_block & skewed
                reb_log = reb_log.at[s].set(trigger.astype(jnp.int32))
                xbuf, state, gbuf, idbuf, n_live = jax.lax.cond(
                    trigger,
                    lambda a: _rebalance(*a, counts, total),
                    lambda a: a,
                    (xbuf, state, gbuf, idbuf, n_live),
                )
            return (
                s + 1, xbuf, gbuf, idbuf, n_live, total, dec, ex, gout,
                n_in_log, reb_log, state,
            )

        def cond(carry):
            s = carry[0]
            total = carry[5]
            # quit when you can, mesh-wide: the psum'd live total hits zero
            return (s < S) & (total > 0)

        with jax.named_scope(tracing.COLLECTIVE):
            total0 = jax.lax.psum(n_live, DATA_AXIS)
        init = (
            jnp.int32(0),
            xbuf,
            jnp.zeros((cap_l,), dtype=jnp.float32),
            idbuf,
            n_live,
            total0,
            jnp.zeros((cap_g,), dtype=jnp.int32),
            jnp.zeros((cap_g,), dtype=jnp.int32),
            jnp.zeros((cap_g,), dtype=jnp.float32),
            jnp.zeros((S,), dtype=jnp.int32),
            jnp.zeros((S,), dtype=jnp.int32),
            self.scorer.init_state(cap_l),
        )
        (s_f, xbuf, gbuf, idbuf, n_live, total, dec, ex, gout,
         n_in_log, reb_log, _) = jax.lax.while_loop(cond, body, init)
        # rows that never exited: classified by the full ensemble score,
        # written through the same exactly-once id scatter
        with jax.named_scope(tracing.FINALIZE):
            lane_valid = lane < n_live
            scat = jnp.where(lane_valid, idbuf, cap_g)
            dec = dec.at[scat].set(
                (gbuf >= jnp.float32(dp.plan.beta)).astype(jnp.int32), mode="drop"
            )
            ex = ex.at[scat].set(jnp.full((cap_l,), T, jnp.int32), mode="drop")
            gout = gout.at[scat].set(gbuf, mode="drop")
            with jax.named_scope(tracing.COLLECTIVE):
                dec = jax.lax.psum(dec, DATA_AXIS)
                ex = jax.lax.psum(ex, DATA_AXIS)
                gout = jax.lax.psum(gout, DATA_AXIS)
        lead = (1, 1) if two_d else (1,)
        one = lambda a: jnp.reshape(a, lead + a.shape)  # noqa: E731
        return (
            one(dec), one(ex), one(gout), one(s_f), one(n_live),
            one(n_in_log), one(reb_log),
        )

    def _program(self, x, idbuf, n_live0):
        self.traces += 1  # trace-time side effect, read by the trace tests
        shards = self.shards
        cap_l = idbuf.shape[1]
        # distribute the operand rows: each shard receives ONLY its cap_l
        # rows (gathered by id here, outside shard_map, so the per-shard
        # working set is O(cap_l), not O(batch))
        xbuf = jnp.take(x, idbuf.reshape(-1), axis=0).reshape(
            (shards, cap_l) + x.shape[1:]
        )
        sharded = jax.shard_map(
            self._per_shard,
            mesh=self.mesh,
            in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS)),
            out_specs=(P(DATA_AXIS),) * 7,
            check_vma=False,
        )
        return sharded(xbuf, idbuf, n_live0)

    def _program2d(self, x, idbuf, n_live0, mparams):
        """The 2-D ``("data", "model")`` launch (DESIGN.md §13): survivor
        buffers sharded over ``"data"`` exactly as in ``_program``, the
        operand replicated over ``"model"`` (in_specs that don't mention
        an axis replicate over it), and the scorer's stage-stacked slab
        slices split one per model shard (``in_specs=P("model")`` on the
        leading axis).  Outputs carry two leading length-1 axes so every
        out_spec is ``P("data", "model")`` — no replicated out_specs,
        same ``check_vma=False`` convention as the 1-D program."""
        self.traces += 1  # trace-time side effect, read by the trace tests
        shards = self.shards
        cap_l = idbuf.shape[1]
        # distribute the operand rows by id, exactly like _program: the
        # per-shard working set stays O(cap_l), not O(batch)
        xbuf = jnp.take(x, idbuf.reshape(-1), axis=0).reshape(
            (shards, cap_l) + x.shape[1:]
        )
        mp_specs = jax.tree_util.tree_map(lambda _: P(MODEL_AXIS), mparams)
        sharded = jax.shard_map(
            self._per_shard,
            mesh=self.mesh,
            in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS), mp_specs),
            out_specs=(P(DATA_AXIS, MODEL_AXIS),) * 7,
            check_vma=False,
        )
        return sharded(xbuf, idbuf, n_live0, mparams)

    # -- host entry -----------------------------------------------------

    def run(
        self,
        batch,
        n: int,
        row_order=None,
        capacity: int | None = None,
        prepared: bool = False,
    ) -> ExecutorResult:
        """Execute the cascade for ``n`` rows, data-parallel over the mesh.

        Same contract as ``DeviceExecutor.run``: ``row_order`` is the
        initial active-set ordering (split contiguously across shards, so
        a sorted order keeps easy rows clustered — the rebalance step
        exists exactly because such slices drain unevenly), ``capacity``
        pins the GLOBAL buffer size so variable flush sizes reuse one
        trace, ``prepared=True`` skips ``scorer.prepare``.  The shards
        are dealt on the host, so a device rows buffer costs one read
        more.
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
        if self.check_finite:
            check_batch_finite(batch, n)
        shards = self.shards
        if capacity is not None and capacity < n:
            # same error contract as compile()'s backend negotiation
            # (DESIGN.md §7): name what was asked and what would fit
            raise ValueError(
                f"capacity {capacity} cannot hold n={n} rows on a "
                f"{shards}x{self.model_shards} ({DATA_AXIS!r}, "
                f"{MODEL_AXIS!r}) mesh: the flush capacity pins the "
                f"global buffer, split into {shards} data-shard slices "
                f"block-padded to {self.block_n} — pass capacity >= n "
                "(or None to size from the batch)"
            )
        with TraceAnnotation(tracing.RUN_DISPATCH):
            cap_l = self._cap_local(max(n, capacity or 0))
            cap_g = shards * cap_l
            if not prepared:
                batch = self.scorer.prepare(pad_rows(batch, cap_g))
            x = pad_rows(self._cast_operand(batch), cap_g)
            reads = 1
            if isinstance(row_order, jax.Array):
                # a device rows buffer (the sort-key program's): the
                # shards are dealt on the host, so it is read once here
                row_order = np.asarray(row_order)[:n]
                reads += 1
            order = (
                np.arange(n, dtype=np.int32)
                if row_order is None
                else np.asarray(row_order, dtype=np.int32)
            )
            if order.shape != (n,):
                raise ValueError(
                    f"row_order must be a ({n},)-shaped ordering of the "
                    f"batch rows, got shape {tuple(order.shape)}"
                )
            # balanced contiguous assignment: shard k takes the k-th slice of
            # the ordered rows (ids travel with the rows from here on)
            base, rem = divmod(n, shards)
            idbuf = np.full((shards, cap_l), cap_g, dtype=np.int32)
            n_live0 = np.zeros(shards, dtype=np.int32)
            start = 0
            for k in range(shards):
                cnt = base + (1 if k < rem else 0)
                idbuf[k, :cnt] = order[start : start + cnt]
                n_live0[k] = cnt
                start += cnt
            args = (x, jnp.asarray(idbuf), jnp.asarray(n_live0))
            if self.model_shards > 1:
                args += (self._mparams,)
            compile_program(self._compiled, self._jit, *args)
            out = launch_wave("sharded", lambda: self._jit(*args))
        with TraceAnnotation(tracing.RUN_FETCH):
            # one blocking read: every copy starts before the first wait
            dec, ex, gout, s_f, n_f, n_in_log, reb_log = jax.device_get(out)
            if self.model_shards > 1:
                # 2-D outputs carry (data, model) leading axes; everything is
                # identical across model replicas, so read model coordinate 0
                dec = dec[0, 0][:n].astype(bool)
                ex = ex[0, 0][:n].astype(np.int64)
                gout = gout[0, 0][:n]
                s_f = int(s_f[0, 0])
                n_f = n_f[:, 0]
                n_in_log = n_in_log[:, 0, :]
                reb_log = reb_log[:, 0, :]
            else:
                dec = dec[0][:n].astype(bool)
                ex = ex[0][:n].astype(np.int64)
                gout = gout[0][:n]
                s_f = int(s_f[0])
                # n_f: (shards,) final live counts; n_in_log and reb_log:
                # (shards, S), reb_log the same across shards
        with TraceAnnotation(tracing.RUN_STATS):
            stages = plan.stages
            bn = self.scorer.row_block(self.block_n, cap_l)
            # a model shard bills its own w_local columns; summed over the
            # model axis a stage bills w_global = M * ceil(W/M) columns —
            # the honest cost of a non-dividing split (== W at M=1)
            w_bill = self._w_global if self.model_shards > 1 else self.dplan.W
            chunk_stats = []
            per_shard_scores = np.zeros((shards, s_f), dtype=np.int64)
            for s in range(s_f):
                n_in_k = n_in_log[:, s]
                n_in = int(n_in_k.sum())
                n_next = int(n_in_log[:, s + 1].sum()) if s + 1 < s_f else int(n_f.sum())
                # each shard bills the live blocks of ITS slab; empty shards
                # bill zero (their block guard skipped the whole stage)
                per_shard_scores[:, s] = (-(-n_in_k // bn)) * bn * w_bill
                chunk_stats.append(
                    ChunkStat(
                        t0=stages[s][0],
                        t1=stages[s][1],
                        n_in=n_in,
                        n_exited=n_in - n_next,
                        scores_computed=int(per_shard_scores[:, s].sum()),
                    )
                )
            self.last_run_info = {
                "shards": shards,
                "stages_run": s_f,
                "per_shard_n_in": n_in_log[:, :s_f].copy(),
                "per_shard_final_live": n_f.copy(),
                "per_shard_scores": per_shard_scores,
                "rebalanced_stages": np.flatnonzero(reb_log[0][:s_f]).tolist(),
                "model_shards": self.model_shards,
            }
            if self.model_shards > 1:
                m = self.model_shards
                # per-("data","model")-coordinate attribution: coordinate
                # (d, j) scored ceil(n_in[d]/bn)*bn rows times ITS w_local
                # columns at every stage step, and issued exactly ONE
                # model-axis psum per stage step (the 2-D contract the perf
                # gate locks)
                coord = (-(-n_in_log[:, :s_f] // bn)) * bn * self._w_local
                self.last_run_info.update(
                    mesh_shape=(shards, m),
                    per_coord_scores=np.repeat(coord[:, None, :], m, axis=1),
                    per_coord_psums=np.full((shards, m), s_f, dtype=np.int64),
                    per_coord_stages=np.full((shards, m), s_f, dtype=np.int64),
                )
        return ExecutorResult(
            decisions=dec,
            exit_step=ex,
            g_final=gout,
            chunk_stats=chunk_stats,
            scores_computed=sum(c.scores_computed for c in chunk_stats),
            scores_possible=n * T,
            device_reads=reads,
        )

    # -- grouped (ranking) decide, data-parallel over groups ------------

    def _cap_groups_local(self, n_groups: int, capacity_groups: int | None) -> int:
        """Per-shard GROUP-slot capacity: the balanced share, padded to
        the group-decide kernel's block granularity."""
        from repro.kernels.cascade_kernel import DEFAULT_BLOCK_G

        per = -(-max(n_groups, capacity_groups or 0, 1) // self.shards)
        return -(-per // DEFAULT_BLOCK_G) * DEFAULT_BLOCK_G

    def _grouped_per_shard(self, k, xbuf, gids, rows2d, valid2d, n_active, eps_g):
        """One shard's grouped stage loop: ``DeviceExecutor``'s
        ``_grouped_program`` body over shard-LOCAL group slots, with the
        psum'd live-group total driving the mesh-wide early exit.

        Groups never straddle a shard — each shard owns whole B-lane
        rectangles, exits them as units, and front-packs its own
        survivors; there is no grouped rebalance (a group is the
        migration quantum and moving one costs a B-lane all-to-all, not
        worth it at serving bucket sizes).  Verdicts scatter into
        GLOBAL-size accumulators by global group id — a group lives on
        exactly one shard, so the final ``psum`` is an exactly-once
        assembly, the same scheme as ``_per_shard``'s result scatter.
        """
        dp = self.dplan
        S, W = dp.S, dp.W
        xbuf = xbuf[0]
        gids = gids[0]
        rows2d = rows2d[0]
        valid2d = valid2d[0]
        n_active = n_active[0]
        eps_g = eps_g[0]
        cap_gl, B = rows2d.shape
        L = cap_gl * B
        cap_gG = self.shards * cap_gl  # == the trash/sentinel group id
        stage_t0 = jnp.asarray(dp.stage_t0)
        col_valid = jnp.asarray(dp.col_valid)
        grp = jnp.arange(cap_gl, dtype=jnp.int32)
        lane = jnp.arange(L, dtype=jnp.int32)
        lane_b = jnp.arange(B, dtype=jnp.int32)

        def body(carry):
            (s, xbuf, gids, rows2d, valid2d, n_active, g2d, total,
             verd, exst, marg, n_in_log, state) = carry
            n_in_log = n_in_log.at[s].set(n_active)
            t0 = stage_t0[s]
            # the survivor lanes ARE the row set: identity gather over
            # the shard-local operand buffer, never the global batch
            scores, state_new = self.scorer.stage(
                state, t0, t0 + W, lane, xbuf, n_active * B
            )
            scores = jnp.where(col_valid[s][None, :], scores, 0.0)
            scores = jnp.where(valid2d.reshape(L, 1) != 0, scores, 0.0)
            # per-column sequential accumulate: the one f32 add order,
            # shared with the host oracle (bit-parity contract)
            g_flat = g2d.reshape(L)
            for j in range(W):
                g_flat = g_flat + scores[:, j]
            g_new = g_flat.reshape(cap_gl, B)
            margin, exit_g = cascade_group_pallas(
                g_new,
                valid2d,
                jnp.broadcast_to(eps_g[s], (cap_gl,)),
                k,
                interpret=self.interpret,
                n_live=n_active,
            )
            exit_b = exit_g.astype(bool)  # live-gated inside the kernel
            verdict = group_topk_rows(g_new, valid2d, rows2d, k)
            # exactly-once verdict scatter by GLOBAL group id; retired
            # and padding slots aim at cap_gG, out of bounds
            scat = jnp.where(exit_b, gids, cap_gG)
            verd = verd.at[scat].set(verdict, mode="drop")
            exst = exst.at[scat].set(s + 1, mode="drop")
            marg = marg.at[scat].set(margin, mode="drop")
            # whole-GROUP cumsum-prefix compaction, local to the shard
            keep = (grp < n_active) & ~exit_b
            pos = jnp.cumsum(keep.astype(jnp.int32)) - 1
            packg = jnp.where(keep, pos, cap_gl)
            n_keep = keep.sum(dtype=jnp.int32)
            gids = (
                jnp.full((cap_gl,), cap_gG, dtype=jnp.int32)
                .at[packg].set(gids, mode="drop")
            )
            rows2d = (
                jnp.zeros((cap_gl, B), dtype=jnp.int32)
                .at[packg].set(rows2d, mode="drop")
            )
            valid2d = (
                jnp.zeros((cap_gl, B), dtype=jnp.int32)
                .at[packg].set(valid2d, mode="drop")
            )
            g2d = (
                jnp.zeros((cap_gl, B), dtype=jnp.float32)
                .at[packg].set(g_new, mode="drop")
            )
            lane_pack = jnp.where(
                keep[:, None], packg[:, None] * B + lane_b[None, :], L
            ).reshape(L)
            xbuf = jnp.zeros_like(xbuf).at[lane_pack].set(xbuf, mode="drop")
            state = repack_state(state, state_new, lane_pack)
            # quit when you can, mesh-wide: one psum per stage
            total = jax.lax.psum(n_keep, DATA_AXIS)
            return (
                s + 1, xbuf, gids, rows2d, valid2d, n_keep, g2d, total,
                verd, exst, marg, n_in_log, state,
            )

        def cond(carry):
            s = carry[0]
            total = carry[7]
            return (s < S) & (total > 0)

        total0 = jax.lax.psum(n_active, DATA_AXIS)
        init = (
            jnp.int32(0),
            xbuf,
            gids,
            rows2d,
            valid2d,
            n_active,
            jnp.zeros((cap_gl, B), dtype=jnp.float32),
            total0,
            jnp.zeros((cap_gG, k), dtype=jnp.int32),
            jnp.zeros((cap_gG,), dtype=jnp.int32),
            jnp.zeros((cap_gG,), dtype=jnp.float32),
            jnp.zeros((S,), dtype=jnp.int32),
            self.scorer.init_state(L),
        )
        (s_f, xbuf, gids, rows2d, valid2d, n_f, g2d, total,
         verd, exst, marg, n_in_log, _) = jax.lax.while_loop(cond, body, init)
        # ran-out groups carry the exact full-cascade ranking; reuse the
        # group kernel at eps = +inf just for its margins
        margin_f, _ = cascade_group_pallas(
            g2d,
            valid2d,
            jnp.full((cap_gl,), jnp.inf, dtype=jnp.float32),
            k,
            interpret=self.interpret,
            n_live=n_f,
        )
        verdict_f = group_topk_rows(g2d, valid2d, rows2d, k)
        scat = jnp.where(grp < n_f, gids, cap_gG)
        verd = verd.at[scat].set(verdict_f, mode="drop")
        exst = exst.at[scat].set(S, mode="drop")
        marg = marg.at[scat].set(margin_f, mode="drop")
        verd = jax.lax.psum(verd, DATA_AXIS)
        exst = jax.lax.psum(exst, DATA_AXIS)
        marg = jax.lax.psum(marg, DATA_AXIS)
        one = lambda a: jnp.reshape(a, (1,) + a.shape)  # noqa: E731
        return (
            one(verd), one(exst), one(marg), one(s_f), one(n_f), one(n_in_log),
        )

    def _grouped_program(self, k, x, gids, rows, valid, n0, eps_g):
        self.traces += 1  # trace-time side effect, read by the trace tests
        shards = self.shards
        _, cap_gl, B = rows.shape
        L = cap_gl * B
        # distribute the operand rows: each shard receives ONLY its own
        # groups' documents (gathered by flat doc id outside shard_map,
        # like the batch path, so the per-shard working set is O(cap_gl*B))
        xbuf = jnp.take(x, rows.reshape(-1), axis=0).reshape(
            (shards, L) + x.shape[1:]
        )
        # the threshold vector rides in sharded (every shard gets the
        # same copy) — no replicated in_specs, check_vma=False friendly
        eps_rep = jnp.broadcast_to(eps_g[None, :], (shards, eps_g.shape[0]))
        sharded = jax.shard_map(
            lambda xb, gi, ro, va, n, ep: self._grouped_per_shard(
                k, xb, gi, ro, va, n, ep
            ),
            mesh=self.mesh,
            in_specs=(P(DATA_AXIS),) * 6,
            out_specs=(P(DATA_AXIS),) * 6,
            check_vma=False,
        )
        return sharded(xbuf, gids, rows, valid, n0, eps_rep)

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
        query groups, data-parallel over the mesh.

        Same contract as ``DeviceExecutor.run_grouped`` (one bucket
        width B per call, ``capacity_groups`` pins the GLOBAL group-slot
        capacity so partial flushes reuse one trace).  Groups split
        contiguously across shards as whole units — compaction is
        shard-local, so no group ever straddles a shard boundary.
        """
        plan = self.dplan.plan
        T = plan.T
        if self.model_shards > 1:
            raise ValueError(
                f"run_grouped is unavailable on a {self.shards}x"
                f"{self.model_shards} ({DATA_AXIS!r}, {MODEL_AXIS!r}) "
                "mesh: the grouped (ranking) decide is data-parallel "
                "only — BackendCapabilities.model_parallel covers batch "
                "run() (DESIGN.md §13); compile with model_shards=1 for "
                "grouped serving"
            )
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
        shards = self.shards
        B = group_rows.shape[1]
        cap_gl = self._cap_groups_local(n_groups, capacity_groups)
        cap_gG = shards * cap_gl
        x = self._cast_operand(batch if prepared else self.scorer.prepare(batch))
        # balanced contiguous assignment: shard j takes the j-th slice
        # of whole groups (global ids travel with the rectangles)
        gids = np.full((shards, cap_gl), cap_gG, dtype=np.int32)
        rows_init = np.zeros((shards, cap_gl, B), dtype=np.int32)
        valid_init = np.zeros((shards, cap_gl, B), dtype=np.int32)
        n0 = np.zeros(shards, dtype=np.int32)
        base, rem = divmod(n_groups, shards)
        start = 0
        for j in range(shards):
            cnt = base + (1 if j < rem else 0)
            gids[j, :cnt] = np.arange(start, start + cnt, dtype=np.int32)
            rows_init[j, :cnt] = group_rows[start : start + cnt]
            valid_init[j, :cnt] = group_valid[start : start + cnt].astype(np.int32)
            n0[j] = cnt
            start += cnt
        args = (
            int(k),
            x,
            jnp.asarray(gids),
            jnp.asarray(rows_init),
            jnp.asarray(valid_init),
            jnp.asarray(n0),
            jnp.asarray(eps_g, dtype=jnp.float32),
        )
        compile_program(self._compiled, self._grouped_jit, *args, static=1)
        verd, exst, marg, s_f, n_f, n_in_log = launch_wave(
            "sharded", lambda: self._grouped_jit(*args)
        )
        verd = np.asarray(verd)[0][:n_groups]
        exst = np.asarray(exst, dtype=np.int64)[0][:n_groups]
        marg = np.asarray(marg)[0][:n_groups]
        s_f = int(np.asarray(s_f)[0])  # identical across shards (psum cond)
        n_f = np.asarray(n_f)  # (shards,) final live group counts
        n_in_log = np.asarray(n_in_log)  # (shards, S) group occupancy
        stages = plan.stages
        bn, W = self.scorer.block_n or self.block_n, self.dplan.W
        chunk_stats = []
        per_shard_scores = np.zeros((shards, s_f), dtype=np.int64)
        for s in range(s_f):
            n_in_k = n_in_log[:, s]
            n_in = int(n_in_k.sum())
            n_next = int(n_in_log[:, s + 1].sum()) if s + 1 < s_f else int(n_f.sum())
            # group-quantized block billing per shard: a live group
            # scores its full B-lane rectangle, block-guarded locally
            per_shard_scores[:, s] = (-(-(n_in_k * B) // bn)) * bn * W
            chunk_stats.append(
                ChunkStat(
                    t0=stages[s][0],
                    t1=stages[s][1],
                    n_in=n_in,
                    n_exited=n_in - n_next,
                    scores_computed=int(per_shard_scores[:, s].sum()),
                )
            )
        self.last_run_info = {
            "shards": shards,
            "stages_run": s_f,
            "per_shard_n_in": n_in_log[:, :s_f].copy(),
            "per_shard_final_live": n_f.copy(),
            "per_shard_scores": per_shard_scores,
            "rebalanced_stages": [],  # no grouped rebalance
        }
        return GroupedResult(
            verdicts=verd,
            exit_stage=exst,
            margin=marg,
            chunk_stats=chunk_stats,
            scores_computed=sum(c.scores_computed for c in chunk_stats),
            scores_possible=n_docs_real * T,
        )

    # -- streaming admission, shard-local (DESIGN.md §8) ----------------

    def _stream_per_shard(self, cap_l, ring_x, ring_ids, arrivals, counts):
        """One shard's streaming loop: the single-device streaming body
        (admission refill -> per-lane-stage score/decide -> retire ->
        compaction) over shard-LOCAL buffers and a shard-local admission
        ring, with the mesh-wide exit condition reading the psum'd
        pending + live total.
        """
        dp = self.dplan
        S, W, T = dp.S, dp.W, dp.plan.T
        shards = self.shards
        ring_x = ring_x[0]
        ring_ids = ring_ids[0]
        arrivals = arrivals[0]
        cnt = counts[0]
        R_l = ring_ids.shape[0]
        R_g = shards * R_l  # == the trash/sentinel id
        stage_t0 = jnp.asarray(dp.stage_t0)
        eps_pos = jnp.asarray(dp.eps_pos)
        eps_neg = jnp.asarray(dp.eps_neg)
        col_valid = jnp.asarray(dp.col_valid)
        beta = jnp.float32(dp.plan.beta)
        lane = jnp.arange(cap_l, dtype=jnp.int32)
        ridx = jnp.arange(R_l, dtype=jnp.int32)
        bn_bill = self.scorer.block_n or self.block_n

        def body(carry):
            (step, xbuf, stage, gbuf, idbuf, n_live, head, total,
             dec, ex, gout, admit, done, state) = carry
            # shard-local admission: freed back slots take the next
            # arrived rows from THIS shard's ring (no collectives)
            arrived = jnp.sum(
                (ridx >= head) & (ridx < cnt) & (arrivals <= step),
                dtype=jnp.int32,
            )
            k = jnp.minimum(cap_l - n_live, arrived)
            src = jnp.clip(head + (lane - n_live), 0, R_l - 1)
            is_new = (lane >= n_live) & (lane < n_live + k)
            xbuf = jnp.where(
                is_new.reshape((cap_l,) + (1,) * (xbuf.ndim - 1)),
                jnp.take(ring_x, src, axis=0),
                xbuf,
            )
            idbuf = jnp.where(is_new, jnp.take(ring_ids, src), idbuf)
            stage = jnp.where(is_new, 0, stage)
            gbuf = jnp.where(is_new, 0.0, gbuf)
            admit = admit.at[jnp.where(is_new, idbuf, R_g)].set(
                step, mode="drop"
            )
            n_live = n_live + k
            head = head + k
            # mixed-stage fused stage, per-lane tables (device_executor
            # _stream_program mirrors this body on one device — a
            # semantics change there must be replayed here)
            t0_lane = jnp.take(stage_t0, stage)
            stop = stage >= S - 1  # lanes running their LAST stage
            if self.megakernel:
                slabs = self.scorer.slabs
                if slabs.variant == "matrix":
                    idx = (
                        t0_lane[:, None]
                        + jnp.arange(W, dtype=jnp.int32)[None, :]
                    )
                    x_in = jnp.take_along_axis(xbuf, idx, axis=1)
                else:
                    x_in = xbuf
                g_new, active, dpos, ex_rel, pack, n_keep = (
                    mk.mega_lane_pallas(
                        slabs, x_in, mk.gather_lane_slabs(slabs, stage),
                        gbuf,
                        jnp.take(eps_pos, stage, axis=0),
                        jnp.take(eps_neg, stage, axis=0),
                        stop, n_live,
                        block_n=bn_bill,
                        interpret=self.interpret,
                    )
                )
                active_b = active.astype(bool)
                lane_valid = lane < n_live
                state_new = state  # megakernel path is stateless-only
            else:
                # rookies admitted above sit at stage 0: the t0==0
                # contract (BoundScorer docs) reinitializes their lane
                # state from the operand, so the zero-filled slots left
                # by compaction are never read as real state
                scores, state_new = self.scorer.lane_stage(
                    state, t0_lane, lane, xbuf, n_live
                )
                scores = jnp.where(
                    jnp.take(col_valid, stage, axis=0), scores, 0.0
                )
                g_new, active, dpos, ex_rel = cascade_lane_pallas(
                    gbuf,
                    scores,
                    jnp.take(eps_pos, stage, axis=0),
                    jnp.take(eps_neg, stage, axis=0),
                    block_n=self.block_n,
                    interpret=self.interpret,
                    n_valid=n_live,
                )
                active_b = active.astype(bool)
                lane_valid = lane < n_live
                # cumsum-prefix compaction, local to the shard
                keep = lane_valid & active_b & ~stop
                pos = jnp.cumsum(keep.astype(jnp.int32)) - 1
                pack = jnp.where(keep, pos, cap_l)
                n_keep = keep.sum(dtype=jnp.int32)
            newly = lane_valid & (ex_rel > 0)
            ran_out = lane_valid & active_b & stop
            fin = newly | ran_out
            dec_val = jnp.where(
                newly, dpos != 0, g_new >= beta
            ).astype(jnp.int32)
            ex_val = jnp.where(newly, ex_rel + t0_lane, T)
            scat = jnp.where(fin, idbuf, R_g)
            dec = dec.at[scat].set(dec_val, mode="drop")
            ex = ex.at[scat].set(ex_val, mode="drop")
            gout = gout.at[scat].set(g_new, mode="drop")
            done = done.at[scat].set(step, mode="drop")
            xbuf = jnp.zeros_like(xbuf).at[pack].set(xbuf, mode="drop")
            gbuf = jnp.zeros_like(gbuf).at[pack].set(g_new, mode="drop")
            stage = (
                jnp.zeros((cap_l,), dtype=jnp.int32)
                .at[pack]
                .set(stage + 1, mode="drop")
            )
            idbuf = (
                jnp.full((cap_l,), R_g, dtype=jnp.int32)
                .at[pack]
                .set(idbuf, mode="drop")
            )
            state = repack_state(state, state_new, pack)
            n_live = n_keep
            # mesh-wide census: the psum'd total now counts pending + live
            total = jax.lax.psum(n_live + (cnt - head), DATA_AXIS)
            return (
                step + 1, xbuf, stage, gbuf, idbuf, n_live, head, total,
                dec, ex, gout, admit, done, state,
            )

        def cond(carry):
            total = carry[7]
            # quit when you can, mesh-wide: every shard is out of both
            # live lanes and pending ring entries
            return total > 0

        total0 = jax.lax.psum(cnt, DATA_AXIS)
        init = (
            jnp.int32(0),
            jnp.zeros((cap_l,) + ring_x.shape[1:], dtype=ring_x.dtype),
            jnp.zeros((cap_l,), dtype=jnp.int32),
            jnp.zeros((cap_l,), dtype=jnp.float32),
            jnp.full((cap_l,), R_g, dtype=jnp.int32),
            jnp.int32(0),
            jnp.int32(0),
            total0,
            jnp.zeros((R_g,), dtype=jnp.int32),
            jnp.zeros((R_g,), dtype=jnp.int32),
            jnp.zeros((R_g,), dtype=jnp.float32),
            jnp.zeros((R_g,), dtype=jnp.int32),
            jnp.zeros((R_g,), dtype=jnp.int32),
            self.scorer.init_state(cap_l),
        )
        (s_f, _, _, _, _, _, _, _, dec, ex, gout, admit, done, _) = (
            jax.lax.while_loop(cond, body, init)
        )
        # exactly-once id scatter per shard: psum assembles the stream
        dec = jax.lax.psum(dec, DATA_AXIS)
        ex = jax.lax.psum(ex, DATA_AXIS)
        gout = jax.lax.psum(gout, DATA_AXIS)
        admit = jax.lax.psum(admit, DATA_AXIS)
        done = jax.lax.psum(done, DATA_AXIS)
        one = lambda a: jnp.reshape(a, (1,) + a.shape)  # noqa: E731
        return (
            one(dec), one(ex), one(gout), one(admit), one(done), one(s_f),
        )

    def _stream_program(self, cap_l, x, ring_ids, arrivals, counts):
        self.traces += 1  # trace-time side effect, read by the trace tests
        shards = self.shards
        R_l = ring_ids.shape[1]
        # distribute the ring operands: each shard's ring holds ITS
        # pending rows (gathered by id outside shard_map, like the batch
        # path, so the per-shard working set is O(R_l))
        ring_x = jnp.take(x, ring_ids.reshape(-1), axis=0).reshape(
            (shards, R_l) + x.shape[1:]
        )
        sharded = jax.shard_map(
            lambda rx, ri, ar, ct: self._stream_per_shard(
                cap_l, rx, ri, ar, ct
            ),
            mesh=self.mesh,
            in_specs=(P(DATA_AXIS),) * 4,
            out_specs=(P(DATA_AXIS),) * 6,
            check_vma=False,
        )
        return sharded(ring_x, ring_ids, arrivals, counts)

    def run_stream(
        self,
        batch,
        n: int,
        arrivals=None,
        capacity: int | None = None,
        ring_capacity: int | None = None,
        prepared: bool = False,
    ) -> StreamResult:
        """Continuously stream ``n`` rows, data-parallel over the mesh.

        Same contract as ``DeviceExecutor.run_stream`` with the admission
        ring split shard-local: pending rows are dealt ROUND-ROBIN in
        arrival order (request i waits in shard ``i % shards``'s ring),
        so every shard keeps receiving admissible work as the trace
        plays out — a contiguous split would starve all but one shard at
        a time.  ``capacity`` is the GLOBAL slot count (cap/shards slots
        per shard); per-shard occupancy lands in ``last_run_info``.
        """
        plan = self.dplan.plan
        T = plan.T
        if self.model_shards > 1:
            raise ValueError(
                f"run_stream is unavailable on a {self.shards}x"
                f"{self.model_shards} ({DATA_AXIS!r}, {MODEL_AXIS!r}) "
                "mesh: streaming admission mixes per-lane stages, which "
                "would need a per-lane model-axis psum — data-parallel "
                "only (DESIGN.md §13); compile with model_shards=1 for "
                "streaming"
            )
        if not self.scorer.has_lanes and not self.megakernel:
            raise ValueError(
                "run_stream needs a scorer with per-lane stage scoring "
                "(lane_fn or lane_stage_fn) on the multi-kernel path; "
                "this scorer only supports batch stages"
            )
        shards = self.shards
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
        cap_l = self._cap_local(capacity or n)
        R_l = -(-max(n, int(ring_capacity or n)) // shards)
        R_g = shards * R_l
        x = self._cast_operand(batch if prepared else self.scorer.prepare(batch))
        if x.shape[0] < R_g:
            x = jnp.pad(x, ((0, R_g - x.shape[0]),) + ((0, 0),) * (x.ndim - 1))
        arr = (
            np.zeros(n, dtype=np.int32)
            if arrivals is None
            else np.asarray(arrivals, dtype=np.int32)
        )
        if arr.shape != (n,):
            raise ValueError(
                f"arrivals must have shape ({n},) matching n, got "
                f"{tuple(arr.shape)}"
            )
        if arr.size and not (np.diff(arr) >= 0).all():
            raise ValueError(
                "arrivals must be nondecreasing (the admission ring "
                "replays requests in arrival order)"
            )
        # round-robin deal: shard k's ring slot i holds request i*shards+k
        ring_ids = np.full((shards, R_l), R_g, dtype=np.int32)
        ring_arr = np.zeros((shards, R_l), dtype=np.int32)
        counts = np.zeros(shards, dtype=np.int32)
        for k in range(shards):
            ids_k = np.arange(k, n, shards, dtype=np.int32)
            ring_ids[k, : ids_k.size] = ids_k
            ring_arr[k, : ids_k.size] = arr[ids_k]
            counts[k] = ids_k.size
        args = (
            cap_l,
            x,
            jnp.asarray(ring_ids),
            jnp.asarray(ring_arr),
            jnp.asarray(counts),
        )
        compile_program(self._compiled, self._stream_jit, *args, static=1)
        dec, ex, gout, admit, done, s_f = launch_wave(
            "sharded", lambda: self._stream_jit(*args)
        )
        steps_run = int(np.asarray(s_f)[0])
        dec = np.asarray(dec)[0][:n].astype(bool)
        ex = np.asarray(ex, dtype=np.int64)[0][:n]
        gout = np.asarray(gout)[0][:n]
        admit = np.asarray(admit, dtype=np.int64)[0][:n]
        done = np.asarray(done, dtype=np.int64)[0][:n]
        # per-shard block-guard billing, reconstructed from the timeline
        # (the host knows the round-robin deal, so shard membership is
        # a function of the row id)
        bn, W = self.scorer.row_block(self.block_n, cap_l), self.dplan.W
        per_shard_occ = np.zeros((shards, steps_run), dtype=np.int64)
        scores_computed = 0
        for k in range(shards):
            sel = np.arange(k, n, shards)
            occ_k = stream_occupancy(admit[sel], done[sel], steps_run)
            per_shard_occ[k] = occ_k
            scores_computed += int(((-(-occ_k // bn)) * bn * W).sum())
        self.last_run_info = {
            "shards": shards,
            "stream_steps": steps_run,
            "per_shard_occupancy": per_shard_occ,
            "per_shard_admitted": counts.copy(),
        }
        return StreamResult(
            decisions=dec,
            exit_step=ex,
            g_final=gout,
            admit_step=admit,
            done_step=done,
            steps_run=steps_run,
            occupancy=per_shard_occ.sum(axis=0),
            capacity=shards * cap_l,
            scores_computed=scores_computed,
            scores_possible=n * T,
        )
