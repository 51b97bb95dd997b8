"""The public ``StageScorer`` protocol (DESIGN.md §11).

One scorer abstraction across every execution tier.  A ``StageScorer`` is a
plan-INDEPENDENT template describing how to score cascade stages; binding it
to a ``DevicePlan`` yields the traceable
``kernels.device_executor.BoundScorer`` whose single protocol method

    ``stage(state, t0, t1, rows, x, n_valid) -> (scores, state)``

is what ChunkedExecutor (through :func:`host_producer`), DeviceExecutor,
ShardedDeviceExecutor and the streaming lanes all call.  ``state`` is a
per-row pytree declared by ``state_spec``: the built-in matrix/tree/lattice
scorers are stateless (``state_spec = ()`` — the executors' state threading
compiles away and billing stays byte-identical to the pre-protocol
programs), while :class:`NeuralScorer` carries the transformer residual
stream through the survivor buffers so early-exited rows stop paying for
deep layers.

This module replaces the ad-hoc ``score_fn`` / ``device_scorer_factory`` /
``lane_fn`` trios grown over PRs 1-6: public entrypoints (``api.fit`` /
``compile`` / ``serve``, ``QWYCServer``) take only protocol scorers, and
the per-backend wiring is an internal detail of :meth:`StageScorer.bind`.

Model-backed fit example (the neural cascade of DESIGN.md §11)::

    from repro import api
    from repro.models.transformer import init_params

    params = init_params(cfg, key)          # cfg.exit_interval = k
    scorer = api.NeuralScorer(params, cfg, seq_len=tokens.shape[1])
    fitted = api.fit(scorer, tokens_calib, y_calib, alpha=0.02)
    result = fitted.compile("device").evaluate(x=tokens_test)
"""

from __future__ import annotations

import abc
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.executor import CascadePlan
from repro.kernels.device_executor import (
    DEFAULT_BLOCK_N,
    BoundScorer,
    DevicePlan,
    lane_block,
    lattice_stage_scorer,
    matrix_stage_scorer,
    tree_stage_scorer,
)

__all__ = [
    "StageScorer",
    "MatrixScorer",
    "TreeScorer",
    "LatticeScorer",
    "NeuralScorer",
    "FunctionScorer",
    "register_scorer",
    "get_scorer",
    "scorer_names",
    "host_producer",
]


class StageScorer(abc.ABC):
    """A plan-independent stage-scorer template.

    ``bind(dplan)`` lowers the template onto a concrete ``DevicePlan``
    (cascade order, stage grid, quantization) and returns the traceable
    ``BoundScorer`` the executors drive.  Templates hold ensemble params
    in ORIGINAL order; cascade reordering happens at bind time from
    ``dplan.plan.order``, so one template serves any fitted cascade over
    the same ensemble.
    """

    #: registry name of the scorer family ("matrix"/"tree"/"lattice"/...)
    name: str = "?"

    @abc.abstractmethod
    def bind(self, dplan: DevicePlan) -> BoundScorer:
        """Lower onto ``dplan`` -> the executors' ``BoundScorer``."""

    def calibration_scores(self, X) -> np.ndarray:
        """(N, T) additive stage scores for ``api.fit(scorer, X)`` — the
        model-backed fit path.  Optional: scorers that cannot self-score
        fit on a precomputed score matrix instead."""
        raise NotImplementedError(
            f"{type(self).__name__} cannot score calibration inputs itself; "
            "pass a precomputed (N, T) score matrix to api.fit instead"
        )

    def fit_overrides(self) -> dict:
        """FitConfig fields this scorer family pins (e.g. depth-pinned
        order for neural cascades).  Merged over the user config by
        ``api.fit``; explicit user ``costs`` win."""
        return {}


def _as_device_plan(plan) -> DevicePlan:
    if isinstance(plan, DevicePlan):
        return plan
    if isinstance(plan, CascadePlan):
        return DevicePlan.from_plan(plan)
    raise TypeError(f"expected CascadePlan or DevicePlan, got {type(plan).__name__}")


@dataclasses.dataclass(frozen=True)
class MatrixScorer(StageScorer):
    """Scorer over a precomputed (N, T) score matrix in ORIGINAL ensemble
    order — ``prepare`` applies the plan's cascade order itself.  The
    protocol analogue of ``core.executor.matrix_producer``; used by
    tests/oracles and the server's eager fallback."""

    quant: str | None = None
    name: str = dataclasses.field(default="matrix", init=False)

    def bind(self, dplan: DevicePlan) -> BoundScorer:
        base = matrix_stage_scorer(dplan, quant=self.quant)
        order = np.asarray(dplan.plan.order)

        def prepare(original: np.ndarray):
            F = np.asarray(original)
            if F.ndim != 2 or F.shape[1] != order.shape[0]:
                raise ValueError(
                    f"MatrixScorer expects an (N, {order.shape[0]}) "
                    f"original-order score matrix, got {F.shape}"
                )
            return base.prepare(F[:, order])

        return dataclasses.replace(base, prepare=prepare)


@dataclasses.dataclass(frozen=True)
class TreeScorer(StageScorer):
    """Oblivious-forest scorer over stacked per-tree params in ORIGINAL
    ensemble order ((T, depth) feats/thrs, (T, 2**depth) leaves)."""

    feats: np.ndarray
    thrs: np.ndarray
    leaves: np.ndarray
    block_n: int = DEFAULT_BLOCK_N
    interpret: bool | None = None
    quant: str | None = None
    name: str = dataclasses.field(default="tree", init=False)

    def bind(self, dplan: DevicePlan) -> BoundScorer:
        order = np.asarray(dplan.plan.order)
        return tree_stage_scorer(
            dplan,
            np.asarray(self.feats)[order],
            np.asarray(self.thrs)[order],
            np.asarray(self.leaves)[order],
            block_n=self.block_n,
            interpret=self.interpret,
            quant=self.quant,
        )


@dataclasses.dataclass(frozen=True)
class LatticeScorer(StageScorer):
    """Lattice scorer over (T, 2**S) theta / (T, S) feats stacks in
    ORIGINAL ensemble order."""

    theta: np.ndarray
    feats: np.ndarray
    block_n: int = DEFAULT_BLOCK_N
    interpret: bool | None = None
    quant: str | None = None
    name: str = dataclasses.field(default="lattice", init=False)

    def bind(self, dplan: DevicePlan) -> BoundScorer:
        order = np.asarray(dplan.plan.order)
        return lattice_stage_scorer(
            dplan,
            np.asarray(self.theta)[order],
            np.asarray(self.feats)[order],
            block_n=self.block_n,
            interpret=self.interpret,
            quant=self.quant,
        )


@dataclasses.dataclass(frozen=True)
class FunctionScorer(StageScorer):
    """Escape hatch: wrap a ``factory(dplan) -> BoundScorer`` closure.

    For custom scorers that build their own kernel-layer ``BoundScorer``
    (tests, benchmarks, one-off experiments) without defining a full
    ``StageScorer`` subclass.  The closure receives the bound
    ``DevicePlan`` and returns the kernel-layer scorer; everything else
    (state specs, lanes, slabs) is whatever the closure put on it.
    """

    factory: object
    name: str = dataclasses.field(default="function", init=False)

    def bind(self, dplan: DevicePlan) -> BoundScorer:
        return self.factory(dplan)


#: tokens a neural stage computes at once: 8,192 fill the MXU tiles, and
#: each held expert still gets hundreds of routed slots a block.  On a TPU
#: v5e, stage 0 (8 layers) of DeepSeek-V2-Lite over 128 rows of 512 tokens
#: took 0.652 s at 8,192 or 16,384 tokens a block and 0.774 s in one block.
NEURAL_BLOCK_TOKENS = 8192


def neural_block_rows(seq_len: int) -> int:
    """Rows of a neural stage's lane block: the largest power of two that
    holds at most ``NEURAL_BLOCK_TOKENS`` tokens (at least one row)."""
    return 1 << (max(1, NEURAL_BLOCK_TOKENS // int(seq_len)).bit_length() - 1)


class NeuralScorer(StageScorer):
    """QWYC over transformer depth: cascade position t is the exit head
    after layer ``(t + 1) * exit_interval``, and the stage score is the
    per-segment delta f_t = s_t - s_{t-1} (``core/early_exit.py``'s
    additive-ensemble view) — so the executor's running sum g IS the
    exit-t classifier score and ``g >= beta`` at margin-infinity is the
    full-depth verdict.

    The carried state is the residual stream itself::

        state = {"h": (S_seq, d_model) residual, "s_prev": () f32}

    ``stage(state, t0, t0+W, ...)`` runs layers ``t0*k .. (t0+W)*k`` of
    the transformer on the survivors' carried ``h`` (same
    ``_apply_block``, same windows/positions as ``forward``), applying
    the exit head to the last-token state after each segment.  Layer
    ``l < first_dense_layers`` runs its own dense block (DeepSeek's layer
    0), every later one the scan-stacked block; the W positions of a
    stage are one loop, so each layer kind is one program region.  Attention
    K/V are recomputed from the carried residual each segment —
    prefill-style classification, exact by construction, so no separate
    KV cache rides the buffers.  At ``t0 == 0`` the state is initialized
    from the prepared operand (embedded tokens), which also covers
    streaming rookies admitted into recycled lanes mid-loop.

    A stage computes only the live rows.  The survivors are front-packed
    in lanes ``[0, n_valid)``, so a ``fori_loop`` with a traced trip count
    runs the segment on the first ``ceil(n_valid / bn)`` blocks of ``bn``
    lanes, and the lanes past them score 0.  ``bn``
    (``neural_block_rows``) is the largest power of two of rows that
    holds at most ``NEURAL_BLOCK_TOKENS`` tokens: 16 rows at 512 tokens.
    Where it does not tile the lane buffer, the stage runs one block of
    all of it (``lane_block``).  ``block_n`` is ``bn``, so the executors
    bill the blocks the stage computes.

    Depth order is pinned (layer t consumes layer t-1's output):
    ``bind`` rejects plans whose order isn't ``arange`` or that use a
    lead stage (``sorted-kernel`` policy).  The lane variant used by the
    streaming executors is a masked sweep over the plan's static stage
    starts over every lane — S_stages x the batch-stage compute: its
    lanes are recycled, not front-packed, so no block loop applies.

    No ``slabs``: the fused megakernel has no survivor-state lane, so
    the executors' auto-megakernel can never engage for this scorer
    (and ``megakernel=True`` raises at construction).
    """

    name = "neural"

    def __init__(self, params, cfg, seq_len: int):
        if not cfg.exit_interval:
            raise ValueError("NeuralScorer needs cfg.exit_interval > 0")
        if not cfg.uniform:
            raise ValueError(
                "NeuralScorer requires a uniform (scan-stacked) layer stack; "
                f"layer_pattern={cfg.layer_pattern!r} is not uniform"
            )
        n_pre = cfg.first_dense_layers
        if n_pre and len(params.get("pre_layers", ())) != n_pre:
            raise ValueError(
                f"cfg.first_dense_layers={cfg.first_dense_layers} needs as many "
                "'pre_layers' in params (models.transformer.init_params)"
            )
        if "exit_heads" not in params:
            raise ValueError("params must carry 'exit_heads' (cfg.exit_interval set at init)")
        self.params = params
        self.cfg = cfg
        self.seq_len = int(seq_len)

    @property
    def n_exits(self) -> int:
        return self.cfg.n_layers // self.cfg.exit_interval

    def calibration_scores(self, tokens) -> np.ndarray:
        """Per-block logit margins: the (N, n_exits) per-segment deltas
        f_t = s_t - s_{t-1} of the exit-head scores (the additive
        ensemble of ``core/early_exit.py`` whose running sum IS the
        exit-t score) — what the thresholds are fit on."""
        from repro.core.early_exit import exit_scores

        s = np.asarray(
            exit_scores(self.params, self.cfg, jnp.asarray(tokens, dtype=jnp.int32)),
            dtype=np.float64,
        )
        return np.diff(
            np.concatenate([np.zeros((s.shape[0], 1)), s], axis=1), axis=1
        )

    def fit_overrides(self) -> dict:
        E = self.n_exits
        return {
            "optimize_order": False,
            "order": np.arange(E),
            "costs": np.full(E, float(self.cfg.exit_interval)),
        }

    def bind(self, dplan: DevicePlan) -> BoundScorer:
        from repro.models.transformer import _apply_block, layer_windows

        cfg, params = self.cfg, self.params
        k = int(cfg.exit_interval)
        E = self.n_exits
        plan = dplan.plan
        if plan.T != E:
            raise ValueError(
                f"plan has {plan.T} cascade positions but the model has {E} "
                f"exits (n_layers={cfg.n_layers}, exit_interval={k})"
            )
        if not np.array_equal(np.asarray(plan.order), np.arange(E)):
            raise ValueError(
                "neural stages are depth-pinned: layer t consumes layer t-1's "
                "output, so the cascade order must be arange(n_exits) "
                "(fit with a pre-selected ordering, DESIGN.md §11)"
            )
        if plan.lead_t:
            raise ValueError(
                "neural stages do not support a lead stage (lead_t="
                f"{plan.lead_t}); use the 'kernel' policy, not 'sorted-kernel'"
            )

        embed = params["embed"]
        n_pre = int(cfg.first_dense_layers)
        kinds = cfg.layer_kinds()
        win_arr = jnp.asarray(layer_windows(cfg), dtype=jnp.int32)
        positions = jnp.arange(self.seq_len, dtype=jnp.int32)
        W = dplan.W
        dt = jax.tree_util.tree_leaves(embed)[0].dtype
        d_model = int(cfg.d_model)
        state_spec = {
            "h": jax.ShapeDtypeStruct((self.seq_len, d_model), dt),
            "s_prev": jax.ShapeDtypeStruct((), jnp.float32),
        }
        weights = {
            "layers": params["layers"],
            "pre": list(params.get("pre_layers", [])),
            "heads": params["exit_heads"],
        }

        def prepare(tokens):
            from repro.models import layers as L

            toks = jnp.asarray(tokens, dtype=jnp.int32)
            if toks.ndim != 2 or toks.shape[1] != self.seq_len:
                raise ValueError(
                    f"NeuralScorer(seq_len={self.seq_len}) got tokens of "
                    f"shape {toks.shape}"
                )
            return L.embed_tokens(embed, toks, cfg)

        def _take(tree, i):
            return jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), tree
            )

        def _layer(wts, h, li):
            """Layer ``li`` (traced) on the carried residual stream: one
            branch per leading dense layer, then the scan-stacked block."""

            def stacked(h):
                lp = _take(wts["layers"], jnp.maximum(li - n_pre, 0))
                return _apply_block(
                    lp, h, cfg, kinds[n_pre], positions, win_arr[li], None
                )[0]

            def dense(i, h):
                return _apply_block(
                    wts["pre"][i], h, cfg, kinds[i], positions, win_arr[i], None
                )[0]

            if not n_pre:
                return stacked(h)
            branches = [functools.partial(dense, i) for i in range(n_pre)]
            return jax.lax.switch(jnp.minimum(li, n_pre), branches + [stacked], h)

        def _segment(wts, h, sp, t0):
            """Run exits [t0, t0 + W) on the carried residual stream.

            ``t0`` may be traced (batch stages) or a static int (the lane
            sweep); exits past E leave the stream as it is and score 0,
            so padded columns stay inert and the loop body is uniform.
            """

            def position(w, carry):
                h, sp, cols = carry
                p_idx = jnp.asarray(t0, jnp.int32) + w
                valid = p_idx < E
                p_c = jnp.minimum(p_idx, E - 1)

                def run(h):
                    for j in range(k):
                        h = _layer(wts, h, p_c * k + j)
                    return h

                h = jax.lax.cond(valid, run, lambda h: h, h)
                head = jax.lax.dynamic_index_in_dim(
                    wts["heads"], p_c, 0, keepdims=False
                )
                # same contraction as core.early_exit.exit_scores: the raw
                # (un-normed) last-token residual against the exit head, f32
                s = jnp.einsum(
                    "bd,d->b",
                    h[:, -1, :].astype(jnp.float32),
                    head.astype(jnp.float32),
                )
                cols = cols.at[:, w].set(jnp.where(valid, s - sp, 0.0))
                return h, jnp.where(valid, s, sp), cols

            cols = jnp.zeros((h.shape[0], W), jnp.float32)
            h, sp, cols = jax.lax.fori_loop(0, W, position, (h, sp, cols))
            return cols, h, sp

        bn = neural_block_rows(self.seq_len)

        def stage_fn(wts, state, t0, t1, rows, x, n_valid):
            cap = rows.shape[0]
            nb = lane_block(bn, cap)
            first = jnp.asarray(t0, jnp.int32) == 0

            def block(i, out):
                # the live rows are front-packed, so blocks [0, ceil(n_valid
                # / nb)) hold every one of them
                scores, h_all, sp_all = out
                r0 = i * nb
                r = jax.lax.dynamic_slice_in_dim(rows, r0, nb)
                xr = jnp.take(x, r, axis=0)  # trash rows clamp; masked later
                h = jax.lax.dynamic_slice_in_dim(h_all, r0, nb)
                sp = jax.lax.dynamic_slice_in_dim(sp_all, r0, nb)
                h = jnp.where(first, xr.astype(dt), h)
                sp = jnp.where(first, 0.0, sp)
                s, h, sp = _segment(wts, h, sp, t0)
                return (
                    jax.lax.dynamic_update_slice_in_dim(scores, s, r0, 0),
                    jax.lax.dynamic_update_slice_in_dim(h_all, h, r0, 0),
                    jax.lax.dynamic_update_slice_in_dim(sp_all, sp, r0, 0),
                )

            # lanes past the live blocks score 0 and keep the state they
            # came with, which the executors' repack drops
            out = (jnp.zeros((cap, W), jnp.float32), state["h"], state["s_prev"])
            n_blocks = (jnp.asarray(n_valid, jnp.int32) + nb - 1) // nb
            scores, h, sp = jax.lax.fori_loop(0, n_blocks, block, out)
            return scores, {"h": h, "s_prev": sp}

        stage_starts = [int(t) for t in dplan.stage_t0]

        def lane_stage_fn(wts, state, t0_lane, rows, x, n_valid):
            xr = jnp.take(x, rows, axis=0)
            first = t0_lane == 0
            h = jnp.where(first[:, None, None], xr.astype(dt), state["h"])
            sp = jnp.where(first, 0.0, state["s_prev"])
            out = jnp.zeros((xr.shape[0], W), jnp.float32)
            h_out, sp_out = h, sp
            for q in stage_starts:
                s_q, h_q, sp_q = _segment(wts, h, sp, q)
                sel = t0_lane == q
                out = jnp.where(sel[:, None], s_q, out)
                h_out = jnp.where(sel[:, None, None], h_q, h_out)
                sp_out = jnp.where(sel, sp_q, sp_out)
            return out, {"h": h_out, "s_prev": sp_out}

        return BoundScorer(
            fn=None,
            prepare=prepare,
            width=W,
            block_n=bn,
            state_spec=state_spec,
            stage_fn=stage_fn,
            lane_stage_fn=lane_stage_fn,
            weights=weights,
        )


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_SCORERS: dict[str, type] = {
    "matrix": MatrixScorer,
    "tree": TreeScorer,
    "lattice": LatticeScorer,
    "neural": NeuralScorer,
    "function": FunctionScorer,
}


def register_scorer(name: str, cls: type) -> None:
    """Register a ``StageScorer`` subclass under ``name``."""
    if not (isinstance(cls, type) and issubclass(cls, StageScorer)):
        raise TypeError(f"{cls!r} is not a StageScorer subclass")
    _SCORERS[str(name)] = cls


def get_scorer(name: str) -> type:
    try:
        return _SCORERS[name]
    except KeyError:
        raise KeyError(
            f"unknown scorer {name!r}; registered: {sorted(_SCORERS)}"
        ) from None


def scorer_names() -> tuple[str, ...]:
    return tuple(sorted(_SCORERS))


# ---------------------------------------------------------------------------
# host adapter: StageScorer -> ChunkedExecutor producer
# ---------------------------------------------------------------------------


def host_producer(scorer, plan, batch):
    """Adapt a ``StageScorer`` (or already-bound ``BoundScorer``) to the
    host ``ChunkedExecutor`` producer contract -> ``(producer, n)``.

    The ChunkedExecutor is the parity ORACLE for every device tier, so
    this adapter drives the SAME ``stage`` protocol the device loops
    trace: the full-batch state pytree lives host-side, the per-call rows
    gather/scatter mirrors the executors' survivor compaction, and each
    stage call is W wide (the bound scorer's uniform stage width) with
    the result sliced back to the requested ``t1 - t0`` columns.
    """
    dplan = _as_device_plan(plan)
    bound = scorer.bind(dplan) if isinstance(scorer, StageScorer) else scorer
    if not isinstance(bound, BoundScorer):
        raise TypeError(
            f"expected a StageScorer or BoundScorer, got {type(scorer).__name__}"
        )
    x = bound.prepare(batch)
    n = int(x.shape[0])
    W = bound.width
    cell = {"state": bound.init_state(n)}

    def producer(rows, t0, t1):
        rows_np = np.asarray(rows, dtype=np.int32)
        m = int(rows_np.shape[0])
        if m == 0:
            return np.zeros((0, t1 - t0), dtype=np.float64)
        # the Pallas-backed scorers compute at their own block_n
        # granularity; pad the gather like ops._bucket_rows does (a
        # stateful stage takes any lane count)
        mult = 1 if bound.stateful else (bound.block_n or 1)
        pad = -m % mult
        rows_p = (
            np.concatenate([rows_np, np.full(pad, rows_np[0], np.int32)])
            if pad
            else rows_np
        )
        rows_j = jnp.asarray(rows_p)
        sub = jax.tree_util.tree_map(
            lambda b: jnp.take(b, rows_j, axis=0), cell["state"]
        )
        scores, sub_new = bound.stage(
            sub, jnp.int32(t0), jnp.int32(t0) + W, rows_j, x, jnp.int32(m)
        )
        if bound.stateful:
            live = jnp.asarray(rows_np)
            # scatter only the m real lanes back: pad lanes duplicate
            # rows_np[0] and must not double-advance its state
            cell["state"] = jax.tree_util.tree_map(
                lambda b, v: b.at[live].set(v[:m]), cell["state"], sub_new
            )
        return np.asarray(jax.device_get(scores))[:m, : t1 - t0].astype(
            np.float64
        )

    return producer, n
