"""The QWYC pipeline: ``fit -> compile -> evaluate / serve``.

One front door over what PRs 1-3 spread across ``fit_qwyc`` ->
``QWYCModel`` -> ``CascadePlan.from_qwyc`` -> three executor classes:

    fitted   = api.fit(scores_or_score_fn, X, beta=..., alpha=...)
    compiled = fitted.compile("auto")          # or "host"|"device"|"sharded"
    result   = compiled.evaluate(scores=F_test)
    server   = compiled.serve(score_fn=score_fn, batch_size=256)

``fit`` wraps Algorithm 1 (joint order + threshold optimization);
``compile`` resolves an execution backend through the registry
(``repro.api.registry``) and binds the cascade plan to it; ``evaluate``
runs one batch and returns the executor's ``ExecutorResult`` (decisions,
exit steps, per-stage billing); ``serve`` builds a ``QWYCServer`` wired
through the same backend.  Backends are adapters over the unchanged
executors, so every path is bit-identical to direct executor
construction (``tests/test_api.py`` asserts this per backend).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import numpy as np

from repro.api.backends import (
    Backend,
    BackoffPolicy,
    DegradationEvent,
    DegradationLadder,
)
from repro.api.registry import AUTO, backend_names, get_backend, resolve_backend
from repro.core.executor import (
    DEFAULT_CHUNK_T,
    CascadePlan,
    ExecutorResult,
    matrix_producer,
)
from repro.api.scorers import StageScorer, host_producer
from repro.core.qwyc import QWYCModel, fit_qwyc
from repro.kernels.device_executor import (
    DEFAULT_BLOCK_N,
    DevicePlan,
    matrix_stage_scorer,
)

__all__ = ["FitConfig", "FittedCascade", "CompiledCascade", "fit"]


@dataclasses.dataclass(frozen=True)
class FitConfig:
    """Calibration + planning knobs for ``fit`` (defaults = ``fit_qwyc``'s).

    ``alpha`` is the allowed disagreement rate vs the FULL ensemble (QWYC
    needs no labels — ``y`` exists in ``fit``'s signature only so scoring
    pipelines can pass it through for their own reporting).  ``chunk_t``
    is the default stage width ``compile`` splits the cascade into.
    """

    beta: float = 0.0
    alpha: float = 0.0
    mode: str = "both"
    costs: Any = None
    optimize_order: bool = True
    order: Any = None
    verbose: bool = False
    chunk_t: int = DEFAULT_CHUNK_T


def _normalize_config(config, overrides: dict) -> FitConfig:
    if config is None:
        cfg = FitConfig()
    elif isinstance(config, FitConfig):
        cfg = config
    elif isinstance(config, dict):
        cfg = FitConfig(**config)
    else:
        raise TypeError(f"config must be FitConfig/dict/None, got {type(config)}")
    return dataclasses.replace(cfg, **overrides) if overrides else cfg


def fit(
    ensemble,
    X: np.ndarray | None = None,
    y: np.ndarray | None = None,
    config: FitConfig | dict | None = None,
    *,
    groups=None,
    topk: int | None = None,
    **overrides,
) -> "FittedCascade":
    """Jointly optimize evaluation order + early-exit thresholds.

    Args:
      ensemble: one of
        * a precomputed calibration score matrix ``(N, T)`` with
          ``F[i, t] = f_t(x_i)`` (original model order);
        * a callable ``score_fn(X) -> (N, T)`` — the trained ensemble's
          batched scorer (e.g. a closure over ``ops.gbt_scores``), kept
          on the result so ``compile(...).evaluate(x=...)`` and
          ``serve()`` can score with it;
        * a ``StageScorer`` that can self-score (model-backed fit):
          ``api.NeuralScorer(params, cfg, seq_len)`` calibrates on its
          per-block logit margins (``calibration_scores``), pins the
          config fields its family requires (depth order, layer costs),
          and becomes the default scorer ``compile``/``serve`` bind.
      X: calibration features (tokens, for the neural scorer); required
        iff ``ensemble`` is callable or a ``StageScorer``.
      y: unused by QWYC (calibration is label-free — the objective is
        agreement with the full ensemble); accepted for pipeline symmetry.
      groups: per-QUERY document counts ``(G,)`` for ranking ensembles —
        calibration rows become ragged query groups (contiguous in the
        score matrix) and the fit additionally calibrates GROUP-level
        margin thresholds (``repro.ranking.fit_grouped``, DESIGN.md §12):
        a query exits as a unit once its top-``topk`` ranking is stable.
        The result then supports ``compile(...).rank(...)`` and a grouped
        ``serve()``.
      topk: ranking depth ``k`` for grouped calibration (default 10;
        requires ``groups=``).
      config / **overrides: a ``FitConfig`` (or dict), with keyword
        overrides applied on top — ``fit(F, beta=0.5, alpha=0.01)``.

    Returns a ``FittedCascade``; ``compile`` it onto a backend next.
    """
    cfg = _normalize_config(config, overrides)
    score_fn = None
    scorer = None
    if isinstance(ensemble, StageScorer):
        if X is None:
            raise ValueError(
                "fit(scorer, ...) needs calibration inputs X to score"
            )
        scorer = ensemble
        score_fn = scorer.calibration_scores
        F = np.asarray(score_fn(X))
        forced = dict(scorer.fit_overrides())
        if cfg.costs is not None:
            forced.pop("costs", None)  # explicit user costs win
        if forced:
            cfg = dataclasses.replace(cfg, **forced)
    elif callable(ensemble):
        if X is None:
            raise ValueError(
                "fit(score_fn, ...) needs calibration features X to score"
            )
        score_fn = ensemble
        F = np.asarray(ensemble(X))
    else:
        F = np.asarray(ensemble)
    if F.ndim != 2:
        raise ValueError(f"calibration scores must be (N, T), got {F.shape}")
    if groups is not None:
        from repro.ranking import fit_grouped

        sizes = np.asarray(groups, dtype=np.int64)
        grouped = fit_grouped(
            F,
            sizes,
            10 if topk is None else int(topk),
            costs=cfg.costs,
            alpha=cfg.alpha,
            beta=cfg.beta,
            mode=cfg.mode,
            optimize_order=cfg.optimize_order,
            order=cfg.order,
            chunk_t=cfg.chunk_t,
            verbose=cfg.verbose,
        )
        return FittedCascade(
            model=grouped.model, config=cfg, score_fn=score_fn,
            calibration_scores=F, scorer=scorer, grouped=grouped,
        )
    if topk is not None:
        raise ValueError("topk= requires groups= (per-query document counts)")
    model = fit_qwyc(
        F,
        costs=cfg.costs,
        beta=cfg.beta,
        alpha=cfg.alpha,
        mode=cfg.mode,
        optimize_order=cfg.optimize_order,
        order=cfg.order,
        verbose=cfg.verbose,
    )
    return FittedCascade(
        model=model, config=cfg, score_fn=score_fn, calibration_scores=F,
        scorer=scorer,
    )


@dataclasses.dataclass
class FittedCascade:
    """A fitted QWYC cascade (ordering + thresholds), backend-agnostic.

    ``model`` is the plain ``QWYCModel`` — existing code that wants the
    raw arrays (``order``, ``eps_pos``, ``eps_neg``) reads it directly.
    ``calibration_scores`` is the (N, T) matrix ``fit`` calibrated on
    (original model order), kept so downstream baselines/reports don't
    re-score the calibration split through the full ensemble.
    """

    model: QWYCModel
    config: FitConfig = dataclasses.field(default_factory=FitConfig)
    score_fn: Callable | None = None
    calibration_scores: np.ndarray | None = dataclasses.field(
        default=None, repr=False
    )
    #: the StageScorer template fit() calibrated (model-backed fit);
    #: compile()/serve() bind it by default
    scorer: StageScorer | None = None
    #: the GroupedPlan from fit(groups=...) — per-stage GROUP margin
    #: thresholds for ranking cascades; enables compile(...).rank() and
    #: the grouped serve() (None for row-level fits)
    grouped: Any | None = None

    @property
    def T(self) -> int:
        return self.model.T

    def plan(self, chunk_t: int | None = None) -> CascadePlan:
        return CascadePlan.from_qwyc(
            self.model, chunk_t=self.config.chunk_t if chunk_t is None else chunk_t
        )

    def compile(
        self,
        backend: str | Backend = "auto",
        *,
        chunk_t: int | None = None,
        block_n: int | None = None,
        interpret: bool | None = None,
        decide: str | None = None,
        bill_block: int | None = None,
        scorer: StageScorer | None = None,
        scorer_factory=None,
        mesh=None,
        shards: int | None = None,
        model_shards: int = 1,
        rebalance: bool = False,
        n_devices: int | None = None,
        backoff: BackoffPolicy | None = None,
        sleep=None,
    ) -> "CompiledCascade":
        """Bind the cascade to an execution backend.

        ``backend``: a registered name, ``"auto"`` (negotiates sharded ->
        device -> host from available devices; ``n_devices`` overrides the
        count for tests), or a ``Backend`` instance.  An explicitly named
        backend that is unavailable on this host raises ``ValueError``
        naming the rung and the backend's own ``available()`` reason —
        compile-time, not as an opaque trace error later; ``"auto"``
        logs each rung it skips on the ``repro.api`` logger instead.

        Host-only options: ``decide`` (``"reference"`` numpy oracle, the
        default, or ``"kernel"`` for the Pallas chunk-decide) and
        ``bill_block`` (producer row-quantization billing granularity).

        ``scorer``: a ``StageScorer`` template (DESIGN.md §11) for fully
        lazy scoring — ``evaluate(x=...)`` feeds the raw batch operand
        straight to the bound scorer on every backend (the host rung
        drives it through ``host_producer``).  Defaults to the template
        ``fit`` calibrated (model-backed fit); otherwise batches are
        precomputed score matrices.  Sharded-only: ``mesh`` / ``shards``
        / ``rebalance`` / ``model_shards`` (``model_shards > 1`` shards
        every stage's param slab over a second ``"model"`` mesh axis —
        DESIGN.md §13 — and needs a backend whose capabilities carry
        ``model_parallel``).

        ``backoff``/``sleep`` tune the runtime degradation ladder
        (DESIGN.md §10): construction and wave failures are retried with
        capped exponential backoff, then fall one rung (sharded ->
        device -> host), recording ``DegradationEvent``s on the result.
        ``sleep`` is injectable so chaos tests never actually wait.
        """
        if scorer_factory is not None:
            raise TypeError(
                "scorer_factory= was removed: pass scorer= with a "
                "repro.api.StageScorer template (MatrixScorer/TreeScorer/"
                "LatticeScorer/NeuralScorer, or any bind(dplan) "
                "implementation — DESIGN.md §11)"
            )
        if scorer is None:
            scorer = self.scorer
        if scorer is not None and not isinstance(scorer, StageScorer):
            raise TypeError(
                f"scorer= must be a repro.api.StageScorer, got "
                f"{type(scorer).__name__} (bare factories/BoundScorers are "
                "internal; wrap them in a StageScorer with a bind() method)"
            )
        if isinstance(backend, str) and backend != AUTO:
            # an explicit rung request fails HERE with the backend's own
            # reason, not later with a registry KeyError or an XLA trace
            # error from a mesh over zero devices
            try:
                b = get_backend(backend)
            except KeyError:
                raise ValueError(
                    f"unknown backend {backend!r}; registered backends: "
                    f"{list(backend_names())} (or {AUTO!r} to negotiate)"
                ) from None
            ok, why = b.available(n_devices=n_devices)
            if not ok and (
                mesh is not None or shards is not None or int(model_shards) > 1
            ):
                # an explicit mesh / shard count that fits the live
                # device count overrides the rung's min-device heuristic
                # (a 1-shard mesh is a legitimate degenerate config);
                # rechecking at the satisfied count keeps the other
                # availability reasons (interpret-only, injected outages)
                import jax

                nd = len(jax.devices()) if n_devices is None else n_devices
                want = (
                    int(shards or 1) * max(1, int(model_shards))
                    if mesh is None
                    else 0
                )
                if nd >= want:
                    ok, why = b.available(
                        n_devices=max(nd, b.capabilities.min_devices)
                    )
            if not ok:
                raise ValueError(
                    f"backend {backend!r} is unavailable here: {why} "
                    f"(compile({AUTO!r}) negotiates a usable rung instead)"
                )
        else:
            b = resolve_backend(backend, n_devices=n_devices)
        caps = b.capabilities
        if caps.on_device:
            for opt, val in (("decide", decide), ("bill_block", bill_block)):
                if val is not None:
                    raise ValueError(
                        f"{opt!r} is a host-backend option; backend is {b.name!r}"
                    )
        if not caps.data_parallel and (
            mesh is not None or shards is not None or rebalance
        ):
            raise ValueError(
                f"mesh/shards/rebalance require a data-parallel backend "
                f"(backend is {b.name!r})"
            )
        if int(model_shards) > 1 and not getattr(
            caps, "model_parallel", False
        ):
            raise ValueError(
                f"model_shards requires a model-parallel backend (backend "
                f"is {b.name!r}; the built-in 'sharded' rung carries the "
                "capability — DESIGN.md §13)"
            )
        if int(model_shards) > 1 and self.grouped is not None:
            raise ValueError(
                "model_shards > 1 is batch-run only: the grouped (ranking) "
                "decide stays data-parallel (DESIGN.md §13); compile with "
                "model_shards=1 for grouped serving"
            )
        if self.grouped is not None and not getattr(caps, "grouped", False):
            raise ValueError(
                f"fit(groups=...) needs a backend with the grouped "
                f"capability; backend {b.name!r} has none (the built-in "
                "'host'/'device'/'sharded' rungs all do)"
            )
        return CompiledCascade(
            fitted=self,
            backend=b,
            plan=self.plan(chunk_t),
            block_n=block_n,
            interpret=interpret,
            decide=decide,
            bill_block=bill_block,
            scorer=scorer,
            mesh=mesh,
            shards=shards,
            model_shards=model_shards,
            rebalance=rebalance,
            backoff=backoff,
            sleep=sleep,
        )


class CompiledCascade:
    """A ``FittedCascade`` bound to one backend, ready to run batches.

    On-device backends construct their executor here (one compiled trace
    then serves every same-shape ``evaluate``); the host backend binds a
    fresh ``ChunkedExecutor`` per call (its "compilation" is just the
    plan).  ``serve`` spins up a ``QWYCServer`` on the same backend — the
    server sizes its own executor to the flush capacity.
    """

    def __init__(
        self,
        fitted: FittedCascade,
        backend: Backend,
        plan: CascadePlan,
        *,
        block_n: int | None = None,
        interpret: bool | None = None,
        decide: str | None = None,
        bill_block: int | None = None,
        scorer: StageScorer | None = None,
        mesh=None,
        shards: int | None = None,
        model_shards: int = 1,
        rebalance: bool = False,
        backoff: BackoffPolicy | None = None,
        sleep=None,
    ):
        self.fitted = fitted
        self.backend = backend
        self.plan = plan
        self.block_n = block_n
        self.interpret = interpret
        self.decide = decide or "reference"
        if self.decide not in ("reference", "kernel"):
            raise ValueError(
                f"decide must be 'reference' or 'kernel', got {decide!r}"
            )
        self.bill_block = bill_block
        self.scorer_template = scorer
        self.mesh = mesh
        self.shards = shards
        self.model_shards = max(1, int(model_shards))
        self.rebalance = bool(rebalance)
        self.ladder = DegradationLadder(backoff=backoff, sleep=sleep)
        self._executor = None
        # runtime degradation ladder (DESIGN.md §10): construction
        # failures retry with backoff, then fall one rung; the recorded
        # events are the API surface chaos tests assert on
        try:
            self._bind_backend(self.backend)
        except RuntimeError as e:
            self._fall_and_rebind("construct", e)

    def _fall_and_rebind(self, kind: str, error, accept=None) -> Backend:
        """Fall down the rung ladder until a backend binds (or the ladder
        runs out and re-raises the last error)."""
        err = error
        while True:
            nxt = self.ladder.fall(kind, self.backend.name, err, accept=accept)
            try:
                self._bind_backend(nxt)
                return nxt
            except RuntimeError as e:
                err = e

    def _bind_backend(self, backend: Backend) -> None:
        """(Re)build the executor for one rung; the host rung binds at
        ``evaluate`` time.  Data-parallel options only travel to rungs
        that understand them, so a sharded -> device fall drops them."""
        self.backend = backend
        if not backend.capabilities.on_device:
            self._executor = None
            return
        dplan = DevicePlan.from_plan(self.plan)
        self.scorer = (
            self.scorer_template.bind(dplan)
            if self.scorer_template is not None
            else matrix_stage_scorer(dplan)
        )
        opts: dict = dict(
            scorer=self.scorer,
            block_n=DEFAULT_BLOCK_N if self.block_n is None else self.block_n,
            interpret=self.interpret,
        )
        if backend.capabilities.data_parallel:
            opts.update(
                mesh=self.mesh, shards=self.shards, rebalance=self.rebalance
            )
            # model_shards likewise only travels to a model-parallel rung
            # (a sharded -> device fall drops the whole 2-D request)
            if self.model_shards > 1 and getattr(
                backend.capabilities, "model_parallel", False
            ):
                opts["model_shards"] = self.model_shards
        self._executor = self.ladder.attempt(
            "construct", backend.name,
            lambda: backend.make_executor(dplan, **opts),
        )

    @property
    def backend_name(self) -> str:
        return self.backend.name

    @property
    def degradation_events(self) -> list[DegradationEvent]:
        """Runtime ladder history: same-rung recoveries and rung falls."""
        return self.ladder.events

    @property
    def traces(self) -> int | None:
        """Compiled-trace count (on-device backends; None on host)."""
        return getattr(self._executor, "traces", None)

    def _ordered_scores(self, scores, x) -> np.ndarray:
        if scores is None:
            if x is None:
                raise ValueError("evaluate() needs scores=, x=, or producer=")
            if self.fitted.score_fn is None and self.scorer_template is None:
                raise ValueError(
                    "evaluate(x=...) needs a score_fn captured by fit() "
                    "(or compile with scorer= for fully-lazy scoring)"
                )
            scores = self.fitted.score_fn(x)
        F = np.asarray(scores)
        if F.ndim != 2 or F.shape[1] != self.fitted.T:
            raise ValueError(
                f"scores must be (N, {self.fitted.T}) in original model "
                f"order, got {F.shape}"
            )
        return F[:, self.fitted.model.order]

    def evaluate(
        self,
        scores: np.ndarray | None = None,
        *,
        x=None,
        producer=None,
        n: int | None = None,
        row_order=None,
        capacity: int | None = None,
    ) -> ExecutorResult:
        """Run the cascade on one batch.

        Scoring input, by backend:
          * ``scores``: precomputed ``(N, T)`` matrix in ORIGINAL model
            order (works on every backend; permuted to cascade order
            internally).
          * ``x``: the raw batch operand — fed straight to the compiled
            ``scorer=`` template (fully lazy, every backend; the host
            rung drives it through ``host_producer``), else scored
            through the ``fit``-captured ``score_fn``.
          * ``producer(rows, t0, t1)``: host-backend lazy producer in
            cascade order (requires ``n``).

        ``row_order`` / ``capacity`` follow the executor contracts
        (initial active-set ordering; pinned buffer size for trace reuse).

        Wave failures (``RuntimeError`` from the device program) are
        retried on the same rung with backoff, then fall a rung and
        re-run — the host floor is only accepted if this call can score
        there (precomputed ``scores`` or a ``fit``-captured ``score_fn``).
        """
        while True:
            if not self.backend.capabilities.on_device:
                return self._evaluate_host(scores, x, producer, n, row_order)
            if producer is not None:
                raise ValueError(
                    "producer= is a host-backend option; compile with "
                    "scorer= for lazy on-device scoring"
                )
            if self.scorer_template is not None:
                if x is None:
                    raise ValueError(
                        "compiled with scorer=: pass the scorer's batch "
                        "operand via x= (it consumes raw inputs, not "
                        "score matrices)"
                    )
                operand = x
                run_n = int(np.shape(x)[0]) if n is None else n
            else:
                operand = self._ordered_scores(scores, x)
                run_n = operand.shape[0]
            ex = self._executor
            try:
                return self.ladder.attempt(
                    "wave", self.backend.name,
                    lambda: ex.run(
                        operand, run_n, row_order=row_order, capacity=capacity
                    ),
                )
            except RuntimeError as e:
                # host can only take over when this call is scoreable there
                can_host = (
                    scores is not None
                    or self.fitted.score_fn is not None
                    or (self.scorer_template is not None and x is not None)
                )
                self._fall_and_rebind(
                    "wave", e,
                    accept=lambda b: b.capabilities.on_device or can_host,
                )

    def _evaluate_host(self, scores, x, producer, n, row_order) -> ExecutorResult:
        if producer is not None:
            if n is None:
                raise ValueError("producer= requires n= (batch row count)")
            p = producer
        elif self.scorer_template is not None and scores is None:
            if x is None:
                raise ValueError(
                    "compiled with scorer=: pass the scorer's batch "
                    "operand via x="
                )
            p, n = host_producer(self.scorer_template, self.plan, x)
        else:
            ordered = self._ordered_scores(scores, x)
            n = ordered.shape[0]
            p = matrix_producer(ordered)
        decide_fn = None
        bill = 1 if self.bill_block is None else self.bill_block
        if self.decide == "kernel":
            from repro.kernels import ops

            bn = 256 if self.block_n is None else self.block_n
            decide_fn = ops.kernel_decide_fn(
                block_n=bn, interpret=self.interpret
            )
            if self.bill_block is None:
                bill = bn
        ex = self.backend.make_executor(
            self.plan, producer=p, decide_fn=decide_fn, bill_block=bill
        )
        return ex.run(n, row_order=row_order)

    def _grouped_plan(self):
        """The fit-time ``GroupedPlan``, validated against this compile's
        stage layout (a ``compile(chunk_t=...)`` override would desync
        the per-stage thresholds from the executor's stages)."""
        gp = self.fitted.grouped
        if gp is None:
            raise ValueError(
                "no grouped plan: calibrate with fit(..., groups=sizes, "
                "topk=k) to rank ragged query groups"
            )
        if list(self.plan.stages) != list(gp.plan.stages):
            raise ValueError(
                f"compile(chunk_t=...) changed the stage layout "
                f"({len(self.plan.stages)} stages vs the grouped plan's "
                f"{gp.S}); compile with chunk_t={gp.plan.chunk_t} (the "
                "fit-time chunking the group thresholds were calibrated on)"
            )
        if self.scorer_template is not None:
            raise ValueError(
                "grouped ranking scores through the matrix scorer; drop "
                "compile(scorer=...) for rank()/grouped serve()"
            )
        return gp

    def rank(
        self,
        scores: np.ndarray | None = None,
        *,
        x=None,
        groups=None,
        capacity_groups: int | None = None,
        margin_inf: bool = False,
    ) -> list[dict]:
        """Rank one batch of ragged query groups through the grouped
        cascade (requires ``fit(..., groups=)``).

        ``scores`` is the flat ``(N, T)`` per-document score matrix in
        ORIGINAL model order (or pass ``x`` to score through the
        ``fit``-captured ``score_fn``); ``groups`` the per-query document
        counts for THIS batch (documents of each query contiguous).
        Returns one dict per query, in order: ``"ranking"`` (top-k LOCAL
        document positions), ``"exit_stage"`` (1-based), ``"margin"``.
        ``margin_inf=True`` forces the full cascade (the parity oracle
        configuration).  Per-flush billing lands on ``last_rank_stats``.
        """
        from repro.ranking import GroupedRankServer, group_offsets

        gp = self._grouped_plan()
        if groups is None:
            raise ValueError(
                "rank() needs groups= (per-query document counts for this "
                "batch)"
            )
        if scores is None:
            if x is None:
                raise ValueError("rank() needs scores= or x=")
            if self.fitted.score_fn is None:
                raise ValueError(
                    "rank(x=...) needs a score_fn captured by fit()"
                )
            scores = self.fitted.score_fn(x)
        F = np.asarray(scores)
        sizes = np.asarray(groups, dtype=np.int64)
        if F.ndim != 2 or F.shape[1] != self.fitted.T:
            raise ValueError(
                f"scores must be (N, {self.fitted.T}) in original model "
                f"order, got {F.shape}"
            )
        if sizes.ndim != 1 or int(sizes.sum()) != F.shape[0]:
            raise ValueError(
                f"group sizes sum to {sizes.sum()} but scores have "
                f"{F.shape[0]} rows"
            )
        server = GroupedRankServer(
            gp,
            executor=(
                self._executor
                if self.backend.capabilities.on_device
                else None
            ),
            batch_groups=max(int(sizes.size), 1),
            capacity_groups=capacity_groups,
            margin_inf=margin_inf,
        )
        offsets = group_offsets(sizes)
        for i in range(sizes.size):
            server.submit(F[offsets[i] : offsets[i + 1]])
        out = server.drain()
        self.last_rank_stats = server.stats
        return out

    def _serve_grouped(
        self,
        *,
        score_fn=None,
        batch_size: int = 32,
        policy: str | None = None,
        streaming: bool = False,
        **server_kw,
    ):
        """Grouped serving: a ``GroupedRankServer`` on this backend.

        ``batch_size`` counts QUERIES per flush; ``policy`` becomes the
        streaming admission policy (the row-level default maps to
        ``"skip-ahead"``; pass ``"wait"`` for strict arrival order).
        """
        from repro.ranking import GroupedRankServer

        gp = self._grouped_plan()
        executor = (
            self._executor if self.backend.capabilities.on_device else None
        )
        if streaming:
            if executor is None:
                raise ValueError(
                    "grouped streaming needs an on-device backend with the "
                    "grouped admission ring; compile onto 'device'"
                )
            if not hasattr(executor, "run_stream_grouped"):
                raise ValueError(
                    f"backend {self.backend.name!r} has no grouped "
                    "streaming path; compile onto 'device'"
                )
        return GroupedRankServer(
            gp,
            score_fn=(
                self.fitted.score_fn if score_fn is None else score_fn
            ),
            executor=executor,
            batch_groups=batch_size,
            streaming=streaming,
            policy="skip-ahead" if policy in (None, "sorted-kernel") else policy,
            **server_kw,
        )

    def serve(
        self,
        *,
        score_fn: Callable | None = None,
        chunk_score_fn: Callable | None = None,
        batch_size: int = 256,
        policy: str | None = None,
        audit_full_scores: bool = True,
        score_block_n: int = 1,
        streaming: bool = False,
        window: int | None = None,
        max_wait: float | None = None,
        **server_kw,
    ):
        """Build a batched ``QWYCServer`` on this backend.

        ``policy`` is the server's sorting/decide policy (what its own
        ``backend=`` kwarg has always named: ``cascade-scan`` | ``kernel``
        | ``sorted-kernel``) — orthogonal to the execution backend.  By
        default it follows the compiled scorer: ``kernel`` where the bound
        scorer carries per-row state (its stage-0 scores cannot be computed
        apart from that state, so there is no sort key), ``sorted-kernel``
        for every other.
        ``score_fn`` defaults to the one captured by ``fit``; a compiled
        ``scorer=`` template becomes the server's device scorer.  The
        server builds its own executor sized to the flush capacity, so
        compiled-evaluate traces and serving traces are independent.

        ``streaming=True`` builds a continuous-batching
        ``StreamingServer`` instead (DESIGN.md §8; requires a backend
        with the ``streaming`` capability): ``batch_size`` becomes the
        survivor-slot capacity, ``window`` the admission-ring size, and
        ``max_wait`` the partial-admission deadline in stage steps.
        Streaming admission replaces the sorting policy, so ``policy``
        must be left unset (streaming admits in ``kernel`` order).

        A grouped fit (``fit(..., groups=)``) serves QUERIES, not rows:
        the call routes to ``_serve_grouped`` and returns a
        ``repro.ranking.GroupedRankServer`` (``batch_size`` counts
        queries per flush; ``policy`` becomes the admission policy).
        """
        if self.fitted.grouped is not None:
            return self._serve_grouped(
                score_fn=score_fn,
                batch_size=batch_size,
                policy=policy,
                streaming=streaming,
                **server_kw,
            )
        from repro.serving.engine import QWYCServer, StreamingServer

        opts: dict = {}
        if self.backend.capabilities.data_parallel:
            if self.mesh is not None:
                opts["mesh"] = self.mesh
            if self.shards is not None:
                opts["shards"] = self.shards
            if self.model_shards > 1 and getattr(
                self.backend.capabilities, "model_parallel", False
            ):
                opts["model_shards"] = self.model_shards
            if self.rebalance:
                opts["rebalance"] = True
        if self.block_n is not None:
            server_kw.setdefault("block_n", self.block_n)
        common = dict(
            score_fn=self.fitted.score_fn if score_fn is None else score_fn,
            chunk_score_fn=chunk_score_fn,
            batch_size=batch_size,
            chunk_t=self.plan.chunk_t,
            audit_full_scores=audit_full_scores,
            score_block_n=score_block_n,
            scorer=(
                self.scorer_template
                if self.backend.capabilities.on_device
                else None
            ),
            exec_backend=self.backend,
            backend_opts=opts,
        )
        if streaming:
            if not getattr(self.backend.capabilities, "streaming", False):
                raise ValueError(
                    f"backend {self.backend.name!r} does not support "
                    "streaming admission; compile onto 'device' or 'sharded'"
                )
            if policy is not None:
                # mirror StreamingServer's own backend= guard: streaming
                # admission IS the ordering policy, so an explicit policy
                # request must fail loudly, not be silently replaced
                raise ValueError(
                    "streaming admission replaces the sorting policy; drop "
                    f"policy={policy!r} when serving with streaming=True"
                )
            return StreamingServer(
                self.fitted.model,
                window=window,
                max_wait=max_wait,
                **common,
                **server_kw,
            )
        if window is not None or max_wait is not None:
            raise ValueError("window/max_wait require serve(streaming=True)")
        return QWYCServer(
            self.fitted.model,
            backend=policy or self._default_policy(),
            **common,
            **server_kw,
        )

    def _default_policy(self) -> str:
        """``kernel`` for a stateful bound scorer, else ``sorted-kernel``."""
        if self.scorer_template is None:
            return "sorted-kernel"
        bound = (
            self.scorer
            if self.backend.capabilities.on_device
            else self.scorer_template.bind(DevicePlan.from_plan(self.plan))
        )
        return "kernel" if bound.stateful else "sorted-kernel"
