"""Batched-request QWYC serving engine — the paper's production use-case.

Requests (feature vectors) arrive one at a time; the engine micro-batches
them and runs the cascade through the **chunked lazy executor**
(``repro.core.executor``, DESIGN.md §4): the QWYC plan is split into
``chunk_t``-sized stages, and between stages the surviving rows are
compacted and only the next stage's base models are evaluated — early-exited
requests genuinely skip the remaining base-model work.

Three execution backends, differing ONLY in batching/sorting policy and the
per-stage decide implementation (the executor owns all control flow):

  * "cascade-scan":   reference numpy decide per stage; semantics oracle +
                      what a real host loop would run.
  * "kernel":         Pallas chunk-decide kernel per stage (TPU target).
  * "sorted-kernel":  beyond-paper — rows are sorted by the first cascade
                      model's score before execution, so easy examples
                      cluster into VMEM blocks that retire early inside a
                      chunk (per-block early exit; see DESIGN.md §3).
                      Results are scattered back to submission order (the
                      inverse-permutation guarantee tested in
                      tests/test_serving.py).

Score producers:

  * ``chunk_score_fn(x, rows, t0, t1)`` — the lazy path: scores of cascade
    positions [t0, t1) for the given row indices only (wire it to
    ``ops.gbt_scores``/``ops.lattice_scores`` with their ``t0``/``t1``/
    ``rows`` arguments over order-permuted stacked params).
  * ``score_fn(x) -> (N, T)`` in ORIGINAL model order — eager back-compat
    fallback: the matrix is materialized once per batch and the executor
    reads from it (no base-model work is skipped; ``ServeStats``
    scores_computed records the difference).
  * ``exec_backend="device"`` + ``scorer=`` (a ``repro.api.StageScorer``
    template, DESIGN.md §11) — the serving fast path (DESIGN.md §5): the
    whole stage loop (scoring, decide, compaction, early exit) runs as
    ONE jit'd device program; the host stage loop above stays as the
    oracle and the host-producer escape hatch.
  * ``exec_backend="sharded"`` (DESIGN.md §6) — the device program
    additionally runs under ``shard_map`` with the microbatch split over
    a ``("data",)`` mesh axis: each flush serves ``shards x batch_size``
    requests at per-device cost ~batch_size.

Execution backends are resolved by name through the backend registry
(``repro.api``, DESIGN.md §7) — the server never constructs an executor
class directly, so new substrates plug in without touching this module.
(The legacy ``device=True`` boolean and ``device_scorer_factory=``
spellings were retired after their deprecation cycle; both raise with
the replacement named.)

Filter-and-Score mode (neg_only): positively classified requests get the
full ensemble score attached, matching the paper's production setting —
lazily, since a neg_only positive by construction ran the whole cascade
(its ``g_final`` IS the full score).

``StreamingServer`` (DESIGN.md §8) replaces batch-at-a-time flushing with
continuous batching: requests carry arrival steps, wait in an
arrival-order queue, and the on-device admission ring refills freed
survivor slots mid-cascade (``run_stream``), so tail requests stop
holding whole batches hostage.  Per-request enqueue->decision latency
(in deterministic stage steps) and slot occupancy land in ``ServeStats``.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro import tracing
from repro.api.backends import BackoffPolicy, DegradationLadder
from repro.core.executor import CascadePlan, matrix_producer
from repro.core.qwyc import QWYCModel
from repro.kernels import ops
from repro.kernels.device_executor import (
    BoundScorer,
    DevicePlan,
    compile_program,
    matrix_stage_scorer,
    pad_rows,
)
from repro.serving.watchdog import DriftWatchdog, WatchdogConfig, widen_plan

__all__ = ["ServeStats", "QWYCServer", "StreamingServer"]

BACKENDS = ("cascade-scan", "kernel", "sorted-kernel")


@dataclasses.dataclass
class ServeStats:
    n_requests: int = 0
    n_batches: int = 0
    models_evaluated: int = 0  # sum of exit steps (paper's modeled count)
    full_cost: float = 0.0
    actual_cost: float = 0.0  # modeled cost at the paper's accounting
    diffs_vs_full: int = 0
    # lazy-execution accounting: what was ACTUALLY computed, vs modeled
    scores_computed: int = 0  # base-model scores produced on the serving path
    scores_possible: int = 0  # N * T — the eager full-matrix bill
    audit_scores: int = 0  # extra scores for diff auditing (not serving work)
    chunk_survivors: list[int] = dataclasses.field(default_factory=list)
    # chunk_survivors[k] = total rows that entered stage k, summed over batches
    # streaming accounting (StreamingServer; all in deterministic stage
    # steps — the perf gate locks these, never wall-clock)
    admitted_rows: int = 0  # rows admitted into stream survivor slots
    stream_steps: int = 0  # total streaming loop steps executed
    stream_slot_steps: int = 0  # sum over steps of live slots (occupancy mass)
    stream_cap_steps: int = 0  # sum over steps of slot capacity
    latency_steps: list[int] = dataclasses.field(default_factory=list)
    # latency_steps[i] = enqueue->decision latency of request i, in steps
    # guarded-serving accounting (DESIGN.md §10) — additive chaos
    # counters, deliberately OUTSIDE the perf gate's baseline set
    quarantined: int = 0  # rows rejected at admission (never batched)
    degradation_events: list = dataclasses.field(default_factory=list)
    # DegradationEvent per ladder action: same-rung recovery or rung fall
    watchdog_alarms: int = 0
    watchdog_state: str = "off"  # off | ok | alarmed | recovering
    watchdog_stat: float = 0.0  # current sequential llr
    watchdog_margin: float = 0.0  # threshold widening in force next flush
    watchdog_recovery_step: int | None = None  # flush index of last recovery
    # blocking device->host reads the flushes made (1 a flush on one
    # device); like the guarded counters, outside the perf gate's baseline
    device_reads: int = 0

    @property
    def mean_models(self) -> float:
        return self.models_evaluated / max(self.n_requests, 1)

    @property
    def speedup(self) -> float:
        return self.full_cost / max(self.actual_cost, 1e-9)

    @property
    def diff_rate(self) -> float:
        return self.diffs_vs_full / max(self.n_requests, 1)

    @property
    def compute_fraction(self) -> float:
        """Scores actually produced / scores the eager path would produce."""
        return self.scores_computed / max(self.scores_possible, 1)

    @property
    def mean_occupancy(self) -> float:
        """Mean live-slot fraction over all streaming loop steps."""
        return self.stream_slot_steps / max(self.stream_cap_steps, 1)

    def latency_pct(self, q: float) -> float:
        """q-th percentile of per-request enqueue->decision latency
        (stage steps)."""
        if not self.latency_steps:
            return 0.0
        return float(np.percentile(np.asarray(self.latency_steps), q))

    @property
    def latency_mean(self) -> float:
        if not self.latency_steps:
            return 0.0
        return float(np.mean(self.latency_steps))

    @property
    def latency_p50(self) -> float:
        return self.latency_pct(50)

    @property
    def latency_p95(self) -> float:
        return self.latency_pct(95)

    @property
    def latency_p99(self) -> float:
        return self.latency_pct(99)


def sort_key_program(scorer: BoundScorer):
    """The sorted-kernel policy's key program, ``(x, n) -> rows``.

    The key is the first cascade model's scores, computed on the device
    from the same stage-0 slab the loop body uses.  The program returns
    the stage loop's initial ``(cap,)`` rows buffer, so the permutation
    never leaves the device: the ``n`` rows in
    ``np.argsort(key, kind="stable")`` order, then ``cap`` on every lane
    past ``n``."""

    def key_rows(x, n):
        with jax.named_scope(tracing.SORT_KEY):
            cap = x.shape[0]
            lane = jnp.arange(cap, dtype=jnp.int32)
            key = scorer.fn(x, lane, jnp.int32(0), n)[:, 0]
            # stable on (padding lane, key): padding lanes sort last
            _, _, perm = jax.lax.sort(
                ((lane >= n).astype(jnp.int32), key, lane),
                num_keys=2,
                is_stable=True,
            )
            return jnp.where(lane < n, perm, cap)

    return key_rows


class QWYCServer:
    def __init__(
        self,
        qwyc: QWYCModel,
        score_fn: Callable[[np.ndarray], np.ndarray] | None = None,
        batch_size: int = 256,
        backend: str = "sorted-kernel",
        block_n: int = 64,
        chunk_t: int = 8,
        chunk_score_fn: Callable | None = None,
        audit_full_scores: bool = True,
        score_block_n: int = 1,
        device: bool | None = None,
        scorer=None,
        device_scorer_factory=None,
        mesh=None,
        rebalance: bool = False,
        exec_backend=None,
        backend_opts: dict | None = None,
        quarantine: bool = True,
        watchdog: bool | WatchdogConfig | DriftWatchdog | None = None,
        backoff: BackoffPolicy | None = None,
        sleep: Callable[[float], None] | None = None,
    ):
        """At least one of ``score_fn`` (eager, ORIGINAL model order),
        ``chunk_score_fn`` (lazy, cascade order — see module docstring) or
        ``scorer`` (a ``repro.api.StageScorer`` template, with an
        on-device ``exec_backend``) is required; when several are given
        the laziest serving path wins.
        ``audit_full_scores`` controls whether
        early-exited rows' full scores are recomputed for diff-vs-full
        accounting (audit work, tracked separately from serving work;
        without it ``diff_rate`` only covers rows that ran the full
        cascade).  ``score_block_n`` is the row-quantization granularity of
        ``chunk_score_fn`` (a blocked kernel pads survivors up to a block
        multiple, so actual compute exceeds rows requested); billing uses
        it so ``ServeStats.scores_computed`` reflects real work — set it to
        the block_n your producer passes to the score kernels, or leave at
        1 for exact producers.

        ``exec_backend`` selects the execution substrate through the
        backend registry (``repro.api``, DESIGN.md §7): ``"host"`` (the
        default — per-stage host loop, the semantics oracle), ``"device"``
        (the serving fast path, DESIGN.md §5: the whole stage loop as one
        jit'd device program, zero per-stage host round-trips),
        ``"sharded"`` (DESIGN.md §6: that program under ``shard_map``, the
        microbatch split over a ``("data",)`` mesh — ``batch_size`` rows
        PER SHARD per flush, partial final flushes padded so one compiled
        trace serves every flush), or ``"auto"`` to negotiate from the
        available devices.  A ``Backend`` instance is accepted directly.
        ``backend_opts`` forwards construction options (``mesh=``,
        ``shards=``, ``rebalance=``, ``rebalance_ratio=``) to the
        backend's ``make_executor``.

        On-device scoring comes from ``scorer`` — a ``StageScorer``
        template bound per device-plan variant (fully lazy, on device;
        stateful scorers like ``NeuralScorer`` carry their per-row state
        through the survivor buffers) — or falls back to ``score_fn``
        (matrix materialized eagerly per batch; control flow still moves
        on device).  The host executor remains the oracle and
        the escape hatch for arbitrary host-side producer injection
        (``chunk_score_fn``); on device an available ``chunk_score_fn`` is
        still used for diff auditing.  The ``cascade-scan`` policy's numpy
        decide is host-only, so on device it executes identically to
        ``kernel`` (policies keep their sorting behavior).

        Guarded serving (DESIGN.md §10): ``quarantine`` (default on)
        validates every ``submit`` — float32-convertible, shape-locked to
        the first accepted row, all-finite — and rejected rows come back
        from ``drain`` with an explicit ``quarantined`` verdict instead
        of poisoning a whole device batch.  ``watchdog`` (True, a
        ``WatchdogConfig``, or a ``DriftWatchdog``) runs the sequential
        drift test over the audit stream and degrades the decide policy
        on alarm; it requires an audited configuration (``score_fn``, or
        ``chunk_score_fn`` with ``audit_full_scores=True``).
        ``backoff``/``sleep`` tune the runtime degradation ladder that
        retries failed waves and falls sharded -> device -> host
        (``sleep`` is injectable so chaos tests never wait); ladder
        history lands in ``ServeStats.degradation_events``.

        ``mesh=``/``rebalance=`` remain supported spellings of the same
        ``backend_opts`` entries and imply ``exec_backend="sharded"``.
        """
        from repro.api.registry import resolve_backend
        from repro.api.scorers import StageScorer

        if device is not None:
            # the PR-4 deprecation shim, retired after its warning cycle
            raise TypeError(
                "QWYCServer(device=...) was removed after its deprecation "
                "cycle: pass exec_backend='device' (or "
                "'auto'/'host'/'sharded' — see repro.api) instead"
            )
        if device_scorer_factory is not None:
            raise TypeError(
                "device_scorer_factory= was removed: pass scorer= with a "
                "repro.api.StageScorer template (MatrixScorer/TreeScorer/"
                "LatticeScorer/NeuralScorer — DESIGN.md §11); the server "
                "binds it per device-plan variant itself"
            )
        if scorer is not None and not isinstance(scorer, StageScorer):
            raise TypeError(
                f"scorer= must be a repro.api.StageScorer, got "
                f"{type(scorer).__name__}"
            )
        opts = dict(backend_opts or {})
        if mesh is not None:
            opts.setdefault("mesh", mesh)
        if rebalance:
            opts["rebalance"] = True
        if exec_backend is None:
            # legacy dispatch forwarded into the backend registry: a mesh
            # (or shard count) means sharded, everything else keeps the
            # historical host default
            exec_backend = "sharded" if ("mesh" in opts or "shards" in opts) else "host"
        self.exec = resolve_backend(exec_backend)
        caps = self.exec.capabilities
        if opts.get("rebalance") and not caps.supports_rebalance:
            raise ValueError(
                "rebalance=True requires the sharded backend "
                f"(exec_backend is {self.exec.name!r}: nothing to repack)"
            )
        if not caps.data_parallel and ("mesh" in opts or "shards" in opts):
            raise ValueError(
                "mesh/shards require a data-parallel backend "
                f"(exec_backend is {self.exec.name!r})"
            )
        if int(opts.get("model_shards") or 1) > 1 and not getattr(
            caps, "model_parallel", False
        ):
            raise ValueError(
                "model_shards > 1 requires a model-parallel backend "
                f"(exec_backend is {self.exec.name!r}; see "
                "Backend.capabilities.model_parallel, DESIGN.md §13)"
            )
        on_device = caps.on_device
        if score_fn is None and chunk_score_fn is None and (
            not on_device or scorer is None
        ):
            raise ValueError(
                "need score_fn, chunk_score_fn, or an on-device exec_backend "
                "with scorer="
            )
        if backend not in BACKENDS:
            raise ValueError(f"unknown backend {backend!r}; choose from {BACKENDS}")
        if scorer is not None and not on_device:
            raise ValueError(
                "scorer= requires an on-device exec_backend "
                "('device', 'sharded', or 'auto' resolving to one)"
            )
        if on_device and scorer is None and score_fn is None:
            raise ValueError(
                "on-device serving needs scorer= or score_fn"
            )
        self.qwyc = qwyc
        self.score_fn = score_fn
        self.chunk_score_fn = chunk_score_fn
        self.batch_size = batch_size
        self.backend = backend
        self.block_n = block_n
        self.chunk_t = chunk_t
        self.audit_full_scores = audit_full_scores
        self.score_block_n = max(1, int(score_block_n))
        self.device = on_device  # True iff the stage loop runs on device
        self.scorer_template = scorer
        self.mesh = None
        self.n_shards = 1
        if caps.data_parallel:
            # ``resolve_mesh`` is an OPTIONAL backend extension (the
            # bundled sharded backend has it); a protocol-conforming
            # third-party backend without it gets mesh/shards passed
            # through to make_executor untouched — the server only needs
            # the shard COUNT up front, to size its flush
            resolver = getattr(self.exec, "resolve_mesh", None)
            if resolver is not None:
                # forward the model axis only when requested: a resolver
                # predating DESIGN.md §13 keeps its 2-arg signature, and
                # an explicit mesh would otherwise silently win over
                # model_shards and drop the whole 2-D request
                mkw = {}
                if int(opts.get("model_shards") or 1) > 1:
                    mkw["model_shards"] = int(opts["model_shards"])
                self.mesh = resolver(
                    opts.pop("mesh", None), opts.pop("shards", None), **mkw
                )
                opts["mesh"] = self.mesh
            else:
                self.mesh = opts.get("mesh")
            if self.mesh is not None:
                self.n_shards = int(self.mesh.shape["data"])
            elif opts.get("shards"):
                self.n_shards = int(opts["shards"])
            else:
                self.n_shards = len(jax.devices())
        self.rebalance = bool(opts.get("rebalance", False))
        self._exec_opts = opts
        # data-parallel serving scales the microbatch with the mesh:
        # batch_size rows PER SHARD per flush
        self.flush_size = batch_size * self.n_shards
        self.plan = CascadePlan.from_qwyc(qwyc, chunk_t=chunk_t)
        self.stats = ServeStats()
        self._queue: list[np.ndarray] = []
        self._qseqs: list[int] = []  # submission seq of each queued row
        self._results: list[tuple[int, dict]] = []  # (seq, result)
        self._quarantined: list[tuple[int, dict]] = []
        self._seq = 0
        self._dev: tuple | None = None  # ACTIVE device-executor state
        # executor state per (rung, watchdog margin): a widened plan is a
        # different compiled trace, and a rung fall a different executor
        self._dev_cache: dict[tuple, tuple] = {}
        self.quarantine = bool(quarantine)
        self._row_shape: tuple | None = None  # admission shape lock
        self.ladder = DegradationLadder(
            backoff=backoff, sleep=sleep, events=self.stats.degradation_events
        )
        if watchdog is True:
            alpha = float(getattr(qwyc, "alpha", 0.0) or 0.0)
            watchdog = WatchdogConfig(p0=alpha)
        if isinstance(watchdog, WatchdogConfig):
            watchdog = DriftWatchdog(watchdog)
        self._watchdog: DriftWatchdog | None = watchdog or None
        self._wd_margin = 0.0
        if self._watchdog is not None:
            audited = (chunk_score_fn is not None and audit_full_scores) or (
                score_fn is not None and scorer is None
            )
            if not audited:
                raise ValueError(
                    "watchdog needs the per-flush audit signal: pass "
                    "score_fn, or chunk_score_fn with audit_full_scores=True"
                )
            self.stats.watchdog_state = self._watchdog.state

    def _admit(self, x) -> tuple[int, np.ndarray | None]:
        """Admission guard: (seq, float32 row) for a clean request, or
        (seq, None) after quarantining a poisoned one.

        The guard runs pre-admission so one poisoned row can never NaN a
        whole device batch (or trip the executors' finite check mid-
        flush); the row still gets a ``drain`` entry — ``quarantined:
        True, decision: None`` — at its submission position.  With
        ``quarantine=False`` conversion errors raise as they always did.
        """
        seq = self._seq
        self._seq += 1
        if not self.quarantine:
            return seq, np.asarray(x, dtype=np.float32)
        reason = None
        row = None
        try:
            row = np.asarray(x, dtype=np.float32)
        except (TypeError, ValueError) as e:
            reason = f"not convertible to float32: {e}"
        if reason is None:
            if self._row_shape is None:
                self._row_shape = row.shape
            elif row.shape != self._row_shape:
                reason = (
                    f"shape {row.shape} != locked request shape "
                    f"{self._row_shape}"
                )
        if reason is None and not np.isfinite(row).all():
            reason = "non-finite feature value (NaN/inf)"
        if reason is None:
            return seq, row
        self._quarantined.append(
            (seq, {"quarantined": True, "decision": None,
                   "models_evaluated": 0, "reason": reason})
        )
        self.stats.quarantined += 1
        return seq, None

    def submit(self, x: np.ndarray) -> None:
        seq, row = self._admit(x)
        if row is None:
            return
        self._queue.append(row)
        self._qseqs.append(seq)
        if len(self._queue) >= self.flush_size:
            self.flush()

    def _producers(self, xb: np.ndarray):
        """(producer, ordered_matrix|None) for this batch.

        Lazy billing happens in the executor (block-quantized via
        ``score_block_n``); the eager path bills the whole materialized
        matrix in ``flush``.  ``producer`` doubles as the audit access path.
        """
        m = self.qwyc
        if self.chunk_score_fn is not None:
            xb_j = jnp.asarray(xb)

            def producer(rows, t0, t1):
                return np.asarray(
                    self.chunk_score_fn(xb_j, np.asarray(rows), t0, t1)
                )

            return producer, None

        scores = np.asarray(self.score_fn(xb))  # (N, T) original order
        ordered = scores[:, m.order]
        return matrix_producer(ordered), ordered

    def _device_state(self):
        """(executor, scorer, eager_matrix, key_fn), built once per server.

        The device plan (and its lead stage, for ``sorted-kernel``) is
        fixed at server construction, so ONE compiled trace serves every
        flush — partial final batches are padded up to ``flush_size``
        (= ``batch_size``, or ``shards x batch_size`` under a mesh) via
        ``run(capacity=...)``.

        Keyed by (rung, watchdog margin): an alarmed watchdog widens the
        thresholds — a different device plan, hence a different compiled
        trace — and a ladder fall changes the executor class.  Each
        variant is built once and cached; ``self._dev`` always holds the
        ACTIVE variant.
        """
        key = (self.exec.name, self._wd_margin)
        cached = self._dev_cache.get(key)
        if cached is not None:
            self._dev = cached
            return cached
        plan = widen_plan(self.plan, self._wd_margin)
        if self.backend == "sorted-kernel":
            plan = dataclasses.replace(plan, lead_t=1)
        dplan = DevicePlan.from_plan(plan)
        if self.scorer_template is not None:
            scorer = self.scorer_template.bind(dplan)
            eager_matrix = False
        else:
            scorer = matrix_stage_scorer(dplan)
            eager_matrix = True
        # executor construction goes through the Backend protocol — the
        # server never names an executor class (DESIGN.md §7); retried
        # and rung-degraded by the caller's ladder on RuntimeError
        executor = self.exec.make_executor(
            dplan, scorer=scorer, block_n=self.block_n, **self._exec_opts
        )
        key_fn = None
        if self.backend == "sorted-kernel" and not eager_matrix:
            if scorer.fn is None:
                raise ValueError(
                    "the sorted-kernel policy needs a stateless scorer for "
                    "its sort key (stage-0 scores standalone); stateful "
                    f"scorers like {type(self.scorer_template).__name__} "
                    "serve under the 'kernel' policy"
                )
            jitted, compiled = jax.jit(sort_key_program(scorer)), set()

            def key_fn(x, n):
                # a refused program raises here, past the wave ladder
                compile_program(compiled, jitted, x, n)
                return jitted(x, n)
        self._dev = (executor, scorer, eager_matrix, key_fn)
        self._dev_cache[key] = self._dev
        return self._dev

    def _eager_or_raw(self, xb, eager_matrix):
        """(batch_operand, ordered|None) for an on-device run: the eager
        path materializes the (N, T) score matrix once per batch and
        permutes it to cascade order (the matrix scorer's operand and the
        audit/full-score source); lazy scorers consume raw features."""
        if not eager_matrix:
            return xb, None
        scores = np.asarray(self.score_fn(xb))  # (N, T) original order
        ordered = scores[:, self.qwyc.order]
        return ordered, ordered

    def _run_device(self, xb: np.ndarray, n: int):
        """Device fast path for one batch -> (result, ordered|None, billed).

        ``billed`` is the serving-work score count: the executor's slab
        billing plus (for ``sorted-kernel`` with a lazy scorer) the
        sort-key slab, which recomputes stage 0 once more on device.
        """
        with TraceAnnotation(tracing.FLUSH_PREPARE):
            executor, scorer, eager_matrix, key_fn = self._device_state()
            batch, ordered = self._eager_or_raw(xb, eager_matrix)
            # padded on the host to the flush capacity: one upload, and
            # one program for every flush size
            batch = pad_rows(batch, executor._cap(max(n, self.flush_size)))
            # a lazy sorted-kernel flush prepares ONCE; the key program
            # and the executor share the same device operand
            prepared = key_fn is not None
            if prepared:
                batch = scorer.prepare(batch)
        row_order = None
        key_scores = 0
        if self.backend == "sorted-kernel":
            with TraceAnnotation(tracing.FLUSH_SORT_KEY):
                if eager_matrix:
                    row_order = np.argsort(ordered[:, 0], kind="stable")
                else:
                    # launched, not waited on: the rows buffer stays on
                    # the device for the stage loop
                    row_order = key_fn(batch, n)
                    kb = scorer.block_n or self.block_n
                    key_scores = -(-n // kb) * kb * scorer.width
        res = executor.run(
            batch, n, row_order=row_order, capacity=self.flush_size,
            prepared=prepared,
        )
        billed = n * self.qwyc.T if eager_matrix else res.scores_computed + key_scores
        return res, ordered, billed

    def _fall_rung(self, error, *, streaming: bool = False) -> None:
        """Fall one rung after a failed wave and rebind executor state;
        re-raises ``error`` when no acceptable rung remains."""

        def accept(b):
            caps = b.capabilities
            if streaming and not getattr(caps, "streaming", False):
                return False
            if caps.on_device:
                return (
                    self.scorer_template is not None
                    or self.score_fn is not None
                )
            # the host floor needs a host-side score source
            return self.score_fn is not None or self.chunk_score_fn is not None

        nxt = self.ladder.fall("wave", self.exec.name, error, accept=accept)
        self.exec = nxt
        caps = nxt.capabilities
        self.device = caps.on_device
        if not caps.data_parallel:
            # data-parallel construction options don't travel down-rung;
            # flush_size stays fixed (the device path pads via capacity=)
            for k in ("mesh", "shards", "rebalance", "rebalance_ratio"):
                self._exec_opts.pop(k, None)
            self.rebalance = False
        if not caps.on_device:
            self.scorer_template = None
        self._dev = None
        self._dev_cache.clear()

    def flush(self) -> list[dict]:
        if not self._queue:
            return []
        with TraceAnnotation(
            tracing.FLUSH, index=self.stats.n_batches, n=len(self._queue)
        ):
            with TraceAnnotation(tracing.FLUSH_STACK):
                xb = np.stack(self._queue)
                seqs = self._qseqs
                self._queue = []
                self._qseqs = []
            n = xb.shape[0]

            # the wave ladder: retry the rung with backoff, then fall one
            # rung and re-run the SAME batch — no request is lost to a fault
            while True:
                try:
                    if self.device:
                        res, ordered, device_billed = self.ladder.attempt(
                            "wave", self.exec.name,
                            lambda: self._run_device(xb, n),
                        )
                        # the host chunk producer (escape hatch) doubles as
                        # the unbilled audit path; _producers builds the same
                        # wrapper the host path uses
                        audit_read = (
                            self._producers(xb)[0]
                            if self.chunk_score_fn is not None
                            else None
                        )
                    else:
                        res, ordered, audit_read, device_billed = (
                            self.ladder.attempt(
                                "wave", self.exec.name,
                                lambda: self._run_host(xb, n),
                            )
                        )
                    break
                except RuntimeError as e:
                    self._fall_rung(e)
            self.stats.device_reads += res.device_reads
            with TraceAnnotation(tracing.FLUSH_FINISH):
                return self._finish_flush(
                    xb, n, res, ordered, audit_read, device_billed, seqs
                )

    def _run_host(self, xb: np.ndarray, n: int):
        """Host stage-loop path for one batch ->
        (result, ordered|None, audit_read, billed=None)."""
        plan = widen_plan(self.plan, self._wd_margin)
        producer, ordered = self._producers(xb)
        audit_read = producer  # unbilled access path for diff auditing

        # backends differ only in sorting policy + decide implementation
        row_order = None
        if self.backend == "sorted-kernel":
            # the first model is its own leading stage (plan.lead_t=1): its
            # scores double as the sort key.  The memo below serves the
            # executor's (0, 1) stage, so the key compute is billed exactly
            # once — as that stage.
            plan = dataclasses.replace(plan, lead_t=1)
            col0 = producer(np.arange(n), 0, 1)
            row_order = np.argsort(col0[:, 0], kind="stable")
            inner = producer

            def producer(rows, t0, t1, _col0=col0, _inner=inner):
                if t0 == 0 and t1 == 1:
                    return _col0[np.asarray(rows)]
                return _inner(rows, t0, t1)

        decide_fn = (
            ops.kernel_decide_fn(block_n=self.block_n)
            if self.backend in ("kernel", "sorted-kernel")
            else None
        )
        res = self.exec.make_executor(
            plan,
            producer=producer,
            decide_fn=decide_fn,
            bill_block=self.score_block_n if ordered is None else 1,
        ).run(n, row_order=row_order)
        return res, ordered, audit_read, None

    def _finish_flush(
        self, xb, n, res, ordered, audit_read, device_billed, seqs
    ) -> list[dict]:
        """Audit, result assembly and stats — shared by host & device paths.

        ``device_billed`` is None on the host path (billing comes from the
        executor / the materialized matrix) and the device path's
        serving-work score count otherwise.
        """
        m = self.qwyc
        T = m.T
        plan = self.plan
        dec, exit_step = res.decisions, res.exit_step

        # full-ensemble score: free for rows that ran the whole cascade;
        # early-exited rows need an audit read (accounted separately).
        audit_scores = 0
        if ordered is not None:
            full_score = ordered.sum(axis=1)
        elif self.audit_full_scores and audit_read is not None:
            full_score = res.g_final.astype(np.float64, copy=True)
            exited = np.nonzero(exit_step < T)[0]
            if exited.size:
                full_score[exited] = audit_read(exited, 0, T).sum(axis=1)
                audit_scores = int(exited.size) * T
        else:
            full_score = None

        cum_cost = plan.cum_costs()
        batch_cost = float(cum_cost[exit_step - 1].sum())

        out = []
        for i in range(n):
            r = {
                "decision": bool(dec[i]),
                "models_evaluated": int(exit_step[i]),
            }
            if m.mode == "neg_only" and dec[i]:
                # Filter-and-Score: a neg_only positive never exited early,
                # so its carried partial sum is the full ensemble score.
                r["full_score"] = float(
                    full_score[i] if full_score is not None else res.g_final[i]
                )
            out.append(r)
        self._results.extend(zip(seqs, out))

        st = self.stats
        st.n_requests += n
        st.n_batches += 1
        st.models_evaluated += int(exit_step.sum())
        st.full_cost += float(cum_cost[-1]) * n
        st.actual_cost += batch_cost
        # eager bills the materialized matrix; lazy bills what the executor
        # actually drew through the producer (block-quantized); the device
        # path bills its fixed-capacity slabs (+ sort-key slab, if any)
        if device_billed is not None:
            st.scores_computed += device_billed
        else:
            st.scores_computed += n * T if ordered is not None else res.scores_computed
        st.scores_possible += n * T
        st.audit_scores += audit_scores
        for k, s in enumerate(res.chunk_stats):
            if k >= len(st.chunk_survivors):
                st.chunk_survivors.append(0)
            st.chunk_survivors[k] += s.n_in
        if full_score is not None:
            full_dec = full_score >= m.beta
            diffs = int((dec != full_dec).sum())
            st.diffs_vs_full += diffs
            if self._watchdog is not None:
                # fold this flush into the sequential drift statistic;
                # the returned margin degrades the NEXT flush's decide
                # policy (DESIGN.md §10)
                self._wd_margin = self._watchdog.observe(n, diffs)
                st.watchdog_alarms = self._watchdog.alarms
                st.watchdog_state = self._watchdog.state
                st.watchdog_stat = self._watchdog.llr
                st.watchdog_margin = self._wd_margin
                st.watchdog_recovery_step = self._watchdog.recovery_step
        else:
            # unaudited: survivors' decision IS the full decision (0 diffs);
            # early-exit rows are unknown and intentionally not guessed at
            pass
        return out

    def _merge_results(self) -> list[dict]:
        """Drain-time merge: flushed results + quarantined verdicts, back
        in submission order."""
        merged = sorted(self._results + self._quarantined, key=lambda t: t[0])
        self._results = []
        self._quarantined = []
        return [d for _, d in merged]

    def drain(self) -> list[dict]:
        self.flush()
        with TraceAnnotation(tracing.DRAIN):
            return self._merge_results()


class StreamingServer(QWYCServer):
    """Continuous-batching server: admit queued requests into freed
    survivor slots mid-cascade (DESIGN.md §8).

    The flush server (``QWYCServer``) serves batch-at-a-time: a flush's
    fixed-capacity survivor buffers drain as rows exit, and the mostly
    idle tail of the cascade holds the NEXT batch's requests hostage.
    This server keeps an arrival-order queue, stamps every request with
    an arrival step, and hands windows of pending requests to the
    executor's on-device admission ring (``run_stream``): freed slots are
    refilled mid-cascade, admitted rows start at stage 0 next to
    mid-cascade veterans (per-lane stage index), and decisions stay
    bit-identical per row id to the host ``ChunkedExecutor`` oracle
    (``tests/test_streaming.py``).

    * ``batch_size`` is the survivor-slot CAPACITY (the in-flight
      concurrency; x ``shards`` under a data-parallel backend) — the
      "equal capacity" knob the streaming benchmark compares at.
    * ``window`` is the admission-ring size: how many queued requests one
      device wave streams through (default ``4 x`` the slot capacity).
      Fixed window + fixed capacity = ONE compiled trace per server
      across all waves, asserted like the batch path's.
    * ``max_wait`` (stage steps) is the admission deadline: a submit that
      finds the oldest queued request waiting ``>= max_wait`` launches a
      PARTIAL wave instead of holding out for a full window.
    * latency is accounted end-to-end in deterministic stage steps:
      queue wait before the wave + ring wait + service
      (``ServeStats.latency_steps``, p50/p95/p99 properties).

    Streaming admission replaces the sorting policy (the ring is the
    arrival order), so only the ``kernel`` decide policy is accepted.
    Requires an execution backend with the ``streaming`` capability
    (device or sharded — the host loop has no fixed-capacity buffers to
    refill).
    """

    def __init__(
        self,
        qwyc: QWYCModel,
        *,
        window: int | None = None,
        max_wait: float | None = None,
        backend: str = "kernel",
        exec_backend="auto",
        **kw,
    ):
        if backend != "kernel":
            raise ValueError(
                "StreamingServer: streaming admission replaces the sorting "
                f"policy; only backend='kernel' is supported (got {backend!r})"
            )
        super().__init__(qwyc, backend=backend, exec_backend=exec_backend, **kw)
        caps = self.exec.capabilities
        if not getattr(caps, "streaming", False):
            raise ValueError(
                f"exec_backend {self.exec.name!r} does not support streaming "
                "admission (needs an on-device executor with run_stream)"
            )
        self.window = int(window) if window else 4 * self.flush_size
        if self.window < self.flush_size:
            raise ValueError(
                f"window ({self.window}) must be >= the slot capacity "
                f"({self.flush_size}); a smaller ring can never fill the slots"
            )
        self.max_wait = None if max_wait is None else float(max_wait)
        self._squeue: list[tuple[np.ndarray, float, int]] = []
        self._clock = 0.0
        # per-wave StreamResults (timeline raw material for the
        # streaming benchmark, like ShardedDeviceExecutor.last_run_info)
        self.stream_results: list = []

    def submit(self, x: np.ndarray, arrival: float | None = None) -> None:
        """Enqueue a request at ``arrival`` (stage-step units, must be
        nondecreasing across submits; default: the last stamp seen).  A
        full window — or a ``max_wait`` deadline breach — launches a
        device wave."""
        a = self._clock if arrival is None else float(arrival)
        if a < self._clock:
            raise ValueError(
                f"arrivals must be nondecreasing (got {a} after {self._clock})"
            )
        self._clock = a
        seq, row = self._admit(x)
        if row is None:
            return
        self._squeue.append((row, a, seq))
        if len(self._squeue) >= self.window:
            self.flush()
        elif (
            self.max_wait is not None
            and a - self._squeue[0][1] >= self.max_wait
        ):
            self.flush()

    def flush(self) -> list[dict]:
        """Stream one window (possibly partial) of queued requests."""
        if not self._squeue:
            return []
        wave, self._squeue = (
            self._squeue[: self.window],
            self._squeue[self.window:],
        )
        xb = np.stack([e[0] for e in wave])
        seqs = [e[2] for e in wave]
        n = xb.shape[0]
        base = wave[0][1]
        arr_steps = np.floor(
            np.array([e[1] for e in wave]) - base
        ).astype(np.int32)
        # wave ladder, streaming edition: only rungs with the streaming
        # capability are acceptable (the host loop has no admission ring)
        while True:
            try:
                executor, scorer, eager_matrix, _ = self._device_state()
                batch, ordered = self._eager_or_raw(xb, eager_matrix)
                res = self.ladder.attempt(
                    "wave", self.exec.name,
                    lambda: executor.run_stream(
                        batch,
                        n,
                        arrivals=arr_steps,
                        capacity=self.flush_size,
                        ring_capacity=self.window,
                    ),
                )
                break
            except RuntimeError as e:
                self._fall_rung(e, streaming=True)
        billed = n * self.qwyc.T if eager_matrix else res.scores_computed
        audit_read = (
            self._producers(xb)[0] if self.chunk_score_fn is not None else None
        )
        out = self._finish_flush(xb, n, res, ordered, audit_read, billed, seqs)
        self.stream_results.append(res)
        st = self.stats
        st.admitted_rows += n
        st.stream_steps += res.steps_run
        st.stream_slot_steps += int(res.occupancy.sum())
        st.stream_cap_steps += res.steps_run * res.capacity
        # end-to-end latency: steps queued BEFORE the wave launched
        # (launch = the wave's first arrival) + ring wait + service
        st.latency_steps.extend(
            (res.done_step - arr_steps + 1).astype(int).tolist()
        )
        return out

    def drain(self) -> list[dict]:
        while self._squeue:
            self.flush()
        return self._merge_results()
