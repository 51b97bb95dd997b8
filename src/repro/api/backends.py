"""Pluggable execution backends behind the ``repro.api`` front door.

Before this module existed, choosing an executor meant boolean-flag
dispatch at every call site: ``QWYCServer(device=True, mesh=...,
rebalance=...)``, ``ops.score_and_decide(device=True)``,
``launch/serve.py --device --shards N``.  Each new execution substrate
(async batching, multi-host, new accelerators) would have added another
flag to every caller.  This module inverts that: each substrate is a
``Backend`` object that

* declares its **capabilities** (``BackendCapabilities``: does control
  flow run on device, how many XLA devices it needs, whether compiled
  traces are cached across calls, whether it can repack survivors across
  data shards),
* answers **availability** (``available()`` — the one place
  "do we have enough devices?" is decided, which benchmarks and CI use
  for skip messages), and
* **constructs** the underlying executor (``make_executor`` — the only
  sanctioned path to ``ChunkedExecutor`` / ``DeviceExecutor`` /
  ``ShardedDeviceExecutor`` from public entrypoints).

Backends are looked up by name through ``repro.api.registry`` (mirroring
``configs/registry.py``); ``"auto"`` negotiates sharded -> device -> host
from the available device count.  The executors themselves are unchanged
— a backend is an adapter, so results stay bit-identical to direct
executor construction (asserted in ``tests/test_api.py``).
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Callable, Protocol, runtime_checkable

import jax

from repro.core.executor import CascadePlan, ChunkedExecutor
from repro.kernels.device_executor import (
    DEFAULT_BLOCK_N,
    DeviceExecutor,
    DevicePlan,
    BoundScorer,
)
from repro.kernels.sharded_executor import ShardedDeviceExecutor
from repro.launch.mesh import make_serving_mesh
from repro.testing import faults

__all__ = [
    "Backend",
    "BackendCapabilities",
    "BackoffPolicy",
    "DegradationEvent",
    "DegradationLadder",
    "HostBackend",
    "DeviceBackend",
    "ShardedBackend",
    "INTERPRET_ONLY",
    "fallback_rung",
]

# Escape hatch for environments where the fused device program must not
# run (e.g. debugging with the host stage loop + interpreted kernels
# only).  ``"auto"`` then negotiates down to the host backend.  Set the
# module flag directly, or export QWYC_INTERPRET_ONLY=1 before import.
INTERPRET_ONLY = os.environ.get("QWYC_INTERPRET_ONLY", "").lower() not in (
    "", "0", "false",
)


@dataclasses.dataclass(frozen=True)
class BackendCapabilities:
    """What a backend can do — the negotiation and validation surface.

    ``on_device``: the whole stage loop runs as one jit'd device program
    (scoring, decide, compaction, early exit — DESIGN.md §5); False means
    the host stage loop with per-stage producer calls (DESIGN.md §4).
    ``min_devices``: XLA devices required before ``available()`` says yes.
    ``trace_cached``: one compiled trace is reused across same-shape runs
    (the one-trace-per-shape guarantee the trace tests assert).
    ``data_parallel``: accepts ``mesh``/``shards`` options and splits the
    batch over a ``("data",)`` mesh axis.
    ``supports_rebalance``: can repack skewed survivor buffers between
    stages (only meaningful when ``data_parallel``).
    ``streaming``: the executor has ``run_stream`` — a device-resident
    admission ring refills freed survivor slots mid-cascade, so a
    ``StreamingServer`` can continuously batch onto it (DESIGN.md §8).
    ``grouped``: the executor has ``run_grouped`` — the group-level
    decide path for ragged ranking queries (DESIGN.md §12), consumed by
    ``repro.ranking.GroupedRankServer`` and ``api.fit(groups=...)``.
    ``model_parallel``: accepts a ``model_shards`` option and splits the
    stage param slabs over a ``"model"`` mesh axis (2-D ``("data",
    "model")`` mesh, DESIGN.md §13) for batch ``run`` — the grouped and
    streaming paths stay data-parallel-only at ``model_shards > 1``.
    """

    on_device: bool
    min_devices: int
    trace_cached: bool
    data_parallel: bool = False
    supports_rebalance: bool = False
    streaming: bool = False
    grouped: bool = False
    model_parallel: bool = False


@runtime_checkable
class Backend(Protocol):
    """Structural protocol every execution backend satisfies.

    Implementations adapt one executor class; they hold no per-model
    state, so a single registered instance serves every caller.
    """

    name: str
    capabilities: BackendCapabilities

    def available(
        self,
        n_devices: int | None = None,
        interpret_only: bool | None = None,
    ) -> tuple[bool, str]:
        """(usable, reason).  ``n_devices`` / ``interpret_only`` override
        the live environment — negotiation tests pass them explicitly."""
        ...

    def make_executor(self, plan: CascadePlan | DevicePlan, **opts) -> Any:
        """Construct this backend's executor for ``plan``.

        Host takes ``producer``/``decide_fn``/``bill_block``; on-device
        backends take ``scorer``/``block_n``/``interpret`` (plus
        ``mesh``/``shards``/``rebalance`` when ``data_parallel``)."""
        ...

    def billing_key(self, **opts) -> str:
        """Stable perf-gate counter-key fragment for this backend under
        ``opts`` — the single source of ``baseline_billing.json`` names."""
        ...


def _n_devices(n_devices: int | None) -> int:
    return len(jax.devices()) if n_devices is None else int(n_devices)


def _as_cascade_plan(plan: CascadePlan | DevicePlan) -> CascadePlan:
    return plan.plan if isinstance(plan, DevicePlan) else plan


def _as_device_plan(plan: CascadePlan | DevicePlan) -> DevicePlan:
    return plan if isinstance(plan, DevicePlan) else DevicePlan.from_plan(plan)


class HostBackend:
    """Host stage loop (``ChunkedExecutor``): the semantics oracle and the
    escape hatch for arbitrary host-side score producers.  Always
    available — it is the floor ``"auto"`` negotiation can't fall below."""

    name = "host"
    capabilities = BackendCapabilities(
        on_device=False, min_devices=0, trace_cached=False, grouped=True,
    )

    def available(self, n_devices=None, interpret_only=None) -> tuple[bool, str]:
        return faults.on_available(
            self.name, True, "host stage loop runs anywhere (numpy control flow)"
        )

    def make_executor(
        self,
        plan: CascadePlan | DevicePlan,
        *,
        producer,
        decide_fn=None,
        bill_block: int = 1,
    ) -> ChunkedExecutor:
        faults.on_make_executor(self.name)
        return ChunkedExecutor(
            _as_cascade_plan(plan), producer,
            decide_fn=decide_fn, bill_block=bill_block,
        )

    def billing_key(self, decide: str | None = None, block_n: int | None = None) -> str:
        # the host loop with the Pallas chunk-decide kernel has always
        # been billed under "kernel<block>"; the reference decide is plain
        # "host" — both names predate this module and must stay stable
        if decide == "kernel":
            return f"kernel{block_n or 256}"
        return self.name


class DeviceBackend:
    """Fused device program (``DeviceExecutor``): the whole cascade as one
    jit'd ``lax.while_loop`` — zero per-stage host round-trips, exactly
    one compiled trace per (N, T, chunk_t)."""

    name = "device"
    capabilities = BackendCapabilities(
        on_device=True, min_devices=1, trace_cached=True, streaming=True,
        grouped=True,
    )

    def available(self, n_devices=None, interpret_only=None) -> tuple[bool, str]:
        it = INTERPRET_ONLY if interpret_only is None else bool(interpret_only)
        if it:
            return False, (
                "interpret-only mode: the fused device program is disabled "
                "(QWYC_INTERPRET_ONLY / repro.api.backends.INTERPRET_ONLY)"
            )
        nd = _n_devices(n_devices)
        if nd < self.capabilities.min_devices:
            return faults.on_available(
                self.name, False, f"no XLA devices visible (have {nd})"
            )
        return faults.on_available(self.name, True, f"{nd} XLA device(s)")

    def make_executor(
        self,
        plan: CascadePlan | DevicePlan,
        *,
        scorer: BoundScorer,
        block_n: int = DEFAULT_BLOCK_N,
        interpret: bool | None = None,
        megakernel: bool | None = None,
        check_finite: bool = False,
    ) -> DeviceExecutor:
        # megakernel: the fused stage-step path (DESIGN.md §9); None =
        # auto (on for f32 slabs — bit-identical results AND billing, so
        # the billing_key does not fork on it)
        faults.on_make_executor(self.name)
        return DeviceExecutor(
            _as_device_plan(plan), scorer, block_n=block_n, interpret=interpret,
            megakernel=megakernel, check_finite=check_finite,
        )

    def billing_key(self) -> str:
        return self.name


class ShardedBackend:
    """Data-parallel device program (``ShardedDeviceExecutor``): the fused
    loop under ``shard_map`` over a ``("data",)`` mesh — per-shard working
    set ~batch/shards, optional skew-triggered survivor rebalancing."""

    name = "sharded"
    capabilities = BackendCapabilities(
        on_device=True, min_devices=2, trace_cached=True,
        data_parallel=True, supports_rebalance=True, streaming=True,
        grouped=True, model_parallel=True,
    )

    def available(self, n_devices=None, interpret_only=None) -> tuple[bool, str]:
        it = INTERPRET_ONLY if interpret_only is None else bool(interpret_only)
        if it:
            return False, (
                "interpret-only mode: the fused device program is disabled "
                "(QWYC_INTERPRET_ONLY / repro.api.backends.INTERPRET_ONLY)"
            )
        nd = _n_devices(n_devices)
        if nd < self.capabilities.min_devices:
            return faults.on_available(
                self.name,
                False,
                f"{nd} device(s) < {self.capabilities.min_devices} — run under "
                "XLA_FLAGS=--xla_force_host_platform_device_count=4",
            )
        return faults.on_available(self.name, True, f"{nd} XLA devices")

    def resolve_mesh(
        self,
        mesh=None,
        shards: int | None = None,
        model_shards: int = 1,
    ):
        """The mesh this backend will run on: an explicit mesh wins, else
        a fresh ``("data",)`` mesh over ``shards`` (default: all) devices
        — or, with ``model_shards > 1``, a 2-D ``("data", "model")`` mesh
        of ``shards x model_shards`` (default data width: the devices
        that remain after the model axis takes its share)."""
        m = max(1, int(model_shards))
        if mesh is not None:
            have = int(dict(mesh.shape).get("model", 1))
            if m > 1 and have != m:
                raise ValueError(
                    f"model_shards={m} conflicts with the explicit mesh "
                    f"{tuple(mesh.shape.items())} (its 'model' axis is "
                    f"{have}-wide); pass one or the other (DESIGN.md §13)"
                )
            return mesh
        if shards:
            n = int(shards)
        else:
            n = max(1, len(jax.devices()) // m)
        return make_serving_mesh(n, m)

    def make_executor(
        self,
        plan: CascadePlan | DevicePlan,
        *,
        scorer: BoundScorer,
        mesh=None,
        shards: int | None = None,
        model_shards: int = 1,
        block_n: int = DEFAULT_BLOCK_N,
        interpret: bool | None = None,
        rebalance: bool = False,
        rebalance_ratio: float = 1.25,
        megakernel: bool | None = None,
        check_finite: bool = False,
    ) -> ShardedDeviceExecutor:
        faults.on_make_executor(self.name)
        return ShardedDeviceExecutor(
            _as_device_plan(plan), scorer,
            self.resolve_mesh(mesh, shards, model_shards),
            block_n=block_n, interpret=interpret,
            rebalance=rebalance, rebalance_ratio=rebalance_ratio,
            megakernel=megakernel, check_finite=check_finite,
        )

    def billing_key(
        self, shards: int, rebalance: bool = False, model_shards: int = 1
    ) -> str:
        # 1-D names predate the model axis and must stay stable (the
        # perf-gate baseline keys them); M > 1 names the full mesh shape
        shape = f"{int(shards)}"
        if int(model_shards) > 1:
            shape += f"x{int(model_shards)}"
        return f"{self.name}{shape}{'r' if rebalance else ''}"


# -- graceful degradation (DESIGN.md §10) -------------------------------
#
# The negotiation ladder (sharded -> device -> host) picks a backend at
# compile time; the classes below make it a RUNTIME ladder: when a rung's
# executor construction or a device wave fails, the caller retries with
# capped exponential backoff, then falls one rung and records a
# ``DegradationEvent``.  ``CompiledCascade`` and the serving engines both
# drive the same ``DegradationLadder``; tests inject faults via
# ``repro.testing.faults`` and a fake ``sleep`` so every delay is
# deterministic and no test ever actually waits.


@dataclasses.dataclass(frozen=True)
class DegradationEvent:
    """One recorded degradation: a same-rung recovery (``to_backend ==
    from_backend``) or a fall to the next rung."""

    kind: str  # "construct" (make_executor failed) | "wave" (run failed)
    from_backend: str
    to_backend: str
    error: str
    retries: int  # failed attempts on from_backend before this resolution


@dataclasses.dataclass(frozen=True)
class BackoffPolicy:
    """Capped exponential backoff: ``retries`` extra attempts after the
    first failure, waiting ``base_delay * factor**i`` (capped at
    ``max_delay``) before attempt i+1.  Delays are data, not clock reads,
    so a test's fake ``sleep`` sees the exact schedule."""

    retries: int = 2
    base_delay: float = 0.05
    factor: float = 2.0
    max_delay: float = 1.0

    def delays(self) -> tuple[float, ...]:
        return tuple(
            min(self.base_delay * self.factor**i, self.max_delay)
            for i in range(max(0, int(self.retries)))
        )


def fallback_rung(name: str, accept: Callable | None = None) -> Backend | None:
    """The first AVAILABLE backend strictly below ``name`` in the
    negotiation order (optionally also satisfying ``accept(backend)``),
    or None at the floor."""
    from repro.api.registry import NEGOTIATION_ORDER, get_backend

    if name not in NEGOTIATION_ORDER:
        # third-party backend: any registered rung is a valid fallback
        start = 0
    else:
        start = NEGOTIATION_ORDER.index(name) + 1
    for lower in NEGOTIATION_ORDER[start:]:
        b = get_backend(lower)
        ok, _ = b.available()
        if ok and (accept is None or accept(b)):
            return b
    return None


class DegradationLadder:
    """Retry-then-fall driver shared by ``CompiledCascade`` and the
    serving engines.

    ``attempt`` runs one callable with same-rung retries under the
    backoff policy; ``fall`` resolves the next usable rung (recording the
    event) or re-raises when the floor is reached.  Only
    ``RuntimeError`` (XLA runtime failures, ``WaveFailure``, injected
    ``FaultInjected``) is retryable — ``ValueError``/``TypeError`` are
    caller bugs and propagate untouched, and so does a device program
    the compiler refuses (``DeviceProgramError``, raised before the wave
    launches): no rung below answers in its place.
    """

    def __init__(
        self,
        backoff: BackoffPolicy | None = None,
        sleep: Callable[[float], None] | None = None,
        events: list | None = None,
    ):
        self.backoff = backoff or BackoffPolicy()
        self.sleep = time.sleep if sleep is None else sleep
        self.events: list[DegradationEvent] = events if events is not None else []

    def attempt(self, kind: str, backend_name: str, fn: Callable[[], Any]):
        """``fn()`` with capped-backoff retries on the SAME rung.  A
        retry that succeeds records a same-rung recovery event; exhausted
        retries re-raise the last error for ``fall`` to resolve."""
        delays = self.backoff.delays()
        err: RuntimeError | None = None
        for i in range(len(delays) + 1):
            try:
                out = fn()
            except RuntimeError as e:
                err = e
                if i < len(delays):
                    self.sleep(delays[i])
                continue
            if i:
                self.events.append(
                    DegradationEvent(
                        kind=kind,
                        from_backend=backend_name,
                        to_backend=backend_name,
                        error=str(err),
                        retries=i,
                    )
                )
            return out
        raise err

    def fall(
        self,
        kind: str,
        from_name: str,
        error: BaseException,
        accept: Callable | None = None,
    ) -> Backend:
        """Next usable rung below ``from_name``; records the fall.  At
        the floor the original ``error`` is re-raised — degradation never
        swallows a failure it cannot route around."""
        nxt = fallback_rung(from_name, accept=accept)
        if nxt is None:
            raise error
        self.events.append(
            DegradationEvent(
                kind=kind,
                from_backend=from_name,
                to_backend=nxt.name,
                error=str(error),
                retries=self.backoff.retries,
            )
        )
        return nxt
