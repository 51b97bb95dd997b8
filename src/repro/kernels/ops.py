"""Public jit'd entry points for the Pallas kernels.

Each op dispatches to the Pallas kernel and is paired with a pure-jnp
oracle in ``ref.py``.  Kernels compile with Mosaic on a TPU and run in
Pallas interpret mode where their arrays live on the CPU (the kernel body
executes as ordinary XLA ops, bit-level semantics intact); the choice is
made per call (``repro.kernels.interpret``), and an explicit
``interpret=`` overrides it.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.executor import CascadePlan, ExecutorResult
from repro.kernels import ref
from repro.kernels.cascade_kernel import cascade_chunk_pallas, cascade_pallas
from repro.kernels.device_executor import BoundScorer
from repro.kernels.lattice_kernel import lattice_scores_pallas
from repro.kernels.tree_kernel import gbt_scores_pallas

__all__ = [
    "cascade_decide",
    "cascade_chunk",
    "kernel_decide_fn",
    "score_and_decide",
    "lattice_scores",
    "gbt_scores",
    "ref",
]

def cascade_decide(scores_ordered, eps_pos, eps_neg, beta, **kw):
    """Early-exit cascade -> (decisions int32, exit_step int32)."""
    return cascade_pallas(scores_ordered, eps_pos, eps_neg, beta, **kw)


def cascade_chunk(g0, chunk_scores, eps_pos, eps_neg, t0, **kw):
    """One-stage threshold tests -> (g, active, decided_pos, exit_step)."""
    return cascade_chunk_pallas(g0, chunk_scores, eps_pos, eps_neg, t0, **kw)


def kernel_decide_fn(block_n: int = 256, interpret: bool | None = None):
    """Adapt the Pallas chunk kernel to the ``ChunkedExecutor`` decide hook.

    The kernel runs at the score dtype (float32 on TPU), and the executor
    carries state at the same dtype (``carry_dtype`` attribute) — the
    kernel's float32 outputs used to be widened to float64 on host only to
    be cast straight back to float32 at the next stage's kernel call, a
    per-stage double conversion of the whole carried vector.  QWYC
    thresholds sit strictly between observed partial sums, so decisions /
    exit steps are unaffected (same contract the eager ``cascade_decide``
    path has always relied on).
    """

    def decide(g0, chunk, eps_pos, eps_neg, t0):
        dt = jnp.asarray(chunk).dtype
        if not jnp.issubdtype(dt, jnp.floating):
            dt = jnp.float32
        g, active, dec, ex = cascade_chunk(
            jnp.asarray(g0, dtype=dt),
            jnp.asarray(chunk, dtype=dt),
            jnp.asarray(eps_pos, dtype=dt),
            jnp.asarray(eps_neg, dtype=dt),
            int(t0),
            block_n=block_n,
            interpret=interpret,
        )
        return (
            np.asarray(g),
            np.asarray(active).astype(bool),
            np.asarray(dec).astype(bool),
            np.asarray(ex, dtype=np.int64),
        )

    decide.carry_dtype = np.float32
    return decide


# on-device executor cache: one compiled executor per
# (backend, scorer, plan, block_n, interpret, opts) — strong refs on purpose, so repeat
# calls with the same plan/scorer objects reuse the single compiled
# trace.  Bounded (FIFO) so a long-lived process building fresh
# plans/scorers per request cannot leak executors + param slabs without
# limit; evicting an entry only costs a recompile on the next reuse.
_DEVICE_EXECUTORS: dict = {}
_DEVICE_EXECUTORS_MAX = 32


def score_and_decide(
    producer,
    plan: CascadePlan,
    n: int,
    block_n: int = 256,
    row_order=None,
    interpret: bool | None = None,
    bill_block: int | None = None,
    device: bool | None = None,
    x=None,
    backend=None,
    backend_opts: dict | None = None,
) -> ExecutorResult:
    """Fused lazy path: chunked scoring composed with the threshold kernel.

    ``backend`` names an execution backend from the registry
    (``repro.api``, DESIGN.md §7) — ``"host"`` (the default) or an
    on-device backend (``"device"``/``"sharded"``/``"auto"``); a
    ``Backend`` instance is accepted directly and executors are only ever
    constructed through it.

    Host mode: instead of consuming a precomputed (N, T) matrix, each
    stage scores only the surviving rows for only that stage's models
    (``producer`` — typically a closure over ``gbt_scores``/
    ``lattice_scores`` with ``t0``/``t1``/``rows``) and immediately runs
    the Pallas chunk-decide kernel; survivors are compacted on host
    before the next stage.

    On-device mode: ``producer`` must be a ``device_executor.BoundScorer``
    and ``x`` the batch operand its ``prepare`` consumes; the entire
    stage loop — scoring, decide, compaction, early exit — runs as one
    jit'd ``lax.while_loop`` with no per-stage host round-trips
    (DESIGN.md §5).  Pass the SAME plan and scorer objects across calls
    to reuse the compiled program.  ``backend_opts`` forwards extra
    construction options (e.g. ``mesh=`` for ``"sharded"``, or
    ``megakernel=`` to force the fused stage-step path of DESIGN.md §9
    on or off — the device backends default it on for f32 slabs).

    ``bill_block`` defaults to ``block_n``: a kernel producer using the
    same block size really computes ceil(m / block_n) * block_n rows per
    stage, and scores_computed bills that, not the rows requested.

    (The legacy ``device=True/False`` boolean was retired after its
    deprecation cycle; it raises naming the ``backend=`` replacement.)
    """
    from repro.api.registry import resolve_backend

    if device is not None:
        raise TypeError(
            "score_and_decide(device=...) was removed after its "
            "deprecation cycle; pass backend='device' (or "
            "'host'/'sharded'/'auto' — see repro.api) instead"
        )
    b = resolve_backend("host" if backend is None else backend)
    opts = dict(backend_opts or {})
    if b.capabilities.on_device:
        if not isinstance(producer, BoundScorer):
            raise TypeError(
                f"backend {b.name!r} requires a device_executor.BoundScorer "
                "producer"
            )
        if x is None:
            raise ValueError(f"backend {b.name!r} requires the batch operand x")
        # opts values are keyed by identity, and the cache entry keeps
        # strong refs to them (alongside producer/plan) so the ids stay
        # valid — like plan/scorer, pass the SAME backend_opts values
        # (e.g. one long-lived mesh) across calls to reuse the program
        key = (
            b.name, id(producer), id(plan), block_n, interpret,
            tuple(sorted((k, id(v)) for k, v in opts.items())),
        )
        entry = _DEVICE_EXECUTORS.get(key)
        if entry is None:
            while len(_DEVICE_EXECUTORS) >= _DEVICE_EXECUTORS_MAX:
                _DEVICE_EXECUTORS.pop(next(iter(_DEVICE_EXECUTORS)))
            entry = (
                b.make_executor(
                    plan, scorer=producer, block_n=block_n,
                    interpret=interpret, **opts,
                ),
                producer,
                plan,
                tuple(opts.values()),
            )
            _DEVICE_EXECUTORS[key] = entry
        return entry[0].run(x, n, row_order=row_order)
    ex = b.make_executor(
        plan,
        producer=producer,
        decide_fn=kernel_decide_fn(block_n=block_n, interpret=interpret),
        bill_block=block_n if bill_block is None else bill_block,
        **opts,
    )
    return ex.run(n, row_order=row_order)


def _bucket_rows(kw):
    """Pad a ``rows`` gather up to a block_n multiple (repeat a valid index).

    The score kernels are jit'd, so a survivor-count-dependent rows shape
    would retrace/recompile at every stage of every batch; quantizing to
    block multiples bounds the distinct traces per (t0, t1) to O(N/block_n).
    Returns the unpadded row count (slice the output back to it), or None.
    """
    rows = kw.get("rows")
    if rows is None:
        return None
    rows = np.asarray(rows)
    mult = kw.get("block_n", 256)
    pad = -rows.shape[0] % mult
    if pad:
        rows = np.concatenate([rows, np.full(pad, rows[0], dtype=rows.dtype)])
    kw["rows"] = jnp.asarray(rows, dtype=jnp.int32)
    return rows.shape[0] - pad


def lattice_scores(theta, feats, x, **kw):
    """(N, T) lattice base-model scores (or a t0/t1/rows-restricted slab)."""
    m = _bucket_rows(kw)
    out = lattice_scores_pallas(theta, feats, x, **kw)
    return out if m is None else out[:m]


def gbt_scores(feats, thrs, leaves, x, **kw):
    """(N, T) oblivious-tree base-model scores (or a t0/t1/rows slab)."""
    m = _bucket_rows(kw)
    out = gbt_scores_pallas(feats, thrs, leaves, x, **kw)
    return out if m is None else out[:m]
