"""Pallas TPU kernel: blocked early-exit cascade ("quit when you can").

TPU adaptation of the paper's per-example sequential early exit.  Examples
are tiled into VMEM blocks of ``block_n`` rows; within a block the kernel
walks the QWYC-ordered base models in chunks of ``chunk_t`` and *stops the
walk for the whole block* once every lane has exited — per-BLOCK early exit,
the SIMD-compatible analogue of the paper's per-example exit.  QWYC's
ordering maximizes early-exit probability, which directly maximizes the
chance an entire block retires after few chunks.

The score tile for a block is DMA'd to VMEM up-front (BlockSpec), so the
skip saves VPU compute, not HBM traffic; on real hardware a further win comes
from `memory_space=ANY` + manual chunk DMA, which we document in
EXPERIMENTS.md §Perf rather than emulate here.  When base models are *real*
models (trees/lattices), the serving path composes this kernel's threshold
logic with the tree/lattice kernels instead of a precomputed score matrix.

Layout (what Mosaic accepts on a v5e): rows sit on sublanes, so every
per-row vector is an ``(n, 1)`` column and its block is ``(block_n, 1)``
(``block_n`` a multiple of 8 on the chip); score tiles are
``(block_n, width)`` with the full model width on lanes.  A cascade step
reads its column with a static lane slice, or — where the position is
dynamic — with an exact one-hot select.  Loop carries are int32/f32
(Mosaic cannot carry bool vectors through a loop).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.interpret import resolve_interpret

DEFAULT_BLOCK_N = 256
DEFAULT_CHUNK_T = 8

__all__ = [
    "cascade_pallas",
    "cascade_chunk_pallas",
    "cascade_group_pallas",
    "cascade_lane_pallas",
    "threshold_step",
]

#: group-decide block: rows of the (G, B) group grid per Pallas program
DEFAULT_BLOCK_G = 8


def threshold_step(g, active, decided_pos, exit_step, f_t, ep, en, step_1b):
    """One cascade threshold test — the single source of the step semantics
    for every decide kernel in the repo: the three kernels below AND the
    fused stage-step megakernel (``kernels/megakernel.py``), which inlines
    this exact function after its in-kernel scoring.  Mirrored
    (bit-identically) by ``core/cascade._step`` and
    ``core/executor.decide_chunk_reference``; a semantics change here must
    be replayed there, and the parity tests in tests/test_executor.py /
    tests/test_kernels.py / tests/test_megakernel.py will catch a skew.
    """
    g = g + jnp.where(active, f_t, 0.0)
    out_neg = active & (g < en)  # negative exit priority (matches fit)
    out_pos = active & (g > ep) & ~out_neg
    newly = out_neg | out_pos
    decided_pos = decided_pos | out_pos
    exit_step = jnp.where(newly, step_1b, exit_step)
    active = active & ~newly
    return g, active, decided_pos, exit_step


def _row_block(bn: int):
    """BlockSpec of an (n, 1) per-row column over a 1-D grid."""
    return pl.BlockSpec((bn, 1), lambda i: (i, 0))


def _cascade_kernel(
    scores_ref,  # (block_n, T) VMEM
    eps_pos_ref,  # (1, T)
    eps_neg_ref,  # (1, T)
    dec_ref,  # (block_n, 1) int32 out
    exit_ref,  # (block_n, 1) int32 out
    *,
    T: int,
    chunk_t: int,
    beta: float,
):
    block_n = scores_ref.shape[0]
    n_chunks = pl.cdiv(T, chunk_t)
    cols = jax.lax.broadcasted_iota(jnp.int32, (block_n, T), 1)
    cols1 = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)

    def pick(x, c, t):
        # exact one-hot select of the dynamic column t (a single nonzero
        # term per row), the aligned stand-in for a dynamic lane slice
        return jnp.sum(jnp.where(c == t, x, 0.0), axis=1, keepdims=True)

    def chunk_body(state):
        c, g, active, decided_pos, exit_step = state
        scores = scores_ref[...]
        active, decided_pos = active != 0, decided_pos != 0
        for j in range(chunk_t):
            t = c * chunk_t + j
            in_range = t < T
            live = active & in_range
            g, live, decided_pos, exit_step = threshold_step(
                g, live, decided_pos, exit_step,
                pick(scores, cols, t),
                pick(eps_pos_ref[...], cols1, t),
                pick(eps_neg_ref[...], cols1, t),
                t + 1,
            )
            # out-of-range padding steps must not deactivate lanes: a lane
            # still active at T is decided by g >= beta, not decided_pos
            active = (live & in_range) | (active & ~in_range)
        i32 = jnp.int32
        return c + 1, g, active.astype(i32), decided_pos.astype(i32), exit_step

    def chunk_cond(state):
        c, _, active, _, _ = state
        # quit when you can: the whole block stops once no lane is active
        return (c < n_chunks) & (jnp.max(active) > 0)

    col = (block_n, 1)
    init = (
        jnp.int32(0),
        jnp.zeros(col, scores_ref.dtype),
        jnp.ones(col, dtype=jnp.int32),
        jnp.zeros(col, dtype=jnp.int32),
        jnp.full(col, T, dtype=jnp.int32),
    )
    _, g, active, decided_pos, exit_step = jax.lax.while_loop(
        chunk_cond, chunk_body, init
    )
    dec_ref[...] = jnp.where(active != 0, (g >= beta).astype(jnp.int32),
                             decided_pos)
    exit_ref[...] = exit_step


def cascade_pallas(
    scores_ordered: jax.Array,
    eps_pos: jax.Array,
    eps_neg: jax.Array,
    beta: float,
    block_n: int = DEFAULT_BLOCK_N,
    chunk_t: int = DEFAULT_CHUNK_T,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Blocked early-exit cascade.  Returns (decisions int32, exit_step int32).

    ``scores_ordered`` is (N, T), already permuted to QWYC order.  N is padded
    to a multiple of ``block_n`` internally (padded lanes exit immediately via
    a 0-score + wide-open thresholds trick and are sliced off).
    """
    return _cascade(
        scores_ordered, eps_pos, eps_neg, beta=float(beta), block_n=block_n,
        chunk_t=chunk_t, interpret=resolve_interpret(interpret, scores_ordered),
    )


@functools.partial(
    jax.jit, static_argnames=("beta", "block_n", "chunk_t", "interpret")
)
def _cascade(scores_ordered, eps_pos, eps_neg, *, beta, block_n, chunk_t,
             interpret):
    n, T = scores_ordered.shape
    n_pad = -n % block_n
    if n_pad:
        scores_ordered = jnp.pad(scores_ordered, ((0, n_pad), (0, 0)))
    np_total = scores_ordered.shape[0]
    eps_pos2 = eps_pos.reshape(1, T).astype(scores_ordered.dtype)
    eps_neg2 = eps_neg.reshape(1, T).astype(scores_ordered.dtype)
    grid = (np_total // block_n,)
    kernel = functools.partial(
        _cascade_kernel, T=T, chunk_t=chunk_t, beta=beta
    )
    dec, exit_step = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, T), lambda i: (i, 0)),
            pl.BlockSpec((1, T), lambda i: (0, 0)),
            pl.BlockSpec((1, T), lambda i: (0, 0)),
        ],
        out_specs=[_row_block(block_n)] * 2,
        out_shape=[jax.ShapeDtypeStruct((np_total, 1), jnp.int32)] * 2,
        interpret=interpret,
    )(scores_ordered, eps_pos2, eps_neg2)
    return dec[:n, 0], exit_step[:n, 0]


def _decide_kernel(
    g0_ref,  # (block_n, 1) carried partial scores
    scores_ref,  # (block_n, ct) this chunk's scores, VMEM
    eps_pos_ref,  # (1, ct) stage thresholds, or (block_n, ct) per lane
    eps_neg_ref,  # same shape as eps_pos_ref
    valid_ref,  # (block_n, 1) int32: 1 = real row, 0 = padding lane
    g_ref,  # (block_n, 1) out
    active_ref,  # (block_n, 1) int32 out
    dec_ref,  # (block_n, 1) int32 out (1 = exited positive)
    exit_ref,  # (block_n, 1) int32 out (1-based step within the chunk; 0 = no exit)
    *,
    ct: int,
):
    """Threshold tests of one stage for one row block, unrolled over the
    chunk's ``ct`` positions.  A lane that exits stops changing, so the
    walk needs no early stop to stay exact.  Stage-shared thresholds
    arrive as one (1, ct) row; per-lane thresholds (the streaming lanes,
    where one block mixes rows at different stages) as (block_n, ct)."""
    scores = scores_ref[...]
    ep, en = eps_pos_ref[...], eps_neg_ref[...]
    g = g0_ref[...]
    # padding lanes start inactive, so they can never exit or move g
    active = valid_ref[...] != 0
    decided_pos = jnp.zeros(g.shape, dtype=jnp.bool_)
    exit_step = jnp.zeros(g.shape, dtype=jnp.int32)
    for j in range(ct):
        g, active, decided_pos, exit_step = threshold_step(
            g, active, decided_pos, exit_step,
            scores[:, j:j + 1], ep[:, j:j + 1], en[:, j:j + 1], j + 1,
        )
    g_ref[...] = g
    active_ref[...] = active.astype(jnp.int32)
    dec_ref[...] = decided_pos.astype(jnp.int32)
    exit_ref[...] = exit_step


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def _decide(g0, chunk_scores, eps_pos, eps_neg, t0, n_valid, *, block_n,
            interpret):
    """Shared wrapper of the chunk and lane decides: pad rows to a block
    multiple, mark rows past ``n_valid`` as padding, run
    ``_decide_kernel``, and rebase exits by ``t0`` (zeros stay zeros)."""
    m, ct = chunk_scores.shape
    # fixed block size (pad up, never shrink to fit): survivor counts vary
    # per stage, and quantizing shapes to block_n multiples keeps the number
    # of distinct traces bounded across a serving session
    bn = block_n
    m_pad = -m % bn
    per_lane = eps_pos.ndim == 2
    if m_pad:
        g0 = jnp.pad(g0, (0, m_pad))
        chunk_scores = jnp.pad(chunk_scores, ((0, m_pad), (0, 0)))
        if per_lane:
            eps_pos = jnp.pad(eps_pos, ((0, m_pad), (0, 0)))
            eps_neg = jnp.pad(eps_neg, ((0, m_pad), (0, 0)))
    m_total = g0.shape[0]
    lim = jnp.minimum(jnp.int32(m), jnp.asarray(n_valid, dtype=jnp.int32))
    valid = (jnp.arange(m_total, dtype=jnp.int32) < lim).astype(jnp.int32)
    dt = chunk_scores.dtype
    if per_lane:
        eps_spec = pl.BlockSpec((bn, ct), lambda i: (i, 0))
    else:
        eps_pos, eps_neg = eps_pos.reshape(1, ct), eps_neg.reshape(1, ct)
        eps_spec = pl.BlockSpec((1, ct), lambda i: (0, 0))
    g, active, dec, exit_step = pl.pallas_call(
        functools.partial(_decide_kernel, ct=ct),
        grid=(m_total // bn,),
        in_specs=[
            _row_block(bn),
            pl.BlockSpec((bn, ct), lambda i: (i, 0)),
            eps_spec,
            eps_spec,
            _row_block(bn),
        ],
        out_specs=[_row_block(bn)] * 4,
        out_shape=[jax.ShapeDtypeStruct((m_total, 1), dt)]
        + [jax.ShapeDtypeStruct((m_total, 1), jnp.int32)] * 3,
        interpret=interpret,
    )(
        g0.astype(dt).reshape(m_total, 1),
        chunk_scores,
        eps_pos.astype(dt),
        eps_neg.astype(dt),
        valid.reshape(m_total, 1),
    )
    exit_step = exit_step[:m, 0]
    exit_step = jnp.where(exit_step > 0, exit_step + t0, 0)
    return g[:m, 0], active[:m, 0], dec[:m, 0], exit_step


def cascade_lane_pallas(
    g0: jax.Array,
    chunk_scores: jax.Array,
    eps_pos: jax.Array,
    eps_neg: jax.Array,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool | None = None,
    n_valid: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Per-lane-stage decide: threshold tests for one MIXED-stage block.

    Same contract as ``cascade_chunk_pallas`` except ``eps_pos`` /
    ``eps_neg`` are (m, ct) PER-ROW threshold slabs (each row gathered
    from the stage table at that lane's own stage) and the returned
    ``exit_step`` is always RELATIVE (1-based within the chunk, 0 where
    the row survived) — the caller owns the per-lane rebase.  Rows past
    ``n_valid`` start inactive, exactly like the chunk decide.
    """
    m = chunk_scores.shape[0]
    return _decide(
        g0, chunk_scores, eps_pos, eps_neg, 0, m if n_valid is None else n_valid,
        block_n=block_n, interpret=resolve_interpret(interpret, chunk_scores),
    )


def cascade_chunk_pallas(
    g0: jax.Array,
    chunk_scores: jax.Array,
    eps_pos: jax.Array,
    eps_neg: jax.Array,
    t0,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool | None = None,
    n_valid: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Threshold tests for ONE cascade stage (the chunked-executor decide).

    Unlike ``cascade_pallas`` this consumes no precomputed (N, T) matrix:
    the executor feeds it just the surviving rows' carried partial sums
    ``g0`` (m,) and the freshly produced ``chunk_scores`` (m, ct) for
    cascade positions [t0, t0 + ct).  Rows are padded to a ``block_n``
    multiple (padded take) and the padding sliced off the outputs.
    ``t0`` may be traced: it only rebases the exit steps, so one compiled
    program serves every stage of the same width.

    ``n_valid`` (optional, traced scalar) marks only the first ``n_valid``
    rows as live — the on-device executor (``kernels/device_executor.py``)
    keeps survivors compacted at the front of a fixed-capacity buffer, so
    the live count is data, not shape, and lanes past it never exit.

    Returns (g, active int32, decided_pos int32, exit_step int32) each (m,);
    ``exit_step`` is the absolute 1-based step, 0 where the row survived.
    """
    m, ct = chunk_scores.shape
    return _decide(
        g0, chunk_scores, jnp.reshape(eps_pos, (ct,)),
        jnp.reshape(eps_neg, (ct,)), t0, m if n_valid is None else n_valid,
        block_n=block_n, interpret=resolve_interpret(interpret, chunk_scores),
    )


def _cascade_group_kernel(
    g_ref,  # (block_g, B) carried partial document scores
    valid_ref,  # (block_g, B) int32: 1 = real document lane, 0 = padding
    eps_ref,  # (block_g, 1) per-GROUP margin threshold
    live_ref,  # (block_g, 1) int32: 1 = group still in the cascade
    margin_ref,  # (block_g, 1) out: top-k stability margin
    exit_ref,  # (block_g, 1) int32 out: 1 = group exits as a unit
    *,
    k: int,
):
    """Group decide: does each group's top-k order look settled?

    The group axis is the segment axis — every ``axis=1`` reduction here
    is a segment_max/segment_sum over one group's document lanes.  The
    top-(k+1) values come from k+1 unrolled masked-max passes with
    first-hit consumption (lowest lane wins ties), matching
    ``ranking.plan.topk_margin`` bit-for-bit; the margin is the k-th
    minus (k+1)-th best, +inf for groups of at most k documents.  Exit
    is STRICTLY ``margin > eps``, so eps = +inf never exits (the
    full-cascade parity configuration).
    """
    g = g_ref[...]
    valid = valid_ref[...] != 0
    dt = g.dtype
    B = g.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, g.shape, 1)
    ninf = jnp.array(-jnp.inf, dtype=dt)
    work = jnp.where(valid, g, ninf)
    avail = valid
    vk = vk1 = None
    for i in range(k + 1):
        masked = jnp.where(avail, work, ninf)
        cur = jnp.max(masked, axis=1, keepdims=True)  # segment max
        if i == k - 1:
            vk = cur
        elif i == k:
            vk1 = cur
        if i < k:
            # consume the first (lowest-lane) hit of the max
            hit = avail & (masked == cur)
            first = jnp.min(jnp.where(hit, lane, B), axis=1, keepdims=True)
            avail = avail & (lane != first)
    size = jnp.sum(valid_ref[...], axis=1, keepdims=True)  # real docs
    inf = jnp.array(jnp.inf, dtype=dt)
    # a head that cannot reorder (size <= k) is trivially stable; the
    # guard also fences the -inf - -inf = NaN of consumed passes
    margin = jnp.where(size <= k, inf, vk - vk1)
    exit_g = (live_ref[...] != 0) & (margin > eps_ref[...])
    margin_ref[...] = margin
    exit_ref[...] = exit_g.astype(jnp.int32)


def cascade_group_pallas(
    g: jax.Array,
    valid: jax.Array,
    eps: jax.Array,
    k: int,
    block_g: int = DEFAULT_BLOCK_G,
    interpret: bool | None = None,
    n_live: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Group-level decide over a rectangular (G, B) bucket layout.

    ``g`` (G, B) carries each group's per-document partial sums after the
    stage's scores were accumulated, ``valid`` (G, B) marks real lanes,
    ``eps`` (G,) is the PER-GROUP margin threshold — the batch executor
    broadcasts the stage's scalar, the streaming ring gathers each slot's
    own stage threshold, and both share this one kernel (hence one trace
    per bucket shape).  ``n_live`` marks only the first ``n_live`` groups
    live, mirroring the front-packed survivor convention of
    ``cascade_chunk_pallas``; padding groups never exit.

    Returns ``(margin (G,) f32-like, exit (G,) int32)``; margins are
    reported for ALL groups (the executor epilogue reuses them for
    ran-out verdicts), exits only for live ones.
    """
    Gq = jnp.shape(g)[0]
    return _group(
        g, valid, eps, Gq if n_live is None else n_live, k=int(k),
        block_g=block_g, interpret=resolve_interpret(interpret, g),
    )


@functools.partial(jax.jit, static_argnames=("k", "block_g", "interpret"))
def _group(g, valid, eps, n_live, *, k, block_g, interpret):
    g = jnp.asarray(g)
    Gq, B = g.shape
    bg = block_g
    g_pad = -Gq % bg
    valid = jnp.asarray(valid).astype(jnp.int32)
    eps = jnp.asarray(eps)
    if g_pad:
        g = jnp.pad(g, ((0, g_pad), (0, 0)))
        valid = jnp.pad(valid, ((0, g_pad), (0, 0)))
        eps = jnp.pad(eps, (0, g_pad))
    g_total = g.shape[0]
    lim = jnp.minimum(jnp.int32(Gq), jnp.asarray(n_live, dtype=jnp.int32))
    live = (jnp.arange(g_total, dtype=jnp.int32) < lim).astype(jnp.int32)
    dt = g.dtype
    col = lambda a: a.reshape(g_total, 1)  # noqa: E731
    margin, exit_g = pl.pallas_call(
        functools.partial(_cascade_group_kernel, k=k),
        grid=(g_total // bg,),
        in_specs=[
            pl.BlockSpec((bg, B), lambda i: (i, 0)),
            pl.BlockSpec((bg, B), lambda i: (i, 0)),
            _row_block(bg),
            _row_block(bg),
        ],
        out_specs=[_row_block(bg)] * 2,
        out_shape=[
            jax.ShapeDtypeStruct((g_total, 1), dt),
            jax.ShapeDtypeStruct((g_total, 1), jnp.int32),
        ],
        interpret=interpret,
    )(g, valid, col(eps.astype(dt)), col(live))
    return margin[:Gq, 0], exit_g[:Gq, 0]
