"""The served path's profiler spans and device scopes (``repro.tracing``).

A flush recorded by the profiler on the CPU carries one ``qwyc.flush``
span with its phases nested inside; the lowered device programs carry the
``qwyc.*`` scopes in their ops' ``op_name`` metadata.  The benchmark's
trace reduction reads both on the chip.

All tests use LOCAL rngs so the session-rng stream stays stable for the
rest of the suite.
"""

import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from conftest import make_scores
from repro import api, tracing
from repro.core import CascadePlan, fit_qwyc
from repro.kernels import ops
from repro.kernels.device_executor import (
    DeviceExecutor,
    DevicePlan,
    matrix_stage_scorer,
)
from repro.kernels.sharded_executor import ShardedDeviceExecutor
from repro.launch.mesh import make_serving_mesh
from repro.serving import engine

N_DEV = len(jax.devices())

FLUSH_PHASES = (
    tracing.FLUSH_STACK,
    tracing.FLUSH_PREPARE,
    tracing.FLUSH_SORT_KEY,
    tracing.RUN_DISPATCH,
    tracing.RUN_FETCH,
    tracing.RUN_STATS,
    tracing.FLUSH_FINISH,
)


def _tree_cascade(seed=5, t=16, depth=3, d=8, n=150):
    """A fitted oblivious-tree cascade and its feature rows."""
    rng = np.random.default_rng(seed)
    feats = rng.integers(0, d, size=(t, depth)).astype(np.int32)
    thrs = rng.uniform(size=(t, depth)).astype(np.float32)
    leaves = rng.normal(size=(t, 1 << depth)).astype(np.float32)
    x = rng.uniform(size=(n, d)).astype(np.float32)
    F = np.asarray(
        ops.gbt_scores(
            jnp.asarray(feats), jnp.asarray(thrs), jnp.asarray(leaves),
            jnp.asarray(x), block_n=64,
        )
    ).astype(np.float64)
    fitted = api.fit(F, beta=0.0, alpha=0.02, chunk_t=4)
    scorer = api.TreeScorer(feats, thrs, leaves, block_n=32)
    return fitted, scorer, x


def _host_spans(trace_dir):
    """[(name, start_ns, end_ns, stats)] of every ``qwyc.*`` host event."""
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out += [
                (e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
                for e in line.events
                if e.name.startswith("qwyc.")
            ]
    return sorted(out, key=lambda s: s[1])


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_served_flush_spans(tmp_path):
    """One ``qwyc.flush`` per non-empty flush, every phase once inside
    it, compiles only inside the first flush, and a ``drain`` with an
    empty queue emits no flush span."""
    fitted, scorer, x = _tree_cascade()
    srv = fitted.compile("device", scorer=scorer, block_n=32).serve(batch_size=64)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for row in x:  # 150 rows: two inline flushes of 64, one of 22
            srv.submit(row)
        out = srv.drain()
        assert srv.drain() == []  # empty queue: no flush
    finally:
        jax.profiler.stop_trace()
    assert len(out) == x.shape[0]

    spans = _host_spans(str(tmp_path))
    flushes = [s for s in spans if s[0] == tracing.FLUSH]
    assert len(flushes) == srv.stats.n_batches == 3
    assert [f[3]["n"] for f in flushes] == [64, 64, 22]
    assert [f[3]["index"] for f in flushes] == [0, 1, 2]
    for name in FLUSH_PHASES:
        phases = [s for s in spans if s[0] == name]
        assert len(phases) == 3, name
        assert all(_inside(p, f) for p, f in zip(phases, flushes)), name
    # the phases follow one another inside their flush
    for f in flushes:
        inner = [s for s in spans if s[0] in FLUSH_PHASES and _inside(s, f)]
        assert [s[0] for s in inner] == list(FLUSH_PHASES)
        assert all(a[2] <= b[1] for a, b in zip(inner, inner[1:]))
    # the stage program and the sort-key program compile once, in the
    # first flush; later flushes reuse them
    compiles = [s for s in spans if s[0] == tracing.COMPILE]
    assert len(compiles) == 2
    assert all(_inside(c, flushes[0]) for c in compiles)
    drains = [s for s in spans if s[0] == tracing.DRAIN]
    assert len(drains) == 2
    assert all(not _inside(d, f) for d in drains for f in flushes)


def _op_names(lowered) -> set:
    """The ``op_name`` paths of a lowered program's ops that pass through
    a ``qwyc.*`` scope."""
    text = lowered.as_text(debug_info=True)
    return set(re.findall(r'loc\("([^"]*qwyc\.[^"]*)"', text))


def _has(names, pattern) -> bool:
    return any(re.search(pattern, n) for n in names)


def _matrix_plan(seed=3, n=220, t=24, chunk_t=4):
    rng = np.random.default_rng(seed)
    F = make_scores(rng, n=n, t=t)
    m = fit_qwyc(F, beta=0.0, alpha=0.02)
    dplan = DevicePlan.from_plan(CascadePlan.from_qwyc(m, chunk_t=chunk_t))
    return dplan, F[:, m.order].astype(np.float32)


@pytest.mark.parametrize("megakernel", [True, False])
def test_device_program_scopes(megakernel):
    """Both stage-step paths put their kernels under ``qwyc.score_decide``
    inside the stage's ``qwyc.compact``, and the tail under
    ``qwyc.finalize``."""
    dplan, Fo = _matrix_plan()
    ex = DeviceExecutor(
        dplan, matrix_stage_scorer(dplan), block_n=32, megakernel=megakernel
    )
    assert ex.megakernel == megakernel
    cap = ex._cap(Fo.shape[0])
    x = jnp.pad(jnp.asarray(Fo), ((0, cap - Fo.shape[0]), (0, 0)))
    names = _op_names(
        ex._jit.lower(x, jnp.arange(cap, dtype=jnp.int32), Fo.shape[0], ex.scorer.weights)
    )
    assert _has(names, r"while/body/qwyc\.compact/qwyc\.score_decide/")
    # gathers, scatters and repacking: in the stage, outside the kernels
    assert _has(names, r"while/body/qwyc\.compact/(?!qwyc\.).*scatter")
    assert _has(names, r"^jit\(_program\)/qwyc\.finalize/")
    # the collective scope is the sharded executor's alone
    assert not _has(names, "qwyc\\.collective")


def test_sort_key_program_scope(monkeypatch):
    """The sorted-kernel policy's key program, its sort included, runs
    under ``qwyc.sort_key``."""
    built = []

    def record(compiled, jitted, *args, **kw):
        built.append((jitted, args))
        return compile_program(compiled, jitted, *args, **kw)

    compile_program = engine.compile_program
    monkeypatch.setattr(engine, "compile_program", record)
    fitted, scorer, x = _tree_cascade()
    srv = fitted.compile("device", scorer=scorer, block_n=32).serve(batch_size=64)
    for row in x[:10]:
        srv.submit(row)
    assert len(srv.drain()) == 10
    (jitted, args), = built
    names = _op_names(jitted.lower(*args))
    assert _has(names, r"^jit\(key_rows\)/qwyc\.sort_key/")
    assert _has(names, r"^jit\(key_rows\)/qwyc\.sort_key/sort")


@pytest.mark.parametrize("megakernel", [True, False])
@pytest.mark.parametrize(
    "shards",
    [
        1,
        pytest.param(
            4,
            marks=pytest.mark.skipif(
                N_DEV < 4,
                reason="needs 4 devices (XLA_FLAGS="
                "--xla_force_host_platform_device_count=4)",
            ),
        ),
    ],
)
def test_sharded_program_scopes(shards, megakernel):
    """The per-shard body carries the same scopes as the one-device
    program, and its all-gathers and psums (the rebalance's included) sit
    under ``qwyc.collective``."""
    dplan, Fo = _matrix_plan()
    sx = ShardedDeviceExecutor(
        dplan, matrix_stage_scorer(dplan), make_serving_mesh(shards),
        block_n=32, megakernel=megakernel, rebalance=True,
    )
    assert sx.megakernel == megakernel
    cap_l = sx._cap_local(Fo.shape[0])
    x = jnp.pad(jnp.asarray(Fo), ((0, shards * cap_l - Fo.shape[0]), (0, 0)))
    idbuf = jnp.arange(shards * cap_l, dtype=jnp.int32).reshape(shards, cap_l)
    n_live = jnp.full((shards,), cap_l, dtype=jnp.int32)
    names = _op_names(sx._jit.lower(x, idbuf, n_live))
    assert _has(names, r"while/body/qwyc\.compact/qwyc\.score_decide/")
    assert _has(names, r"while/body/qwyc\.compact/(?!qwyc\.).*scatter")
    # the live-count census each stage, the rebalance's migration (under
    # its conditional), the first total and the final assembly
    assert _has(names, r"while/body/qwyc\.compact/qwyc\.collective/all_gather")
    assert _has(names, r"qwyc\.compact/cond/.*qwyc\.collective/all_gather")
    # (the per-shard body is a function of its own where shards > 1)
    assert _has(names, r"^(jit\(_program\)/)?qwyc\.collective/psum")
    assert _has(names, r"^(jit\(_program\)/)?qwyc\.finalize/qwyc\.collective/psum")
