"""StageScorer protocol-conformance suite (DESIGN.md §11).

ONE parametrized contract over every built-in scorer family — matrix,
tree, lattice, neural — crossed with every execution tier: the host
``ChunkedExecutor`` (via ``api.scorers.host_producer``, the parity
oracle), the fused ``DeviceExecutor``, the shard_map'd
``ShardedDeviceExecutor`` at 1/2/4 shards, and the continuous-batching
``run_stream`` admission loop.  A scorer that passes this file serves on
every tier with bit-identical verdicts and one compiled trace per shape.

Also locked here: the survivor-state pytree contract — zero-state
round-trip through the executors' cumsum-prefix compaction
(``repack_state``), the empty-state fast path for stateless scorers,
and the megakernel x stateful incompatibility raise.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.api.scorers import host_producer
from repro.core import CascadePlan, evaluate_cascade, fit_qwyc
from repro.core.early_exit import exit_scores
from repro.core.executor import ChunkedExecutor
from repro.kernels import ops
from repro.kernels.device_executor import (
    DeviceExecutor,
    DevicePlan,
    repack_state,
)
from repro.kernels.sharded_executor import ShardedDeviceExecutor
from repro.launch.mesh import make_serving_mesh
from repro.models.config import ModelConfig
from repro.models.transformer import init_params

N_DEV = len(jax.devices())
ALPHA = 0.05
SCORERS = ["matrix", "tree", "lattice", "neural"]


def _shards_params(counts=(1, 2, 4)):
    return [
        pytest.param(
            k,
            marks=pytest.mark.skipif(
                N_DEV < k,
                reason=f"needs {k} devices (XLA_FLAGS="
                f"--xla_force_host_platform_device_count={k})",
            ),
        )
        for k in counts
    ]


def _neural_fixture():
    cfg = ModelConfig(
        name="conformance", arch_type="dense", n_layers=6, d_model=32,
        n_heads=2, n_kv_heads=1, head_dim=16, d_ff=64, vocab_size=64,
        exit_interval=2,
    )
    params = init_params(cfg, jax.random.PRNGKey(7))
    toks = np.asarray(
        jax.random.randint(jax.random.PRNGKey(8), (160, 8), 0, cfg.vocab_size)
    )
    return params, cfg, toks


_FIXTURES: dict = {}


def fixture_for(kind: str):
    """(scorer_template, F original-order (N, T) calibration scores,
    x batch operand, chunk_t) — cached, the fits are deterministic."""
    if kind in _FIXTURES:
        return _FIXTURES[kind]
    rng = np.random.default_rng({"matrix": 60, "tree": 61, "lattice": 62}.get(kind, 63))
    if kind == "matrix":
        t, d, n = 16, 6, 200
        W = rng.normal(size=(t, d))
        X = rng.normal(size=(n, d)).astype(np.float32)
        F = (X @ W.T).astype(np.float64)
        out = (api.MatrixScorer(), F, F, 4)
    elif kind == "tree":
        t, depth, d, n = 16, 3, 8, 180
        feats = rng.integers(0, d, size=(t, depth)).astype(np.int32)
        thrs = rng.uniform(size=(t, depth)).astype(np.float32)
        leaves = rng.normal(size=(t, 1 << depth)).astype(np.float32)
        x = rng.uniform(size=(n, d)).astype(np.float32)
        F = np.asarray(
            ops.gbt_scores(
                jnp.asarray(feats), jnp.asarray(thrs), jnp.asarray(leaves),
                jnp.asarray(x), block_n=32,
            )
        ).astype(np.float64)
        out = (api.TreeScorer(feats, thrs, leaves, block_n=32), F, x, 4)
    elif kind == "lattice":
        t, s, d, n = 16, 4, 9, 180
        theta = rng.normal(size=(t, 1 << s)).astype(np.float32)
        feats = np.stack(
            [rng.choice(d, s, replace=False) for _ in range(t)]
        ).astype(np.int32)
        x = rng.uniform(size=(n, d)).astype(np.float32)
        F = np.asarray(
            ops.lattice_scores(
                jnp.asarray(theta), jnp.asarray(feats), jnp.asarray(x),
                block_n=32,
            )
        ).astype(np.float64)
        out = (api.LatticeScorer(theta, feats, block_n=32), F, x, 4)
    else:
        params, cfg, toks = _neural_fixture()
        scorer = api.NeuralScorer(params, cfg, seq_len=toks.shape[1])
        out = (scorer, scorer.calibration_scores(toks), toks, 2)
    _FIXTURES[kind] = out
    return out


def _fit_plan(kind: str, alpha: float = ALPHA):
    scorer, F, x, chunk_t = fixture_for(kind)
    kw = scorer.fit_overrides() if kind == "neural" else {}
    m = fit_qwyc(F, beta=0.0, alpha=alpha, **kw)
    plan = CascadePlan.from_qwyc(m, chunk_t=chunk_t)
    return scorer, F, x, m, plan, DevicePlan.from_plan(plan)


# ------------------------------------------------------ host oracle tier


@pytest.mark.parametrize("kind", SCORERS)
def test_host_oracle_matches_evaluate_cascade(kind):
    """The ChunkedExecutor driving the SAME stage protocol through
    ``host_producer`` reproduces evaluate_cascade bit for bit — the
    oracle every device tier below is held to."""
    scorer, F, x, m, plan, _ = _fit_plan(kind)
    ev = evaluate_cascade(m, F)
    producer, n = host_producer(scorer, plan, x)
    res = ChunkedExecutor(plan, producer).run(n)
    np.testing.assert_array_equal(res.decisions, ev["decisions"])
    np.testing.assert_array_equal(res.exit_step, ev["exit_step"])


# ------------------------------------------------- device/sharded tiers


@pytest.mark.parametrize("kind", SCORERS)
def test_device_executor_parity(kind):
    scorer, F, x, m, plan, dplan = _fit_plan(kind)
    ev = evaluate_cascade(m, F)
    dex = DeviceExecutor(dplan, scorer.bind(dplan), block_n=32)
    res = dex.run(x, np.asarray(F).shape[0])
    np.testing.assert_array_equal(res.decisions, ev["decisions"])
    np.testing.assert_array_equal(res.exit_step, ev["exit_step"])
    assert dex.traces == 1


@pytest.mark.parametrize("shards", _shards_params())
@pytest.mark.parametrize("kind", SCORERS)
def test_sharded_executor_parity(kind, shards):
    scorer, F, x, m, plan, dplan = _fit_plan(kind)
    ev = evaluate_cascade(m, F)
    sx = ShardedDeviceExecutor(
        dplan, scorer.bind(dplan), make_serving_mesh(shards), block_n=32
    )
    res = sx.run(x, np.asarray(F).shape[0])
    np.testing.assert_array_equal(res.decisions, ev["decisions"])
    np.testing.assert_array_equal(res.exit_step, ev["exit_step"])
    assert sx.traces == 1


@pytest.mark.parametrize("kind", SCORERS)
def test_streaming_admission_parity(kind):
    """run_stream: rookies admitted into freed survivor lanes mid-cascade
    (per-lane stages, carried state re-initialized at t0 == 0) decide
    identically to the batch path, per row id."""
    scorer, F, x, m, plan, dplan = _fit_plan(kind)
    ev = evaluate_cascade(m, F)
    n = np.asarray(F).shape[0]
    dex = DeviceExecutor(dplan, scorer.bind(dplan), block_n=32)
    arrivals = np.sort(
        np.random.default_rng(9).integers(0, n // 8, size=n)
    ).astype(np.int32)
    res = dex.run_stream(x, n, arrivals=arrivals, capacity=32)
    np.testing.assert_array_equal(res.decisions, ev["decisions"])
    np.testing.assert_array_equal(res.exit_step, ev["exit_step"])
    assert dex.traces == 1


# -------------------------------------------------- margin-inf identity


def test_neural_margin_inf_is_full_depth_forward():
    """With thresholds at +/-inf nothing exits early, and the cascade's
    running sum telescopes to the LAST exit head's margin: verdicts are
    bit-identical to the full-depth forward on every tier."""
    scorer, F, x, m, plan, dplan = _fit_plan("neural")
    inf = np.full(m.T, np.inf)
    m_inf = dataclasses.replace(m, eps_pos=inf, eps_neg=-inf)
    plan_inf = CascadePlan.from_qwyc(m_inf, chunk_t=2)
    dplan_inf = DevicePlan.from_plan(plan_inf)
    params, cfg, toks = _neural_fixture()
    full = np.asarray(exit_scores(params, cfg, toks))[:, -1] >= m.beta
    producer, n = host_producer(scorer, plan_inf, x)
    host = ChunkedExecutor(plan_inf, producer).run(n)
    np.testing.assert_array_equal(host.decisions, full)
    assert np.all(host.exit_step == m.T)  # nobody left early
    dex = DeviceExecutor(dplan_inf, scorer.bind(dplan_inf), block_n=32)
    res = dex.run(x, n)
    np.testing.assert_array_equal(res.decisions, full)
    np.testing.assert_array_equal(res.exit_step, host.exit_step)
    assert dex.traces == 1


# ------------------------------------------------- neural block loop


_BLOCK_TOKENS = 32  # 4 rows of the fixture's 8 tokens a block
_CAP = 16  # four such blocks


def _neural_stage_outputs(monkeypatch, tokens_per_block, n_valid, t0):
    """One neural stage over a ``_CAP``-lane buffer, its state carried in
    from random values: (scores, h, s_prev) and the block size bound."""
    monkeypatch.setattr(api.scorers, "NEURAL_BLOCK_TOKENS", tokens_per_block)
    scorer, F, x, m, plan, dplan = _fit_plan("neural")
    bound = scorer.bind(dplan)
    xp = bound.prepare(x[:_CAP])
    rng = np.random.default_rng(3)
    state = {
        "h": jnp.asarray(rng.normal(size=xp.shape), xp.dtype),
        "s_prev": jnp.asarray(rng.normal(size=_CAP), jnp.float32),
    }
    rows = jnp.asarray(rng.permutation(_CAP), jnp.int32)
    scores, out = bound.stage(
        state, jnp.int32(t0), jnp.int32(t0) + dplan.W, rows, xp, jnp.int32(n_valid)
    )
    return np.asarray(scores), np.asarray(out["h"]), np.asarray(out["s_prev"]), bound.block_n


@pytest.mark.parametrize("n_valid", [1, 3, 4, 5, _CAP])
def test_neural_block_loop_computes_the_live_lanes_as_one_block(monkeypatch, n_valid):
    """The block-looped stage gives the live lanes the scores and carried
    state of one whole-capacity block, to float32 rounding, at the first
    stage (state from the embedded tokens) and a later one (carried);
    lanes past the live blocks score 0."""
    for t0 in (0, 2):
        blocked = _neural_stage_outputs(monkeypatch, _BLOCK_TOKENS, n_valid, t0)
        whole = _neural_stage_outputs(monkeypatch, 1 << 20, n_valid, t0)
        bn = blocked[3]
        assert bn == 4 and whole[3] >= _CAP
        for got, want in zip(blocked[:3], whole[:3]):
            np.testing.assert_allclose(got[:n_valid], want[:n_valid], rtol=1e-5, atol=1e-5)
        live = -(-n_valid // bn) * bn
        np.testing.assert_array_equal(blocked[0][live:], 0.0)


# ------------------------------------------------- survivor-state pytree


@pytest.mark.parametrize("kind", SCORERS)
def test_state_spec_and_empty_state_fast_path(kind):
    scorer, F, x, m, plan, dplan = _fit_plan(kind)
    bound = scorer.bind(dplan)
    if kind == "neural":
        assert bound.stateful
        state = bound.init_state(8)
        assert set(state) == {"h", "s_prev"}
        assert state["h"].shape[0] == 8
        # stateful scorers cannot feed the sorted-kernel policy's sort
        # key (no stateless fn) and carry no megakernel slabs
        assert bound.fn is None and bound.slabs is None
    else:
        # the empty-state fast path: no leaves, init_state returns the
        # empty pytree, and the state threading adds nothing to carries
        assert not bound.stateful
        assert bound.state_spec == ()
        assert jax.tree_util.tree_leaves(bound.init_state(8)) == []


def test_repack_state_front_packs_like_row_compaction():
    """The state pytree rides the SAME cumsum-prefix compaction as row
    ids: survivors land front-packed in pack order, retired lanes drop
    (out-of-bounds scatter), vacated tail lanes read zero."""
    cap = 6
    state = {
        "h": jnp.arange(cap * 2, dtype=jnp.float32).reshape(cap, 2),
        "s": jnp.arange(cap, dtype=jnp.float32),
    }
    updated = jax.tree_util.tree_map(lambda a: a + 100.0, state)
    # lanes 1, 3, 4 survive -> packed slots 0, 1, 2; others scatter OOB
    pack = jnp.asarray([cap, 0, cap, 1, 2, cap], dtype=jnp.int32)
    out = repack_state(state, updated, pack)
    np.testing.assert_array_equal(
        np.asarray(out["s"]), [101.0, 103.0, 104.0, 0.0, 0.0, 0.0]
    )
    np.testing.assert_array_equal(
        np.asarray(out["h"][:3]), np.asarray(updated["h"])[[1, 3, 4]]
    )
    np.testing.assert_array_equal(np.asarray(out["h"][3:]), 0.0)
    # stateless no-op: empty pytree in, empty pytree out
    assert repack_state((), (), pack) == ()


def test_megakernel_rejects_stateful_scorer():
    scorer, F, x, m, plan, dplan = _fit_plan("neural")
    with pytest.raises(ValueError, match="stateful|state"):
        DeviceExecutor(dplan, scorer.bind(dplan), block_n=32, megakernel=True)


# ------------------------------------------------------- registry + api


def test_registry_round_trip():
    for name, cls in (
        ("matrix", api.MatrixScorer),
        ("tree", api.TreeScorer),
        ("lattice", api.LatticeScorer),
        ("neural", api.NeuralScorer),
        ("function", api.FunctionScorer),
    ):
        assert name in api.scorer_names()
        assert api.get_scorer(name) is cls
    with pytest.raises(KeyError, match="registered"):
        api.get_scorer("warp-drive")
    with pytest.raises(TypeError):
        api.register_scorer("nope", object)


def test_model_backed_fit_pins_depth_order():
    """api.fit(NeuralScorer, tokens): calibrates on per-block logit
    margins, pins order=arange and per-stage cost=exit_interval, and the
    compiled host/device paths agree."""
    scorer, F, x, _, _, _ = _fit_plan("neural")
    fitted = api.fit(scorer, x, alpha=ALPHA, chunk_t=2)
    assert fitted.scorer is scorer
    np.testing.assert_array_equal(fitted.model.order, np.arange(scorer.n_exits))
    np.testing.assert_array_equal(
        fitted.model.costs, np.full(scorer.n_exits, scorer.cfg.exit_interval)
    )
    host = fitted.compile("host").evaluate(x=x)
    dev = fitted.compile("device").evaluate(x=x)
    np.testing.assert_array_equal(dev.decisions, host.decisions)
    np.testing.assert_array_equal(dev.exit_step, host.exit_step)
