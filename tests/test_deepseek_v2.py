"""DeepSeek-V2 as a depth cascade against its plain float32 reference, on a
tiny DeepSeek-shaped model: d_model 64, 4 layers of which layer 0 is
dense, 16 routed experts of which 4 are held here, top-6 without
renormalisation, 2 shared experts, and the published YaRN settings.

Tolerances: program and reference both compute in float32 on the CPU
(the expert kernel in Pallas interpret mode), in different but sound
orders of operations (query chunks, a grouped expert buffer, fused
einsums), so scores agree to float32 rounding over 4 layers: a few 1e-7
of the largest score.  ``ATOL`` allows 100x that.  A lower precision
(bfloat16 weights) moves the scores by about 1e-3, which it would fail.
"""

import dataclasses
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import reference_deepseek_v2 as ref  # noqa: E402

from repro import api  # noqa: E402
from repro.configs.deepseek_v2_lite_16b import CONFIG  # noqa: E402
from repro.core.executor import CascadePlan  # noqa: E402
from repro.kernels.device_executor import DevicePlan  # noqa: E402
from repro.models import mla as MLA  # noqa: E402
from repro.models import moe as MOE  # noqa: E402
from repro.models.transformer import init_params  # noqa: E402

HELD = (1, 5, 9, 13)
TINY = CONFIG.scaled(
    n_layers=4, d_model=64, n_heads=2, n_kv_heads=2, d_ff=96, vocab_size=256,
    kv_lora_rank=32, nope_head_dim=16, v_head_dim=16, n_experts=16, experts_held=HELD,
    moe_d_ff=32, exit_interval=1,
)
SEQ = 12
ATOL = 5e-5


@pytest.fixture(scope="module")
def model():
    params = init_params(TINY, jax.random.PRNGKey(0))
    toks = np.random.default_rng(0).integers(0, 256, size=(6, SEQ)).astype(np.int32)
    return params, toks, ref.exit_scores(params, TINY, toks)


def _served_scores(scorer, toks, chunk_t=2):
    """Exit scores through the bound scorer's stage protocol, the calls the
    device executor's loop makes, at margin infinity."""
    E = scorer.n_exits
    plan = CascadePlan(order=np.arange(E), eps_pos=np.full(E, np.inf),
                       eps_neg=np.full(E, -np.inf), beta=0.0, costs=np.ones(E),
                       chunk_t=chunk_t)
    dplan = DevicePlan.from_plan(plan)
    bound = scorer.bind(dplan)
    n = toks.shape[0]
    x, state = bound.prepare(toks), bound.init_state(n)
    rows = jnp.arange(n, dtype=jnp.int32)
    cols = []
    for t0 in dplan.stage_t0:
        s, state = bound.stage(state, jnp.int32(t0), jnp.int32(t0) + dplan.W, rows, x,
                               jnp.int32(n))
        cols.append(np.asarray(s))
    return np.cumsum(np.concatenate(cols, 1)[:, :E], 1)


def test_published_settings():
    assert CONFIG.d_ff == 10944 and CONFIG.moe_d_ff == 1408 and CONFIG.first_dense_layers == 1
    assert not CONFIG.tie_embeddings and not CONFIG.norm_topk_prob
    assert not CONFIG.scale_embeddings and CONFIG.held_experts() == tuple(range(64))


def test_yarn_band_and_scale():
    assert MLA.yarn_band(CONFIG) == (10, 23) == ref.yarn_band(CONFIG)
    m = MLA.yarn_mscale(40, 0.707)
    assert m == pytest.approx(0.1 * 0.707 * math.log(40) + 1)
    assert m * m == pytest.approx(1.5896, abs=1e-4)
    assert MLA.softmax_scale(CONFIG) == pytest.approx(192**-0.5 * 1.5896, rel=1e-4)
    assert MLA.rope_mscale(CONFIG) == 1.0
    np.testing.assert_allclose(np.asarray(MLA.rope_inv_freq(CONFIG)), ref.inv_freq(CONFIG),
                               rtol=1e-6)
    inv = ref.inv_freq(CONFIG)
    extra = 10000.0 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(inv[:10], extra[:10])  # below lo: plain rope
    np.testing.assert_allclose(inv[23:], extra[23:] / 40)  # above hi: interpolated


@pytest.mark.parametrize("path", ["stage", "calibration"])
def test_exit_scores_match_reference(model, path):
    params, toks, want = model
    scorer = api.NeuralScorer(params, TINY, seq_len=SEQ)
    got = (_served_scores(scorer, toks) if path == "stage"
           else np.cumsum(scorer.calibration_scores(toks), 1))
    assert np.abs(want).max() > 0.05
    np.testing.assert_allclose(got, want, rtol=0, atol=ATOL)


def test_an_exit_every_two_layers_matches_reference(model):
    """Two layers a cascade position, the dense layer 0 inside the first."""
    params, toks, _ = model
    cfg = TINY.scaled(exit_interval=2)
    params = {**params, "exit_heads": params["exit_heads"][:2]}
    want = ref.exit_scores(params, cfg, toks)
    scorer = api.NeuralScorer(params, cfg, seq_len=SEQ)
    assert scorer.n_exits == 2
    np.testing.assert_allclose(_served_scores(scorer, toks), want, rtol=0, atol=ATOL)


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four chips' shares of a layer, each holding 4 of the 16 experts,
    add up to the whole layer once the shared experts, which every chip
    computes alike, are counted once."""
    uncut = dataclasses.replace(TINY, experts_held=())
    p = MOE.init_moe(jax.random.PRNGKey(3), uncut)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, SEQ, TINY.d_model))
    with jax.default_matmul_precision("highest"):
        whole = ref.moe(p, x.reshape(-1, TINY.d_model), uncut)
    parts = []
    for c in range(4):
        ids = tuple(range(4 * c, 4 * c + 4))
        share = {**p, **{k: p[k][4 * c : 4 * c + 4] for k in ("wi", "wg", "wo")}}
        out, _ = MOE.apply_moe(share, x, dataclasses.replace(TINY, experts_held=ids))
        parts.append(np.asarray(out).reshape(-1, TINY.d_model))
    sh = p["shared"]
    with jax.default_matmul_precision("highest"):
        shared = np.asarray(ref._swiglu(sh["wi"], sh["wg"], sh["wo"], x.reshape(-1, TINY.d_model)))
    np.testing.assert_allclose(sum(parts) - 3 * shared, np.asarray(whole), atol=ATOL)


def test_a_row_does_not_depend_on_its_batch_mates(model):
    """Dropless: a row's scores, verdict and exit step are the same alone
    and among 63 copies of one other row, which route every token to the
    same experts (a capacity-bound layer would drop most of them and move
    the scores by their size).  Alone the CPU's matmuls take another
    kernel, so scores agree to float32 rounding, not bit for bit."""
    params, toks, _ = model
    scorer = api.NeuralScorer(params, TINY, seq_len=SEQ)
    alone = _served_scores(scorer, toks[:1])
    crowd = np.concatenate([toks[:1], np.repeat(toks[1:2], 63, axis=0)])
    among = _served_scores(scorer, crowd)
    np.testing.assert_allclose(among[0], alone[0], rtol=0, atol=1e-6)
    np.testing.assert_array_equal(among[1], among[63])

    s = np.cumsum(scorer.calibration_scores(toks), 1)
    fitted = api.fit(np.diff(s, axis=1, prepend=0.0), beta=float(np.median(s[:, -1])),
                     alpha=0.2, mode="both", **scorer.fit_overrides())
    comp = fitted.compile("device", scorer=scorer)
    one = comp.evaluate(x=toks[:1])
    many = comp.evaluate(x=crowd)
    assert one.decisions[0] == many.decisions[0]
    assert one.exit_step[0] == many.exit_step[0]


def _plain_cascade(f, plan, tol):
    """(decisions, exit steps, near) of the plain cascade over stage scores
    ``f``; ``near`` marks rows within ``tol`` of a threshold they meet."""
    g = np.zeros(f.shape[0])
    live = np.ones(f.shape[0], bool)
    dec = np.zeros(f.shape[0], bool)
    ex = np.full(f.shape[0], f.shape[1])
    near = np.zeros(f.shape[0], bool)
    for t in range(f.shape[1]):
        g = np.where(live, g + f[:, t], g)
        near |= live & ((np.abs(g - plan.eps_neg[t]) < tol) | (np.abs(g - plan.eps_pos[t]) < tol))
        neg, pos = live & (g < plan.eps_neg[t]), live & (g > plan.eps_pos[t])
        out = neg | (pos & ~neg)
        dec |= pos & ~neg
        ex[out] = t + 1
        live &= ~out
    dec[live] = g[live] >= plan.beta
    near |= live & (np.abs(g - plan.beta) < tol)
    return dec, ex, near


def test_fit_compile_serve_matches_the_reference_cascade(model):
    params, _, _ = model
    rng = np.random.default_rng(1)
    calib = rng.integers(0, 256, size=(96, SEQ)).astype(np.int32)
    rows = rng.integers(0, 256, size=(40, SEQ)).astype(np.int32)
    s_cal = ref.exit_scores(params, TINY, calib)
    scorer = api.NeuralScorer(params, TINY, seq_len=SEQ)
    fitted = api.fit(np.diff(s_cal, axis=1, prepend=0.0), beta=float(np.median(s_cal[:, -1])),
                     alpha=0.05, mode="both", **scorer.fit_overrides())
    srv = fitted.compile("device", scorer=scorer).serve(batch_size=16)
    assert srv.backend == "kernel"
    for r in rows:
        srv.submit(r)
    srv.flush()
    out = srv.drain()
    dec = np.array([o["decision"] for o in out])
    ex = np.array([o["models_evaluated"] for o in out])
    s = ref.exit_scores(params, TINY, rows)
    want_dec, want_ex, near = _plain_cascade(np.diff(s, axis=1, prepend=0.0),
                                             fitted.model, ATOL)
    assert (ex < TINY.n_layers).any()  # some rows exit early
    ok = ~near
    assert ok.sum() >= 35
    np.testing.assert_array_equal(dec[ok], want_dec[ok])
    np.testing.assert_array_equal(ex[ok], want_ex[ok])


def test_serve_picks_the_policy_from_the_scorer(model):
    params, _, _ = model
    scorer = api.NeuralScorer(params, TINY, seq_len=SEQ)
    F = np.random.default_rng(2).normal(size=(64, TINY.n_layers))
    fitted = api.fit(F, beta=0.0, alpha=0.1, mode="both", **scorer.fit_overrides())
    assert fitted.compile("device", scorer=scorer).serve(batch_size=8).backend == "kernel"
    assert fitted.compile("host", scorer=scorer)._default_policy() == "kernel"
    # a stateless scorer (or none) still sorts by its stage-0 key
    plain = api.fit(F, beta=0.0, alpha=0.1)
    assert plain.compile("device")._default_policy() == "sorted-kernel"
    assert plain.compile("device", scorer=api.MatrixScorer()).serve(
        batch_size=8).backend == "sorted-kernel"
    # an explicit policy still wins
    assert fitted.compile("device", scorer=scorer).serve(
        batch_size=8, policy="cascade-scan").backend == "cascade-scan"


def _served_with_blocks(params, fitted, rows, tokens_per_block, monkeypatch):
    """``rows`` through ``compile("device")`` with ``tokens_per_block``
    tokens a neural block: (the evaluated batch, the served stats, the
    bound block size)."""
    from repro.api import scorers

    monkeypatch.setattr(scorers, "NEURAL_BLOCK_TOKENS", tokens_per_block)
    scorer = api.NeuralScorer(params, TINY, seq_len=SEQ)
    comp = fitted.compile("device", scorer=scorer, chunk_t=1)
    res = comp.evaluate(x=rows)
    srv = comp.serve(batch_size=32)
    for r in rows:
        srv.submit(r)
    srv.flush()
    srv.drain()
    return res, srv.stats, scorers.neural_block_rows(SEQ)


def test_neural_stages_bill_the_blocks_they_compute(model, monkeypatch):
    """A fit with exits, served with 4-row blocks: each stage bills
    ``ceil(n_in / bn) x bn x W``, less than the eager matrix, and the
    verdicts and exit steps are those of one block over all lanes."""
    params, _, _ = model
    rng = np.random.default_rng(1)
    calib = rng.integers(0, 256, size=(96, SEQ)).astype(np.int32)
    rows = rng.integers(0, 256, size=(40, SEQ)).astype(np.int32)
    s_cal = ref.exit_scores(params, TINY, calib)
    fitted = api.fit(np.diff(s_cal, axis=1, prepend=0.0), beta=float(np.median(s_cal[:, -1])),
                     alpha=0.05, mode="both", **api.NeuralScorer(params, TINY, SEQ).fit_overrides())
    res, stats, bn = _served_with_blocks(params, fitted, rows, 4 * SEQ, monkeypatch)
    one, _, whole = _served_with_blocks(params, fitted, rows, 1 << 20, monkeypatch)
    assert bn == 4 and whole >= 64  # the 40 rows' buffer is 64 lanes
    assert (res.exit_step < TINY.n_layers).any()  # some rows exit early
    W = 1  # chunk_t=1: one position a stage
    want = [-(-c.n_in // bn) * bn * W for c in res.chunk_stats]
    assert [c.scores_computed for c in res.chunk_stats] == want
    assert res.scores_computed == sum(want) < res.scores_possible
    assert stats.compute_fraction < 1.0
    np.testing.assert_array_equal(res.decisions, one.decisions)
    np.testing.assert_array_equal(res.exit_step, one.exit_step)
