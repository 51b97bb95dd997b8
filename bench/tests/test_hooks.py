"""The optional hooks of an ensemble kind (``bench/ensembles/__init__.py``):
a toy kind that brings its own integer rows, a pinned order with a cost
per position, a wider rounding band and per-position work, driven through
the harness on the CPU; and the three accepted configurations, which
define no hook, on the default path."""

import dataclasses
import hashlib
import json
import sys
import time
import types
from pathlib import Path

import numpy as np
import pytest

import harness
import work
import world as worldgen

BENCH = Path(__file__).resolve().parents[1]
T, VOCAB = 12, 16


def _toy_module(band=(1e-4, 0.05, "toy: a wider band than the default")) -> types.ModuleType:
    """An additive model over integer rows: model ``t`` looks up row
    column ``t`` in its own table of ``VOCAB`` scores."""
    mod = types.ModuleType("ensembles.toy_additive")

    def world(cfg):
        rng = np.random.default_rng(int(cfg["world_seed"]))
        n_train, n_pool = int(cfg["train_rows"]), int(cfg["pool_rows"])
        x = rng.integers(0, VOCAB, size=(n_train + n_pool, T), dtype=np.int32)
        y = rng.integers(0, 2, size=n_train)
        return worldgen.World(x[:n_train], y, x[n_train:])

    def scores(params, x):
        return params["table"][np.arange(T), np.asarray(x, np.int64)].astype(np.float32)

    def train(cfg, w):
        rng = np.random.default_rng(int(cfg["world_seed"]) + 1)
        table = (rng.normal(size=(T, VOCAB)) / np.arange(1, T + 1)[:, None]).astype(np.float32)
        return {"table": table}, float(np.median(scores({"table": table}, w.x_train).sum(1)))

    def lower_precision(params):
        import jax.numpy as jnp

        q = jnp.asarray(params["table"], jnp.bfloat16).astype(jnp.float32)
        return {"table": np.asarray(q)}

    def program_scorer(params):
        from repro import api
        from repro.kernels.device_executor import matrix_stage_scorer

        def factory(dplan):
            base = matrix_stage_scorer(dplan)
            order = np.asarray(dplan.plan.order)

            def prepare(rows):
                return base.prepare(scores(params, np.asarray(rows))[:, order])

            return dataclasses.replace(base, prepare=prepare)

        return api.FunctionScorer(factory)

    def model_ops(cfg):
        return np.arange(1, T + 1, dtype=np.int64) * 3

    def model_param_bytes(cfg):
        return np.full(T, 4 * VOCAB) + np.arange(T)

    def fit_settings(cfg, n):
        return {"optimize_order": False, "order": np.arange(n), "costs": model_ops(cfg)}

    def rounding_band(cfg):
        return band

    for f in (world, scores, train, lower_precision, program_scorer, model_ops,
              model_param_bytes, fit_settings, rounding_band):
        setattr(mod, f.__name__, f)
    return mod


def toy_cell() -> harness.Cell:
    cfg = {"name": "toy_additive", "ensemble": "toy_additive", "precision": "float32",
           "world_seed": 5, "train_rows": 800, "pool_rows": 300, "mode": "both",
           "alpha": 0.02, "batch_size": 64, "backend": "device"}
    metrics = {"end_to_end": [{"name": "setup_s", "unit": "s"},
                              {"name": "rows_per_s", "unit": "rows/s"}], "per_layer": []}
    return harness.Cell("toy.closed", cfg, json.dumps(cfg).encode(), {"loop": "closed"}, 1,
                        metrics)


@pytest.fixture(autouse=True)
def _cache(tmp_path_factory, monkeypatch):
    monkeypatch.setattr(harness, "CACHE", tmp_path_factory.getbasetemp() / "bench-cache")


@pytest.fixture
def toy(monkeypatch):
    mod = _toy_module()
    monkeypatch.setitem(sys.modules, "ensembles.toy_additive", mod)
    return mod


def run(cell):
    return harness.run_cell(cell, 2**34 + 7, 0.5, False, time.time(), log=lambda s: None)


@pytest.fixture
def served(toy, monkeypatch):
    """A session of the toy cell and one closed-loop window, with every
    row the harness handed to ``submit``."""
    from repro.serving.engine import QWYCServer

    sess = harness.open_session(toy_cell())
    harness.warm_up(sess)
    seen = []
    inner = QWYCServer.submit

    def submit(self, x):
        seen.append(x)
        return inner(self, x)

    monkeypatch.setattr(QWYCServer, "submit", submit)
    run_ = harness.closed_loop(sess, 2**33 + 1, 0.3, harness.Spans(False))
    return sess, run_, seen


def test_rows_reach_submit_unchanged(served):
    sess, run_, seen = served
    assert sess.world.pool.dtype == np.int32
    sent = np.stack(seen)
    assert sent.dtype == np.int32
    np.testing.assert_array_equal(sent, sess.world.pool[run_["idx"]])
    checks = harness.check(sess, run_)
    assert checks["mismatched_rows"]["value"] == 0, checks
    assert checks["ambiguous_share"]["limit"] == 0.05


def test_fit_settings_pin_the_order(served):
    sess, _, _ = served
    np.testing.assert_array_equal(sess.plan["order"], np.arange(T))
    np.testing.assert_array_equal(sess.plan["costs"], np.arange(1, T + 1) * 3)


def test_per_position_work_is_the_hand_sum(served, toy):
    sess, run_, _ = served
    cfg, order = sess.cell.config, sess.plan["order"]
    per, pbytes = toy.model_ops(cfg), toy.model_param_bytes(cfg)
    ex, fmax = run_["ex"], run_["flush_max_exit"]
    assert work.ops(toy, cfg, ex, order) == sum(int(per[order[:k]].sum()) for k in ex)
    want = ex.size * (4 * T + 8) + sum(int(pbytes[order[:k]].sum()) for k in fmax)
    assert work.hbm_bytes(toy, cfg, T, ex, fmax, order) == want


def test_per_position_work_follows_the_plan_order(toy):
    cfg = {}
    order = np.array([3, 0, 1, 2] + list(range(4, T)))
    # one row exits after one model: the plan's first, model 3 (ops 12)
    assert work.ops(toy, cfg, np.array([1]), order) == 12
    assert work.ops(toy, cfg, np.array([2, 0]), order) == 12 + 3


def test_toy_cell_is_correct_and_a_planted_fault_is_not(toy, monkeypatch):
    out = run(toy_cell())
    assert out["correct"], out["checks"]
    assert out["checks"]["ambiguous_share"]["value"] <= 0.05

    from repro.kernels.device_executor import DeviceExecutor

    inner = DeviceExecutor.run

    def flipped(self, batch, n, *a, **kw):
        res = inner(self, batch, n, *a, **kw)
        dec = res.decisions.copy()
        dec[: max(n // 2, 1)] = ~dec[: max(n // 2, 1)]
        res.decisions = dec
        return res

    monkeypatch.setattr(DeviceExecutor, "run", flipped)
    out = run(toy_cell())
    assert not out["correct"]
    assert out["checks"]["mismatched_rows"]["value"] > 0


def test_a_band_that_hides_most_rows_fails_ambiguous_share(monkeypatch):
    monkeypatch.setitem(sys.modules, "ensembles.toy_additive",
                        _toy_module(band=(1.0, 0.05, "toy: far too wide")))
    out = run(toy_cell())
    assert out["checks"]["mismatched_rows"]["value"] == 0
    assert out["checks"]["ambiguous_share"]["value"] > 0.5
    assert not out["correct"]


# the artifact file and a checksum of the world pool each accepted
# configuration had before the hooks existed
ACCEPTED = {
    "gbt500_adult": ("gbt500_adult-5389a5912aa9b129.npz", "2277b65d8e206e38"),
    "gbt500_adult_b256": ("gbt500_adult_b256-872c32cbf09de05d.npz", "2277b65d8e206e38"),
    "lattice500_rw2": ("lattice500_rw2-2637512cdf505fbe.npz", "b07df57e560e2ef2"),
}


@pytest.mark.parametrize("name", sorted(ACCEPTED))
def test_accepted_configs_take_the_default_path(name):
    raw = (BENCH / "configs" / f"{name}.json").read_bytes()
    cfg = json.loads(raw)
    cell = harness.Cell(name, cfg, raw, {"loop": "closed"}, 1, {})
    ens = harness.load_ensemble(cfg["ensemble"])
    artifact, pool_sum = ACCEPTED[name]
    assert harness.artifact_path(cell).name == artifact
    pool = harness._world(ens, cfg).pool
    assert pool.dtype == np.float32
    assert hashlib.sha256(pool.tobytes()).hexdigest()[:16] == pool_sum
    assert harness.fit_settings(ens, cfg, 500) == {}
    assert harness.rounding_band(ens, cfg) == harness.DEFAULT_BAND
    assert np.ndim(ens.model_ops(cfg)) == 0 and np.ndim(ens.model_param_bytes(cfg)) == 0
