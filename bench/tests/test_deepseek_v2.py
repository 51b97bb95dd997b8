"""The DeepSeek-V2 kind at toy size through the harness on the CPU: sound,
its float8 control refused, its expert metrics readable; and the real
configuration's artifact name and pool pinned."""

import hashlib
import json
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

import harness
from ensembles import deepseek_v2 as ds

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
METRICS = ("expert_share.offline", "expert_roofline.offline")


def toy_cell(**over) -> harness.Cell:
    cfg = json.loads((CONFIGS / "deepseek_v2_lite_cls512.json").read_text())
    cfg.update(
        name="toy_deepseek_v2", hidden_size=64, num_attention_heads=2, num_key_value_heads=2,
        qk_nope_head_dim=16, v_head_dim=16, kv_lora_rank=32, intermediate_size=96,
        moe_intermediate_size=32, n_routed_experts=16, n_experts_held=4,
        num_hidden_layers=4, vocab_size=256, seq_len=12, probe_rows=96, train_rows=160,
        pool_rows=64, batch_size=32, alpha=0.05, backend="device",
    )
    cfg.update(over)
    metrics = {"end_to_end": [{"name": "setup_s", "unit": "s"},
                              {"name": "rows_per_s", "unit": "rows/s"}], "per_layer": []}
    return harness.Cell("toy_deepseek_v2.offline", cfg, json.dumps(cfg).encode(),
                        {"loop": "closed"}, 1, metrics)


@pytest.fixture(scope="module")
def session(tmp_path_factory):
    """One toy session, served for one closed-loop window."""
    mp = pytest.MonkeyPatch()
    base = tmp_path_factory.mktemp("ds-cache")
    mp.setattr(harness, "CACHE", base)
    mp.setattr(ds, "CACHE", base / "deepseek_v2")
    # a toy's 4 layers move bfloat16 scores far less than 27 do: its own band
    mp.setattr(ds, "BAND", (1e-3, 0.30, "toy"))
    sess = harness.open_session(toy_cell())
    harness.warm_up(sess)
    run = harness.closed_loop(sess, 2**34 + 5, 0.5, harness.Spans(False))
    yield sess, run
    mp.undo()


def test_toy_cell_is_correct(session):
    sess, run = session
    assert sess.srv.backend == "kernel"  # stateful scorer: no sort key
    assert harness.executor_problem(sess) is None
    checks = harness.check(sess, run)
    assert all(c["value"] <= c["limit"] for c in checks.values()), checks
    assert run["idx"].size > 0
    # the cascade exits early on some rows and runs deep on others
    assert 1 <= run["ex"].min() and run["ex"].max() <= 4


def test_float8_control_fails(session):
    sess, run = session
    cdec, cex, _ = harness.reference_verdicts(sess, lower=True)
    ctrl = harness.check(sess, dict(run, dec=cdec[run["idx"]], ex=cex[run["idx"]]))
    assert ctrl["mismatched_rows"]["value"] > 0, ctrl


def test_plan_is_depth_ordered_and_costed(session):
    sess, _ = session
    cfg = sess.cell.config
    np.testing.assert_array_equal(sess.plan["order"], np.arange(4))
    ops = ds.model_ops(cfg)
    np.testing.assert_allclose(sess.plan["costs"], ops)
    assert ops[0] != ops[1] == ops[3]  # the dense layer 0 against an MoE layer
    assert ds.model_param_bytes(cfg).shape == (4,)


def test_expert_metrics_read_a_trace(session):
    """The two expert readers on a summary that holds the kernel's ops,
    and nothing on one that does not."""
    import work

    sess, run = session
    from harness import load_reader

    def ctx(ops):
        summary = SimpleNamespace(busy_s=[2.0], device_ops=ops)
        return SimpleNamespace(summary=summary, ens=ds, cfg=sess.cell.config, run=run,
                               peak={"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})

    hit = ctx([["%qwyc_moe_experts.3 (custom-call)", 0.5], ["%fusion.1 (fusion)", 1.0]])
    assert load_reader("expert_share.offline")(hit) == pytest.approx(25.0)
    flops, nbytes = ds.expert_work(sess.cell.config, run["idx"], run["ex"],
                                   run["flush_max_exit"])
    assert flops > 0 and nbytes > 0
    want = 100 * work.least_seconds(flops, nbytes, hit.peak) / 0.5
    assert load_reader("expert_roofline.offline")(hit) == pytest.approx(want)
    miss = ctx([["%fusion.1 (fusion)", 1.0]])
    assert all(load_reader(m)(miss) is None for m in METRICS)


def test_reference_runs_once_per_checkout(session, monkeypatch):
    sess, _ = session

    def refuse(*a, **kw):
        raise AssertionError("reference recomputed")

    monkeypatch.setattr(ds, "_reference", refuse)
    pool = sess.world.pool
    np.testing.assert_array_equal(ds.scores(sess.params, pool), ds.scores(sess.params, pool))
    assert ds.pool_slots(sess.cell.config).shape == (pool.shape[0], 4)


def test_real_config_artifact_and_pool_are_pinned():
    """As ``test_hooks.py`` pins the accepted configurations'."""
    raw = (CONFIGS / "deepseek_v2_lite_cls512.json").read_bytes()
    cfg = json.loads(raw)
    cell = harness.Cell(cfg["name"], cfg, raw, {"loop": "closed"}, 1, {})
    assert harness.artifact_path(cell).name == ARTIFACT
    pool = ds.world(cfg).pool
    assert pool.dtype == np.int32 and pool.shape == (512, 512)
    assert hashlib.sha256(pool.tobytes()).hexdigest()[:16] == POOL_SUM
    ops = ds.model_ops(cfg)
    # about 165 MFLOP a token in the dense layer 0 and 78 in an MoE layer (PERF.md §4)
    assert ops[0] / 512 == pytest.approx(165e6, rel=0.02)
    assert ops[1] / 512 == pytest.approx(78e6, rel=0.02)


# the artifact file and a checksum of the pool the configuration serves
ARTIFACT = "deepseek_v2_lite_cls512-4dc322bdae9a6ccc.npz"
POOL_SUM = "03fd408aae4b0070"
