"""A tiny cell driven end to end on the CPU, sound and with faults planted
underneath the timed path; and the control against the reference."""

import json
import time
from pathlib import Path

import numpy as np
import pytest

import harness

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def tiny_cell(kind: str, loop: str, **over) -> harness.Cell:
    cfg = json.loads((CONFIGS / f"{kind}.json").read_text())
    if cfg["ensemble"] == "oblivious_trees":
        cfg.update(n_trees=24, depth=3)
    else:
        cfg.update(n_lattices=12, lattice_features=3, train_steps=20, train_batch=128)
    cfg.update(train_rows=600, pool_rows=300, batch_size=64, backend="device",
               name=f"tiny_{kind}")
    cfg.update(over)
    mix = {"loop": "closed"} if loop == "closed" else {
        "loop": "open", "arrivals": "poisson", "rate_per_s": 400.0}
    metrics = {
        "end_to_end": [{"name": n, "unit": "-"} for n in
                       ("setup_s", "rows_per_s", "latency_p50_ms")],
        "per_layer": [],
    }
    return harness.Cell(f"tiny.{loop}", cfg, json.dumps(cfg).encode(), mix, 1, metrics)


@pytest.fixture(autouse=True)
def _cache(tmp_path_factory, monkeypatch):
    monkeypatch.setattr(harness, "CACHE", tmp_path_factory.getbasetemp() / "bench-cache")


def run(cell, seconds=1.0):
    return harness.run_cell(cell, 2**35 + 3, seconds, False, time.time(), log=lambda s: None)


@pytest.mark.parametrize("kind,loop", [("gbt500_adult", "closed"), ("lattice500_rw2", "open")])
def test_tiny_cell_is_correct(kind, loop):
    out = run(tiny_cell(kind, loop))
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    names = set(out["metrics"])
    assert "setup_s" in names
    assert ("rows_per_s" in names) == (loop == "closed")
    assert ("latency_p50_ms" in names) == (loop == "open")


def _faulty(fault, executor=None):
    """Wrap ``executor.run`` (``DeviceExecutor``'s by default) so its
    verdicts come out broken."""
    from repro.kernels.device_executor import DeviceExecutor

    inner = (executor or DeviceExecutor).run

    def run(self, batch, n, *a, **kw):
        res = inner(self, batch, n, *a, **kw)
        dec, ex = res.decisions.copy(), res.exit_step.copy()
        T = self.dplan.plan.T
        if fault == "answer_altered":
            dec[0] = ~dec[0]
        elif fault == "half_left_out":
            # the second half of the batch never ran: its initial verdicts
            dec[n // 2 :], ex[n // 2 :] = False, T
        elif fault == "state_unchanged":
            # a stage that hands back its carry: no score ever accumulates
            dec[:], ex[:] = 0.0 >= self.dplan.plan.beta, T
        elif fault == "exchange_left_out":
            # without the live-count exchange, the loop ends when the first
            # quarter of the batch (one shard's rows) has exited
            stop = ex[: max(n // 4, 1)].max()
            late = np.arange(n) >= n // 4
            dec[late & (ex > stop)], ex[late & (ex > stop)] = False, T
        res.decisions, res.exit_step = dec, ex
        return res

    return run


@pytest.mark.parametrize(
    "fault", ["answer_altered", "half_left_out", "state_unchanged", "exchange_left_out"]
)
def test_planted_fault_is_not_correct(fault, monkeypatch):
    from repro.kernels.device_executor import DeviceExecutor

    monkeypatch.setattr(DeviceExecutor, "run", _faulty(fault))
    out = run(tiny_cell("gbt500_adult", "closed"))
    assert not out["correct"]
    assert out["checks"]["mismatched_rows"]["value"] > 0


def test_unanswered_request_is_not_correct(monkeypatch):
    from repro.serving.engine import QWYCServer

    inner = QWYCServer.drain

    def drain(self):
        return inner(self)[:-1]

    monkeypatch.setattr(QWYCServer, "drain", drain)
    cell = tiny_cell("gbt500_adult", "open")
    out = run(cell)
    assert not out["correct"] and out["checks"]["unanswered"]["value"] > 0


def test_control_fails_where_the_reference_holds():
    """The reference with its weights in bfloat16, put in the program's
    place, reads mismatched rows at a size a test can hold."""
    cell = tiny_cell("lattice500_rw2", "closed", n_lattices=60, lattice_features=4,
                     train_rows=2000, pool_rows=2000)
    ens = harness.load_ensemble(cell.config["ensemble"])
    params, plan, _ = harness.fitted_artifact(cell, ens)
    sess = harness.Session(cell, ens, params, plan, harness._world(ens, cell.config), False)
    dec, ex, amb = harness.reference_verdicts(sess)
    cdec, cex, _ = harness.reference_verdicts(sess, lower=True)
    idx = np.arange(dec.size)
    sound = harness.check(sess, {"idx": idx, "dec": dec, "ex": ex})
    control = harness.check(sess, {"idx": idx, "dec": cdec, "ex": cex})
    assert sound["mismatched_rows"]["value"] == 0
    assert control["mismatched_rows"]["value"] > 0
