"""Per-model operation and byte counts against hand-computed values."""

import json
from pathlib import Path

import numpy as np
import pytest

import work
from ensembles import lattices, oblivious_trees

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def test_tree_counts_by_hand():
    cfg = json.loads((CONFIGS / "gbt500_adult.json").read_text())
    # depth 5: 5 compares + 5 shift-adds + 1 add into the score
    assert oblivious_trees.model_ops(cfg) == 11
    # 5 feature ids + 5 thresholds + 32 leaves, 4 bytes each
    assert oblivious_trees.model_param_bytes(cfg) == 168


def test_lattice_counts_by_hand():
    cfg = json.loads((CONFIGS / "lattice500_rw2.json").read_text())
    # 255 contractions of 3 ops, 8 (1 - x) terms, 1 add into the score
    assert lattices.model_ops(cfg) == 3 * 255 + 8 + 1 == 774
    # 8 feature ids + 256 corners, 4 bytes each
    assert lattices.model_param_bytes(cfg) == 1056


def test_work_and_least_time():
    cfg = {"depth": 5}
    ex = np.array([1, 40, 500, 3])
    order = np.arange(500)
    assert work.ops(oblivious_trees, cfg, ex, order) == 544 * 11
    # 4 rows of 14 float32 features in and 8 bytes out; two flushes that
    # reached models 500 and 40
    assert work.hbm_bytes(oblivious_trees, cfg, 14, ex, [500, 40], order) == 4 * 64 + 540 * 168
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_seconds(1000.0, 50.0, peak) == 10.0
    assert work.least_seconds(100.0, 50.0, peak) == 5.0


@pytest.mark.parametrize("mod", [oblivious_trees, lattices])
def test_lower_precision_changes_only_the_payload(mod):
    rng = np.random.default_rng(0)
    params = (
        {"feats": np.zeros((3, 2), np.int32), "thrs": rng.random((3, 2), np.float32),
         "leaves": rng.normal(size=(3, 4)).astype(np.float32)}
        if mod is oblivious_trees
        else {"feats": np.zeros((3, 2), np.int32), "theta": rng.normal(size=(3, 4)).astype(np.float32)}
    )
    low = mod.lower_precision(params)
    changed = [k for k in params if not np.array_equal(params[k], low[k])]
    assert changed == (["leaves"] if mod is oblivious_trees else ["theta"])
