"""The plain cascade agrees with the program's host oracle at a tiny size."""

import numpy as np
import pytest

import reference
from ensembles import lattices, oblivious_trees


@pytest.fixture(scope="module")
def chip_smoke():
    import chip_smoke

    return chip_smoke


def _compare(world, params, mod, chip_smoke):
    from repro import api

    fitted = api.fit(
        world.score_fn, world.x_train, beta=world.beta, alpha=0.02, mode=world.mode
    )
    m = fitted.model
    dec, ex = chip_smoke.host_oracle(world, m, world.x_test)
    scores = mod.scores(params, world.x_test)
    rdec, rex, amb = reference.cascade(scores[:, m.order], m.eps_pos, m.eps_neg, m.beta)
    assert amb.mean() < 0.05
    assert np.array_equal(rdec[~amb], dec[~amb])
    assert np.array_equal(rex[~amb], ex[~amb])
    assert ex.min() < m.T  # some rows exit early: the thresholds are exercised


def test_trees_match_host_oracle(chip_smoke):
    w = chip_smoke.gbt_world(n_trees=16, scale=0.05)
    s = w.scorer
    params = {"feats": np.asarray(s.feats), "thrs": np.asarray(s.thrs), "leaves": np.asarray(s.leaves)}
    _compare(w, params, oblivious_trees, chip_smoke)


def test_lattices_match_host_oracle(chip_smoke):
    w = chip_smoke.lattice_world(n_lattices=8, scale=0.05, steps=5)
    s = w.scorer
    params = {"theta": np.asarray(s.theta), "feats": np.asarray(s.feats)}
    _compare(w, params, lattices, chip_smoke)


def test_ambiguity_band_marks_rows_near_a_threshold():
    f = np.array([[1.0, 1.0], [1.0, 1.0]], np.float32)
    f[1, 0] = 1.0 + 1e-6  # within 1e-5 of the threshold at position 0
    dec, ex, amb = reference.cascade(f, [np.inf, np.inf], [1.0, -np.inf], 0.0)
    assert ex.tolist() == [2, 2] and dec.tolist() == [True, True]
    assert amb.tolist() == [True, True]  # row 0 sits on it, row 1 within the band
    dec, ex, amb = reference.cascade(f, [np.inf, np.inf], [0.5, -np.inf], 0.0)
    assert not amb.any()
