"""A served flush crosses the host-device boundary once each way.

The batch is padded on the host to the flush capacity, the sorted-kernel
policy's permutation stays on the device (the key program returns the
stage loop's rows buffer), and the results come back in one read
(``ServeStats.device_reads``).  So a server builds its programs in its
first flush and none after, whatever the later flush sizes.

All tests use LOCAL rngs so the session-rng stream stays stable for the
rest of the suite.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import evaluate_cascade
from repro.kernels import ops
from repro.kernels.device_executor import pad_rows

N_DEV = len(jax.devices())
CAP = 64  # the servers' flush capacity: batch_size 64 at block_n 32

_LOWERED = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_lowered = [0]


def _count(event, duration, **kw):
    if event == _LOWERED:
        _lowered[0] += 1


jax.monitoring.register_event_duration_secs_listener(_count)


def _tree_cascade(seed=11, t=16, depth=3, d=8, n=4 * CAP):
    """A fitted oblivious-tree cascade, its rows and their (n, T) scores.

    Every 4th row repeats the row before it, so the stage-0 sort key has
    planted ties on top of the leaf collisions depth 3 gives anyway."""
    rng = np.random.default_rng(seed)
    feats = rng.integers(0, d, size=(t, depth)).astype(np.int32)
    thrs = rng.uniform(size=(t, depth)).astype(np.float32)
    leaves = rng.normal(size=(t, 1 << depth)).astype(np.float32)
    x = rng.uniform(size=(n, d)).astype(np.float32)
    x[3::4] = x[2::4]
    F = np.asarray(
        ops.gbt_scores(
            jnp.asarray(feats), jnp.asarray(thrs), jnp.asarray(leaves),
            jnp.asarray(x), block_n=64,
        )
    )
    fitted = api.fit(F.astype(np.float64), beta=0.0, alpha=0.02, chunk_t=4)
    scorer = api.TreeScorer(feats, thrs, leaves, block_n=32)
    return fitted, scorer, x, F


def _serve(srv, rows):
    for row in rows:
        srv.submit(row)
    return srv.drain()


def _check(fitted, F, rows_from, out):
    ev = evaluate_cascade(fitted.model, F[rows_from].astype(np.float64))
    np.testing.assert_array_equal([r["decision"] for r in out], ev["decisions"])
    np.testing.assert_array_equal(
        [r["models_evaluated"] for r in out], ev["exit_step"]
    )


@pytest.mark.parametrize("n", [1, 2, CAP - 1, CAP])
def test_device_permutation_matches_host_sort(n):
    """The key program's rows buffer is the host's stable argsort of the
    stage-0 keys, ``cap`` past ``n``; served verdicts and exit steps
    equal the host oracle's at every partial flush size."""
    fitted, scorer, x, F = _tree_cascade()
    srv = fitted.compile("device", scorer=scorer, block_n=32).serve(batch_size=CAP)
    assert srv.flush_size == CAP
    _check(fitted, F, slice(0, n), _serve(srv, x[:n]))

    executor, bound, _, key_fn = srv._device_state()
    batch = bound.prepare(pad_rows(x[:n], executor._cap(CAP)))
    rows = key_fn(batch, n)
    assert isinstance(rows, jax.Array) and rows.shape == (CAP,)
    rows = np.asarray(rows)
    col0 = F[:n, fitted.model.order[0]]
    assert len(np.unique(col0)) < n or n < 3  # the planted ties
    np.testing.assert_array_equal(rows[:n], np.argsort(col0, kind="stable"))
    np.testing.assert_array_equal(rows[n:], CAP)


def test_server_builds_no_program_after_first_flush():
    """Once the first flush has built the key and stage programs, flushes
    of any other size lower nothing: padding to capacity is host work."""
    fitted, scorer, x, F = _tree_cascade()
    srv = fitted.compile("device", scorer=scorer, block_n=32).serve(batch_size=CAP)
    _check(fitted, F, slice(0, CAP), _serve(srv, x[:CAP]))
    before = _lowered[0]
    start = CAP
    for n in (1, 2, CAP - 1, 5, 37, CAP, 3):
        _check(fitted, F, slice(start, start + n), _serve(srv, x[start : start + n]))
        start = (start + n) % (x.shape[0] - CAP)
    assert _lowered[0] == before
    assert srv.stats.device_reads == srv.stats.n_batches == 8


@pytest.mark.parametrize(
    "backend, per_flush",
    [
        ("device", 1),
        pytest.param(
            "sharded",
            2,
            marks=[
                pytest.mark.multidevice,
                pytest.mark.skipif(
                    N_DEV < 4,
                    reason="needs 4 devices (XLA_FLAGS="
                    "--xla_force_host_platform_device_count=4)",
                ),
            ],
        ),
    ],
)
def test_device_reads_per_flush(backend, per_flush):
    """One blocking read a flush on one device; the sharded executor
    deals rows over its shards on the host, so it reads the device
    permutation once more."""
    fitted, scorer, x, F = _tree_cascade()
    if backend == "sharded":
        compiled = fitted.compile("sharded", shards=4, scorer=scorer, block_n=32)
        srv = compiled.serve(batch_size=CAP // 4)
    else:
        compiled = fitted.compile("device", scorer=scorer, block_n=32)
        srv = compiled.serve(batch_size=CAP)
    n = 2 * CAP + 7  # two full flushes, then a partial one
    _check(fitted, F, slice(0, n), _serve(srv, x[:n]))
    assert srv.stats.n_batches == 3
    assert srv.stats.device_reads == per_flush * srv.stats.n_batches


def test_host_path_makes_no_device_reads():
    """The host rung never reads a device result."""
    fitted, _, x, F = _tree_cascade()
    rows_of = {row.tobytes(): i for i, row in enumerate(x)}

    def score_fn(xb):  # the rows' precomputed scores, ORIGINAL order
        return F[[rows_of[row.tobytes()] for row in xb]].astype(np.float64)

    srv = fitted.compile("host").serve(batch_size=CAP, score_fn=score_fn)
    _check(fitted, F, slice(0, 70), _serve(srv, x[:70]))
    assert srv.stats.n_batches == 2
    assert srv.stats.device_reads == 0
