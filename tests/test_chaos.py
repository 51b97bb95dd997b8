"""Chaos suite: fault injection, NaN confinement, the degradation
ladder, quarantine, and the drift watchdog (DESIGN.md §10).

Every scenario is driven deterministically from a ``FaultPlan`` seed
(``repro.testing.faults``), so a failure reproduces bit-for-bit.  The
multi-shard cases need forged XLA devices, as the CI chaos job provides:
``XLA_FLAGS=--xla_force_host_platform_device_count=4``.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from conftest import make_scores  # noqa: E402
from repro import api  # noqa: E402
from repro.api.backends import (  # noqa: E402
    BackoffPolicy,
    DegradationLadder,
    fallback_rung,
)
from repro.kernels.cascade_kernel import (  # noqa: E402
    cascade_chunk_pallas,
    cascade_lane_pallas,
)
from repro.kernels.device_executor import (  # noqa: E402
    DeviceProgramError,
    DevicePlan,
    WaveFailure,
    matrix_stage_scorer,
)
from repro.serving import (  # noqa: E402
    DriftWatchdog,
    QWYCServer,
    WatchdogConfig,
)
from repro.serving.watchdog import widen_plan  # noqa: E402
from repro.testing import FaultInjected, FaultPlan, faults  # noqa: E402

N_DEV = len(jax.devices())
NO_SLEEP = {"backoff": BackoffPolicy(retries=2), "sleep": lambda s: None}


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


def _setup(seed=40, n=300, t=20, mode="both", alpha=0.01):
    rng = np.random.default_rng(seed)
    F = make_scores(rng, n=n, t=t)
    fitted = api.fit(F, beta=0.0, alpha=alpha, mode=mode, chunk_t=4)
    return F, fitted


def _linear_world(seed=11, n_cal=400, d=6, t=12, alpha=0.02):
    """A servable world: raw features + a batched score_fn, so servers
    can take feature vectors (the quarantine guard's input type)."""
    rng = np.random.default_rng(seed)
    Xc = rng.normal(size=(n_cal, d)).astype(np.float32)
    W = rng.normal(size=(d, t))
    z = rng.normal(size=(1, t)) * 0.1

    def score_fn(X):
        return np.asarray(X, dtype=np.float64) @ W / np.sqrt(d) + z

    m = api.fit(score_fn, Xc, alpha=alpha, chunk_t=4).model
    return rng, Xc, score_fn, m


# ------------------------------------------------------------ fault plans


def test_fault_plan_poison_is_deterministic_and_nonempty():
    X = np.random.default_rng(0).normal(size=(200, 5))
    p1, m1 = FaultPlan(seed=9, poison_fraction=0.05).poison(X)
    p2, m2 = FaultPlan(seed=9, poison_fraction=0.05).poison(X)
    assert (m1 == m2).all()
    np.testing.assert_array_equal(np.isnan(p1), np.isnan(p2))
    assert m1.sum() == 10
    assert not np.isfinite(p1[m1]).all(axis=1).any()  # every marked row hit
    np.testing.assert_array_equal(p1[~m1], X[~m1])  # clean rows untouched
    # a fraction that rounds to zero rows still poisons one (else the
    # scenario silently tests nothing)
    _, m3 = FaultPlan(seed=9, poison_fraction=1e-6).poison(X)
    assert m3.sum() == 1


def test_fault_plan_arming_and_nesting():
    assert faults.active() is None
    with FaultPlan(seed=1) as fp:
        assert faults.active() is fp
        with pytest.raises(RuntimeError, match="already armed"):
            FaultPlan(seed=2).__enter__()
    assert faults.active() is None


def test_fault_plan_make_executor_window():
    plan = FaultPlan(seed=3, fail_backend="device", fail_on_call=2, fail_calls=1)
    with plan:
        faults.on_make_executor("device")  # 1: clean
        with pytest.raises(FaultInjected):
            faults.on_make_executor("device")  # 2: faults
        faults.on_make_executor("device")  # 3: window closed
        faults.on_make_executor("sharded")  # other names unaffected
    assert plan.injected["make_executor"] == 1


# ------------------------------------------------- NaN decide confinement


def _chunk_inputs(seed=0, m=64, ct=4):
    rng = np.random.default_rng(seed)
    g0 = rng.normal(size=m).astype(np.float32)
    scores = rng.normal(size=(m, ct)).astype(np.float32)
    eps_pos = np.full(ct, 1.2, np.float32)
    eps_neg = np.full(ct, -1.2, np.float32)
    return g0, scores, eps_pos, eps_neg


@pytest.mark.parametrize("poison", [np.nan, np.inf, -np.inf])
def test_chunk_decide_poison_never_flips_clean_lanes(poison):
    g0, scores, eps_pos, eps_neg = _chunk_inputs()
    clean = cascade_chunk_pallas(
        jnp.asarray(g0), jnp.asarray(scores), jnp.asarray(eps_pos),
        jnp.asarray(eps_neg), t0=0, block_n=16, interpret=True,
    )
    bad = scores.copy()
    rows = np.array([3, 17, 40, 63])
    bad[rows, 0] = poison  # poison the FIRST step so every marked lane
    # consumes it before any exit opportunity
    dirty = cascade_chunk_pallas(
        jnp.asarray(g0), jnp.asarray(bad), jnp.asarray(eps_pos),
        jnp.asarray(eps_neg), t0=0, block_n=16, interpret=True,
    )
    keep = np.setdiff1d(np.arange(len(g0)), rows)
    for c, d in zip(clean, dirty):  # g, active, dec, exit_step
        np.testing.assert_array_equal(np.asarray(c)[keep], np.asarray(d)[keep])
    if np.isnan(poison):
        # NaN cannot cross the decide: the lane never exits, never
        # reports positive
        g, active, dec, ex = (np.asarray(a)[rows] for a in dirty)
        assert (dec == 0).all()
        assert (ex == 0).all() and (active == 1).all()
        assert np.isnan(g).all()


@pytest.mark.parametrize("poison", [np.nan, np.inf, -np.inf])
def test_lane_decide_poison_never_flips_clean_lanes(poison):
    g0, scores, eps_pos, eps_neg = _chunk_inputs(seed=1)
    m, ct = scores.shape
    eps_pos2 = np.tile(eps_pos, (m, 1))
    eps_neg2 = np.tile(eps_neg, (m, 1))
    clean = cascade_lane_pallas(
        jnp.asarray(g0), jnp.asarray(scores), jnp.asarray(eps_pos2),
        jnp.asarray(eps_neg2), block_n=16, interpret=True,
    )
    bad = scores.copy()
    rows = np.array([0, 21, 42])
    bad[rows, 0] = poison
    dirty = cascade_lane_pallas(
        jnp.asarray(g0), jnp.asarray(bad), jnp.asarray(eps_pos2),
        jnp.asarray(eps_neg2), block_n=16, interpret=True,
    )
    keep = np.setdiff1d(np.arange(m), rows)
    for c, d in zip(clean, dirty):
        np.testing.assert_array_equal(np.asarray(c)[keep], np.asarray(d)[keep])
    if np.isnan(poison):
        g, active, dec, ex = (np.asarray(a)[rows] for a in dirty)
        assert (dec == 0).all() and (ex == 0).all()


@pytest.mark.parametrize("shards", _shards_params())
@pytest.mark.parametrize("megakernel", [False, True])
def test_executor_nan_confined_to_poisoned_rows(shards, megakernel):
    """All three decide paths end-to-end (chunk/lane via the multi-kernel
    executor, the megakernel decide via megakernel=True): poisoned rows
    never exit and decide False; every clean row's verdict, exit step and
    final score are bit-identical to the unpoisoned run."""
    F, fitted = _setup(seed=44, n=192, t=16)
    T = fitted.T
    dplan = DevicePlan.from_plan(fitted.plan())
    scorer = matrix_stage_scorer(dplan)
    b = api.get_backend("sharded")
    ex = b.make_executor(
        dplan, scorer=scorer, shards=shards, interpret=True,
        megakernel=megakernel, block_n=16,
    )
    ordered = F[:, fitted.model.order].astype(np.float32)
    res = ex.run(ordered, ordered.shape[0])

    bad = ordered.copy()
    rows = np.random.default_rng(5).choice(len(bad), size=6, replace=False)
    bad[rows, 0] = np.nan
    res2 = ex.run(bad, bad.shape[0])
    keep = np.setdiff1d(np.arange(len(bad)), rows)
    np.testing.assert_array_equal(res.decisions[keep], res2.decisions[keep])
    np.testing.assert_array_equal(res.exit_step[keep], res2.exit_step[keep])
    np.testing.assert_array_equal(res.g_final[keep], res2.g_final[keep])
    # NaN lanes run the whole cascade and decide False — NaN never
    # crosses a threshold comparison in any decide implementation
    assert (~res2.decisions[rows]).all()
    assert (res2.exit_step[rows] == T).all()
    assert np.isnan(res2.g_final[rows]).all()


def test_executor_check_finite_guard_names_rows():
    F, fitted = _setup(seed=45, n=96, t=12)
    dplan = DevicePlan.from_plan(fitted.plan())
    ex = api.get_backend("device").make_executor(
        dplan, scorer=matrix_stage_scorer(dplan), interpret=True,
        check_finite=True,
    )
    ordered = F[:, fitted.model.order].astype(np.float32)
    bad = ordered.copy()
    bad[7, 3] = np.inf
    with pytest.raises(ValueError, match=r"rows \[7\]"):
        ex.run(bad, bad.shape[0])
    ex.run(ordered, ordered.shape[0])  # clean batch passes


# ------------------------------------------------------ degradation ladder


def test_backoff_policy_delays_capped():
    p = BackoffPolicy(retries=4, base_delay=0.1, factor=3.0, max_delay=0.5)
    np.testing.assert_allclose(p.delays(), (0.1, 0.3, 0.5, 0.5))
    assert BackoffPolicy(retries=0).delays() == ()


def test_ladder_attempt_retries_then_records_recovery():
    sleeps = []
    ladder = DegradationLadder(
        backoff=BackoffPolicy(retries=2, base_delay=0.05), sleep=sleeps.append
    )
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise WaveFailure("transient")
        return "ok"

    assert ladder.attempt("wave", "device", flaky) == "ok"
    assert sleeps == [0.05, 0.1]
    (ev,) = ladder.events
    assert (ev.kind, ev.from_backend, ev.to_backend, ev.retries) == (
        "wave", "device", "device", 2,
    )


def test_ladder_attempt_exhausts_then_caller_falls():
    ladder = DegradationLadder(
        backoff=BackoffPolicy(retries=1), sleep=lambda s: None
    )

    def dead():
        raise WaveFailure("permanent")

    with pytest.raises(WaveFailure):
        ladder.attempt("wave", "sharded", dead)
    nxt = ladder.fall("wave", "device", WaveFailure("x"))
    assert nxt.name == "host"
    with pytest.raises(WaveFailure, match="floor"):
        ladder.fall("wave", "host", WaveFailure("floor"))


def test_ladder_does_not_retry_caller_bugs():
    ladder = DegradationLadder(sleep=lambda s: None)
    calls = {"n": 0}

    def bug():
        calls["n"] += 1
        raise TypeError("bad argument")

    with pytest.raises(TypeError):
        ladder.attempt("wave", "device", bug)
    assert calls["n"] == 1 and ladder.events == []


def test_fallback_rung_skips_unavailable():
    with FaultPlan(seed=0, drop_device=True):
        b = fallback_rung("__start__")  # full scan: sharded reported down
        assert b is not None and b.name in ("device", "host")


def test_compile_construction_fault_falls_to_host():
    F, fitted = _setup()
    sleeps = []
    with FaultPlan(seed=3, fail_backend="device") as fp:
        c = fitted.compile("device", interpret=True, sleep=sleeps.append)
    assert c.backend_name == "host"
    assert fp.injected["make_executor"] == 3  # 1 try + 2 retries
    kinds = {(e.kind, e.from_backend, e.to_backend) for e in c.degradation_events}
    assert ("construct", "device", "host") in kinds
    assert sleeps == [0.05, 0.1]
    # degraded rung still computes the exact cascade
    oracle = fitted.compile("host").evaluate(scores=F)
    got = c.evaluate(scores=F)
    np.testing.assert_array_equal(got.decisions, oracle.decisions)
    np.testing.assert_array_equal(got.exit_step, oracle.exit_step)


def test_evaluate_wave_fault_recovers_same_rung():
    F, fitted = _setup()
    c = fitted.compile("device", interpret=True, sleep=lambda s: None)
    oracle = fitted.compile("host").evaluate(scores=F)
    with FaultPlan(seed=4, wave_failures=1) as fp:
        res = c.evaluate(scores=F)
    assert c.backend_name == "device"  # recovered WITHOUT falling
    assert fp.injected["waves"] == 1
    np.testing.assert_array_equal(res.decisions, oracle.decisions)
    (ev,) = c.degradation_events
    assert (ev.kind, ev.to_backend, ev.retries) == ("wave", "device", 1)


def test_evaluate_wave_fault_falls_to_host_with_identical_verdicts():
    F, fitted = _setup()
    c = fitted.compile("device", interpret=True, sleep=lambda s: None)
    oracle = fitted.compile("host").evaluate(scores=F)
    with FaultPlan(seed=5, wave_failures=10_000):
        res = c.evaluate(scores=F)
    assert c.backend_name == "host"
    np.testing.assert_array_equal(res.decisions, oracle.decisions)
    np.testing.assert_array_equal(res.exit_step, oracle.exit_step)
    # once healthy again the cascade stays on the rung it landed on
    res2 = c.evaluate(scores=F)
    np.testing.assert_array_equal(res2.decisions, oracle.decisions)


# ------------------------------------------------- server: device loss


@pytest.mark.parametrize("shards", _shards_params((2,)))
def test_server_device_loss_degrades_ladder_with_identical_verdicts(shards):
    """The issue's device-loss scenario: a sharded server loses a mesh
    device mid-serving; the ladder retries, then falls sharded -> device,
    and every verdict matches the host oracle bit-for-bit."""
    rng, Xc, score_fn, m = _linear_world(seed=21)
    Xt = rng.normal(size=(96, Xc.shape[1])).astype(np.float32)

    oracle = QWYCServer(m, score_fn=score_fn, batch_size=16, backend="kernel")
    for x in Xt:
        oracle.submit(x)
    want = oracle.drain()

    srv = QWYCServer(
        m, score_fn=score_fn, batch_size=8, backend="kernel",
        exec_backend="sharded", backend_opts={"shards": shards},
        **NO_SLEEP,
    )
    with FaultPlan(
        seed=7, drop_device=True, wave_failures=10_000,
        wave_fail_backend="sharded",
    ):
        for x in Xt:
            srv.submit(x)
        got = srv.drain()

    assert srv.exec.name == "device"  # fell exactly one rung
    falls = [
        e for e in srv.stats.degradation_events
        if e.from_backend != e.to_backend
    ]
    assert [(e.from_backend, e.to_backend) for e in falls] == [
        ("sharded", "device")
    ]
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g["decision"] == w["decision"]
        assert g["models_evaluated"] == w["models_evaluated"]


def test_server_wave_fault_falls_to_host_floor():
    rng, Xc, score_fn, m = _linear_world(seed=22)
    Xt = rng.normal(size=(64, Xc.shape[1])).astype(np.float32)
    oracle = QWYCServer(m, score_fn=score_fn, batch_size=16, backend="kernel")
    srv = QWYCServer(
        m, score_fn=score_fn, batch_size=16, backend="kernel",
        exec_backend="device", **NO_SLEEP,
    )
    with FaultPlan(seed=8, wave_failures=10_000, wave_fail_backend="device"):
        for x in Xt:
            oracle.submit(x)
            srv.submit(x)
        want = oracle.drain()
        got = srv.drain()
    assert srv.exec.name == "host"
    assert not srv.device
    for g, w in zip(got, want):
        assert g["decision"] == w["decision"]
        assert g["models_evaluated"] == w["models_evaluated"]


@pytest.mark.parametrize("policy", ["kernel", "sorted-kernel"])
@pytest.mark.parametrize("error", [RuntimeError, NotImplementedError, Exception])
def test_refused_device_program_raises_without_fallback(policy, error):
    """A device program the compiler refuses is a bug, not a runtime
    fault: serve() and evaluate() raise before any wave launches, record
    no DegradationEvent, and no host rung answers in the device's place
    (the runtime wave faults above still take the ladder)."""
    rng, Xc, score_fn, m = _linear_world(seed=23)
    Xt = rng.normal(size=(32, Xc.shape[1])).astype(np.float32)

    def refused(dplan):
        def fn(x, rows, t0, n_valid):
            raise error("compiler refused the stage program")

        return dataclasses.replace(
            matrix_stage_scorer(dplan), fn=fn, lane_fn=None, slabs=None,
            prepare=lambda x: jnp.asarray(x, dtype=jnp.float32),
        )

    compiled = api.fit(score_fn, Xc, alpha=0.02, chunk_t=4).compile(
        "device", scorer=api.FunctionScorer(refused), **NO_SLEEP
    )
    srv = compiled.serve(batch_size=16, policy=policy)
    with pytest.raises(DeviceProgramError, match="refused"):
        for x in Xt:
            srv.submit(x)
        srv.drain()
    assert srv.stats.degradation_events == []
    assert srv.exec.name == "device" and srv.stats.n_requests == 0
    with pytest.raises(DeviceProgramError, match="refused"):
        compiled.evaluate(x=Xt)
    assert compiled.degradation_events == []
    assert compiled.backend_name == "device"


# ------------------------------------------------- server: quarantine


def test_server_quarantines_poisoned_rows_clean_rows_unchanged():
    """1%-poisoned traffic: every poisoned row quarantined with an
    explicit verdict, every clean row's decision AND per-row billing
    (models_evaluated) unchanged vs the unpoisoned run."""
    rng, Xc, score_fn, m = _linear_world(seed=23)
    Xt = rng.normal(size=(200, Xc.shape[1])).astype(np.float32)

    ref = QWYCServer(m, score_fn=score_fn, batch_size=32, backend="kernel")
    for x in Xt:
        ref.submit(x)
    want = ref.drain()

    fp = FaultPlan(seed=31, poison_fraction=0.01, poison_mode="mix")
    Xp, mask = fp.poison(Xt)
    srv = QWYCServer(m, score_fn=score_fn, batch_size=32, backend="kernel")
    for x in Xp:
        srv.submit(x)
    got = srv.drain()

    assert srv.stats.quarantined == int(mask.sum()) == 2
    assert len(got) == len(want)  # quarantined rows still answered
    for i in range(len(Xt)):
        if mask[i]:
            assert got[i]["quarantined"] and got[i]["decision"] is None
            assert got[i]["models_evaluated"] == 0
        else:
            assert "quarantined" not in got[i]
            assert got[i]["decision"] == want[i]["decision"]
            assert got[i]["models_evaluated"] == want[i]["models_evaluated"]
    # quarantined rows are not billed as served requests
    assert srv.stats.n_requests == len(Xt) - int(mask.sum())


def test_server_quarantine_shape_and_dtype_guard():
    rng, Xc, score_fn, m = _linear_world(seed=24)
    srv = QWYCServer(m, score_fn=score_fn, batch_size=8, backend="kernel")
    d = Xc.shape[1]
    srv.submit(np.zeros(d, np.float32))  # locks the request shape
    srv.submit(np.zeros(d + 1, np.float32))  # wrong shape -> quarantined
    srv.submit("not a vector")  # unconvertible -> quarantined
    out = srv.drain()
    assert [r.get("quarantined", False) for r in out] == [False, True, True]
    assert "shape" in out[1]["reason"]
    assert "float32" in out[2]["reason"]
    assert srv.stats.quarantined == 2


def test_server_quarantine_off_keeps_legacy_behavior():
    rng, Xc, score_fn, m = _linear_world(seed=25)
    srv = QWYCServer(
        m, score_fn=score_fn, batch_size=8, backend="kernel", quarantine=False
    )
    with pytest.raises(ValueError):
        srv.submit("not a vector")


# ------------------------------------------------------------- watchdog


def test_watchdog_unit_alarms_on_drift_not_on_clean():
    cfg = WatchdogConfig(p0=0.01, alarm=4.0)
    p0, p1 = cfg.rates()
    assert p0 == 0.01 and p1 == pytest.approx(0.06)

    clean = DriftWatchdog(cfg)
    rng = np.random.default_rng(6)
    for _ in range(200):
        clean.observe(64, int(rng.binomial(64, p0)))
    assert clean.state == "ok" and clean.alarms == 0

    drifted = DriftWatchdog(cfg)
    fired_at = None
    for i in range(200):
        drifted.observe(64, int(rng.binomial(64, 0.15)))
        if drifted.alarms and fired_at is None:
            fired_at = i + 1
    assert drifted.state != "ok" and drifted.alarms >= 1
    assert fired_at is not None and fired_at <= 5  # detection is fast
    assert drifted.margin == np.inf  # default schedule: full cascade

    # recovery: zero-diff flushes (what a full-cascade policy produces)
    # decay the statistic and re-arm the calibrated thresholds
    steps = 0
    while drifted.state != "ok":
        drifted.observe(64, 0)
        steps += 1
        assert steps < 50
    assert drifted.margin == 0.0
    assert drifted.recovery_step == drifted.flushes


def test_watchdog_margin_schedule_escalates():
    wd = DriftWatchdog(
        WatchdogConfig(p0=0.01, alarm=1.0, margin_schedule=(0.5, 1.0, np.inf))
    )
    wd.observe(64, 30)  # way past alarm
    assert wd.state == "alarmed" and wd.margin == 0.5
    wd.observe(64, 30)
    assert wd.margin == 1.0
    wd.observe(64, 30)
    assert wd.margin == np.inf  # last margin repeats from here on
    wd.observe(64, 30)
    assert wd.margin == np.inf


def test_widen_plan_margins():
    _, fitted = _setup()
    plan = fitted.plan()
    wide = widen_plan(plan, 0.7)
    np.testing.assert_allclose(wide.eps_pos, plan.eps_pos + 0.7)
    np.testing.assert_allclose(wide.eps_neg, plan.eps_neg - 0.7)
    full = widen_plan(plan, np.inf)
    assert (full.eps_pos == np.inf).all() and (full.eps_neg == -np.inf).all()
    assert widen_plan(plan, 0.0) is plan


def _drift_pool(m, score_fn, Xpool):
    """Rows where the calibrated cascade disagrees with the full ensemble
    — traffic concentrated there IS distribution drift for the watchdog's
    statistic."""
    F = np.asarray(score_fn(Xpool))
    srv = QWYCServer(m, score_fn=score_fn, batch_size=64, backend="kernel")
    for x in Xpool:
        srv.submit(x)
    out = srv.drain()
    dec = np.array([r["decision"] for r in out])
    full = F.sum(axis=1) >= m.beta
    return Xpool[dec != full], Xpool[dec == full]


def test_server_watchdog_alarm_degrades_decide_then_recovers():
    rng, Xc, score_fn, m = _linear_world(seed=26, alpha=0.05)
    pool = rng.normal(size=(600, Xc.shape[1])).astype(np.float32)
    drift, clean = _drift_pool(m, score_fn, pool)
    assert len(drift) >= 8, "world must produce some disagreeing rows"

    srv = QWYCServer(
        m, score_fn=score_fn, batch_size=16, backend="kernel", watchdog=True
    )
    T = m.T
    # phase 1: one flush of drifted traffic -> alarm (16 disagreements
    # in 16 rows crosses alarm=4 in a single step)
    drift_batch = np.tile(drift, (max(1, 16 // len(drift)) + 1, 1))[:16]
    for x in drift_batch:
        srv.submit(x)
    srv.flush()
    assert srv.stats.watchdog_alarms == 1
    assert srv.stats.watchdog_state == "alarmed"
    assert srv.stats.watchdog_margin == np.inf

    # phase 2: the degraded decide policy forces the full cascade — every
    # row's verdict now IS the full-ensemble verdict (alarm containment)
    n0 = srv.stats.n_requests
    for x in clean[:16]:
        srv.submit(x)
    srv.flush()
    out = srv.drain()
    degraded = out[n0:]
    assert all(r["models_evaluated"] == T for r in degraded)

    # phase 3: clean traffic under the degraded policy produces zero
    # diffs, the statistic decays, and the watchdog re-arms
    steps = 0
    while srv.stats.watchdog_state != "ok":
        for x in clean[:16]:
            srv.submit(x)
        srv.flush()
        steps += 1
        assert steps < 40
    assert srv.stats.watchdog_margin == 0.0
    assert srv.stats.watchdog_recovery_step is not None
    # and the calibrated thresholds are back: early exits resume
    for x in clean[16:32]:
        srv.submit(x)
    srv.flush()
    out = srv.drain()
    assert any(r["models_evaluated"] < T for r in out)


def test_watchdog_requires_audit_stream():
    _, Xc, score_fn, m = _linear_world(seed=27)
    with pytest.raises(ValueError, match="audit"):
        QWYCServer(
            m, score_fn=None, chunk_score_fn=lambda *a: None,
            audit_full_scores=False, batch_size=8, backend="kernel",
            watchdog=True,
        )


# ------------------------------------------------------------- streaming


@pytest.mark.parametrize("shards", _shards_params((2,)))
def test_streaming_device_loss_falls_to_device_rung(shards):
    from repro.serving import StreamingServer

    rng, Xc, score_fn, m = _linear_world(seed=28)
    Xt = rng.normal(size=(64, Xc.shape[1])).astype(np.float32)

    oracle = QWYCServer(m, score_fn=score_fn, batch_size=64, backend="kernel")
    for x in Xt:
        oracle.submit(x)
    want = oracle.drain()

    srv = StreamingServer(
        m, score_fn=score_fn, batch_size=8, window=32,
        exec_backend="sharded", backend_opts={"shards": shards},
        **NO_SLEEP,
    )
    with FaultPlan(
        seed=9, drop_device=True, wave_failures=10_000,
        wave_fail_backend="sharded",
    ):
        for i, x in enumerate(Xt):
            srv.submit(x, arrival=float(i))
        got = srv.drain()
    assert srv.exec.name == "device"
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g["decision"] == w["decision"]


def test_streaming_quarantine_preserves_submission_order():
    from repro.serving import StreamingServer

    rng, Xc, score_fn, m = _linear_world(seed=29)
    Xt = rng.normal(size=(48, Xc.shape[1])).astype(np.float32)
    fp = FaultPlan(seed=41, poison_fraction=0.1)
    Xp, mask = fp.poison(Xt)
    srv = StreamingServer(
        m, score_fn=score_fn, batch_size=8, window=16, exec_backend="device"
    )
    for i, x in enumerate(Xp):
        srv.submit(x, arrival=float(i))
    got = srv.drain()
    assert len(got) == len(Xt)
    assert srv.stats.quarantined == int(mask.sum())
    for i in range(len(Xt)):
        assert got[i].get("quarantined", False) == bool(mask[i])


# ----------------------------------------------------- launcher signals


def test_serve_cli_sigterm_drains_and_prints_stats(monkeypatch, capsys):
    """The launcher's SIGINT/SIGTERM handler stops admission, drains the
    queue (partial final flush) and still prints the final ServeStats."""
    import signal
    import sys

    from repro.launch import serve
    from repro.serving.engine import QWYCServer as Srv

    calls = {"n": 0}
    orig_submit = Srv.submit

    def submit_then_sigterm(self, x):
        calls["n"] += 1
        if calls["n"] == 5:
            signal.raise_signal(signal.SIGTERM)
        return orig_submit(self, x)

    monkeypatch.setattr(Srv, "submit", submit_then_sigterm)
    monkeypatch.setattr(
        sys, "argv",
        ["serve", "--dataset", "adult", "--T", "8", "--scale", "0.05",
         "--backend", "host", "--eager", "--batch-size", "16"],
    )
    prev = signal.getsignal(signal.SIGTERM)
    serve.main()
    # the launcher restored the previous handler on its way out
    assert signal.getsignal(signal.SIGTERM) is prev
    out = capsys.readouterr().out
    assert "caught SIGTERM after 5 submit(s)" in out
    assert "requests in" in out  # the final ServeStats block printed
