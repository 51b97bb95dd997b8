"""One run of one cell: set-up, the measured window, the check, the metrics.

Everything a cell is made of is found by name: the configuration file
named in ``BENCHMARK.json``, the mix ``bench/traffic/<traffic>.json``, the
ensemble module ``bench/ensembles/<ensemble>.py`` and one reader
``bench/metrics/<metric>.py`` per metric.  From the program the harness
takes the served path alone: ``api.fit`` once per checkout, then
``FittedCascade.compile(backend, scorer=...).serve(batch_size=...)`` and
the server's ``submit`` / ``flush`` / ``drain``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import hashlib
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np

import devtrace
import reference
import traffic
import world as worldgen
from ensembles import load as load_ensemble

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = BENCH / ".cache"


def read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    config_bytes: bytes
    mix: dict
    chips: int
    metrics: dict  # "end_to_end" / "per_layer" -> [metric entries for this cell]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has {sorted(cells)}")
    w = cells[name]
    conf = next(c for c in spec["configs"] if c["name"] == w["config"])
    raw = (root / conf["file"]).read_bytes()

    def here(m):
        return "workloads" not in m or name in m["workloads"]

    return Cell(
        name=name,
        config=json.loads(raw),
        config_bytes=raw,
        mix=read_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        chips=int(w["chips"]),
        metrics={k: [m for m in spec[k] if here(m)] for k in ("end_to_end", "per_layer")},
    )


def setup_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    or where ``JAX_COMPILATION_CACHE_DIR`` says; every program is kept."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE / "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# --------------------------------------------------------------- set-up


def _world(ens, cfg: dict):
    """The rows the cell trains, calibrates and serves on: the kind's own
    ``world(cfg)`` where its module has one, else the synthetic world."""
    if hasattr(ens, "world"):
        return ens.world(cfg)
    return worldgen.make_world(cfg["world"], int(cfg["train_rows"]), int(cfg["pool_rows"]))


def fit_settings(ens, cfg: dict, T: int) -> dict:
    """Keyword arguments for ``api.fit`` beyond beta, alpha and mode: the
    kind's ``fit_settings(cfg, T)`` (order, costs), else none."""
    return dict(ens.fit_settings(cfg, T)) if hasattr(ens, "fit_settings") else {}


DEFAULT_BAND = (
    reference.REL_TOL,
    0.01,
    "float32 program against a float32 reference: a row within 1e-5 x sum|f| of a "
    "threshold is not compared",
)


def rounding_band(ens, cfg: dict) -> tuple[float, float, str]:
    """(rel_tol, max_ambiguous_share, why): the band around a threshold
    within which a row is not compared, and the share of answered rows
    that may fall in it; the kind's ``rounding_band(cfg)``, else
    ``DEFAULT_BAND``."""
    if hasattr(ens, "rounding_band"):
        rel_tol, share, why = ens.rounding_band(cfg)
        return float(rel_tol), float(share), str(why)
    return DEFAULT_BAND


def artifact_path(cell: Cell) -> Path:
    """Where the fitted cascade is kept, keyed by the configuration file's
    content."""
    key = hashlib.sha256(cell.config_bytes).hexdigest()[:16]
    return CACHE / "fitted" / f"{cell.config['name']}-{key}.npz"


def fitted_artifact(cell: Cell, ens) -> tuple[dict, dict, bool]:
    """(params, plan, built): the trained ensemble and its fitted plan,
    built on a checkout's first run and loaded after."""
    cfg = cell.config
    path = artifact_path(cell)
    if path.exists():
        z = np.load(path)
        params = {k[6:]: z[k] for k in z.files if k.startswith("param.")}
        plan = {k: z[k] for k in z.files if not k.startswith("param.")}
        return params, plan, False
    from repro import api

    w = _world(ens, cfg)
    params, beta = ens.train(cfg, w)
    F = ens.scores(params, w.x_train)
    model = api.fit(
        F, beta=beta, alpha=float(cfg["alpha"]), mode=cfg["mode"],
        **fit_settings(ens, cfg, F.shape[1]),
    ).model
    plan = {
        "order": np.asarray(model.order), "eps_pos": np.asarray(model.eps_pos),
        "eps_neg": np.asarray(model.eps_neg), "costs": np.asarray(model.costs),
        "beta": np.float64(model.beta), "alpha": np.float64(model.alpha),
        "mode": np.str_(model.mode), "train_mean_models": np.float64(model.train_mean_models),
        "train_diff_rate": np.float64(model.train_diff_rate),
    }
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(".partial.npz")
    np.savez(tmp, **plan, **{f"param.{k}": v for k, v in params.items()})
    os.replace(tmp, path)
    return params, plan, True


@dataclasses.dataclass
class Session:
    cell: Cell
    ens: object
    params: dict
    plan: dict
    world: object
    built: bool
    compiled: object = None
    srv: object = None


def open_session(cell: Cell) -> Session:
    """Load (or build) the fitted cascade, compile it and start serving."""
    from repro import api
    from repro.core.qwyc import QWYCModel

    cfg = cell.config
    ens = load_ensemble(cfg["ensemble"])
    params, plan, built = fitted_artifact(cell, ens)
    model = QWYCModel(
        order=plan["order"], eps_pos=plan["eps_pos"], eps_neg=plan["eps_neg"],
        beta=float(plan["beta"]), costs=plan["costs"], alpha=float(plan["alpha"]),
        mode=str(plan["mode"]), train_mean_models=float(plan["train_mean_models"]),
        train_diff_rate=float(plan["train_diff_rate"]),
    )
    fitted = api.FittedCascade(
        model=model, config=api.FitConfig(beta=model.beta, alpha=model.alpha, mode=model.mode)
    )
    compiled = fitted.compile(cfg["backend"], scorer=ens.program_scorer(params))
    srv = compiled.serve(batch_size=int(cfg["batch_size"]))
    return Session(cell, ens, params, plan, _world(ens, cfg), built, compiled, srv)


def warm_up(sess: Session) -> None:
    """Serve every batch shape the cell's traffic will use, once."""
    srv, pool, mix = sess.srv, sess.world.pool, sess.cell.mix
    if mix["loop"] == "closed":
        serve_rows(srv, pool[np.arange(2 * srv.flush_size) % pool.shape[0]])
    else:
        warm_every_flush_size(srv, pool)


def serve_rows(srv, rows) -> list:
    for row in rows:
        srv.submit(row)
    srv.flush()
    return srv.drain()


def warm_every_flush_size(srv, pool) -> None:
    """An open loop flushes whatever is queued: 1 to ``flush_size`` rows.

    Serve a full and a one-row flush, then a flush at a size not served
    before.  Where that builds a program, the server builds one per row
    count: the server pads each partial batch to its capacity on the
    device with ``jnp.pad``, whose program is keyed by the batch's shape.
    Build that program for every other size without a wave, check that a
    fresh size now builds nothing, and otherwise serve every size.
    """
    cap = srv.flush_size
    rows = pool[np.arange(cap) % pool.shape[0]]
    serve_rows(srv, rows)
    serve_rows(srv, rows[:1])
    before = programs_built()
    serve_rows(srv, rows[:2])
    if programs_built() == before:
        return
    import jax.numpy as jnp

    for n in range(3, cap):
        jnp.pad(jnp.asarray(np.zeros((n, pool.shape[1]), np.float32)), ((0, cap - n), (0, 0)))
    before = programs_built()
    serve_rows(srv, rows[:3])
    if programs_built() != before:
        for n in range(4, cap):
            serve_rows(srv, rows[:n])


_LOWERED = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_built = [0, False]  # programs lowered so far; listener registered


def programs_built() -> int:
    """Programs JAX has lowered in this process, whether it then compiled
    them or read them from the persistent cache."""
    if not _built[1]:
        import jax

        def count(event, duration, **kw):
            if event == _LOWERED:
                _built[0] += 1

        jax.monitoring.register_event_duration_secs_listener(count)
        _built[1] = True
    return _built[0]


class GcPauses:
    """Pauses of Python's cyclic collector while in use, by generation."""

    def __enter__(self):
        self.pauses = {0: [], 1: [], 2: []}
        gc.callbacks.append(self._cb)
        return self

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses[info["generation"]].append(time.perf_counter() - self._t)

    def __exit__(self, *exc):
        gc.callbacks.remove(self._cb)

    def summary(self) -> str:
        return "; ".join(
            f"gen{g} {len(p)} pauses, max {max(p, default=0.0) * 1e3:.3f} ms"
            for g, p in self.pauses.items()
        )


def executor_problem(sess: Session) -> str | None:
    """Why the executor that served is not the one the cell stands for."""
    dev = getattr(sess.srv, "_dev", None)
    if dev is None:
        return f"no device executor (backend {sess.srv.exec.name!r})"
    ex = dev[0]
    name = type(ex).__name__
    chips = sess.cell.chips
    if chips == 1:
        return None if name == "DeviceExecutor" else f"served by {name}, not DeviceExecutor"
    if name != "ShardedDeviceExecutor":
        return f"served by {name}, not ShardedDeviceExecutor"
    used = {d.id for d in ex.mesh.devices.flat}
    return None if len(used) == chips else f"mesh spans {len(used)} devices, not {chips}"


def degradation_events(sess: Session) -> int:
    return len(sess.srv.stats.degradation_events) + len(sess.compiled.degradation_events)


# --------------------------------------------------------------- window


class Spans:
    """The benchmark's own host spans, written into the profiler's trace."""

    def __init__(self, on: bool):
        self.on = on
        if on:
            import jax

            self._ann = jax.profiler.TraceAnnotation

    def __call__(self, what: str):
        return self._ann("bench." + what) if self.on else contextlib.nullcontext()


def _verdicts(out: list) -> tuple[np.ndarray, np.ndarray]:
    n = len(out)
    dec = np.fromiter((r["decision"] for r in out), bool, n)
    ex = np.fromiter((r["models_evaluated"] for r in out), np.int64, n)
    return dec, ex


def closed_loop(sess: Session, seed: int, seconds: float, spans: Spans) -> dict:
    """One client pushes rows back to back; each full batch flushes inside
    ``submit``, and the client takes its results with ``drain``."""
    srv, pool, st = sess.srv, sess.world.pool, sess.srv.stats
    flush = srv.flush_size
    stream = traffic.RowStream(pool.shape[0], seed)
    idx_l, dec_l, ex_l, flush_max = [], [], [], []
    submit_s = 0.0
    submit_rows = 0
    with spans("window"):
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while True:
            idx = stream.take(flush)
            rows = pool[idx]
            nb = st.n_batches
            a = time.perf_counter()
            with spans("submit"):
                for row in rows[:-1]:
                    srv.submit(row)
            b = time.perf_counter()
            with spans("flush"):
                srv.submit(rows[-1])
            if st.n_batches != nb + 1:
                raise RuntimeError(
                    f"the server flushed {st.n_batches - nb} times for {flush} rows "
                    f"(flush_size {flush})"
                )
            with spans("drain"):
                dec, ex = _verdicts(srv.drain())
            submit_s += b - a
            submit_rows += flush - 1
            idx_l.append(idx)
            dec_l.append(dec)
            ex_l.append(ex)
            flush_max.append(int(ex.max()) if ex.size else 0)
            if time.perf_counter() >= deadline:
                break
        elapsed = time.perf_counter() - t0
    return {
        "elapsed_s": elapsed, "idx": np.concatenate(idx_l),
        "dec": np.concatenate(dec_l), "ex": np.concatenate(ex_l),
        "flush_max_exit": np.asarray(flush_max), "n_flush": len(flush_max),
        "submit_s": submit_s, "submit_rows": submit_rows,
    }


def open_loop(sess: Session, seed: int, seconds: float, spans: Spans) -> dict:
    """Single-row requests due at Poisson arrivals; the client submits
    every row that is due, up to the server's ``flush_size`` (where
    ``submit`` flushes inline), then flushes, and takes the verdicts."""
    srv, pool, mix = sess.srv, sess.world.pool, sess.cell.mix
    due = traffic.arrivals(mix, seed, seconds)
    n = due.size
    idx = traffic.RowStream(pool.shape[0], seed).take(n)
    cap = srv.flush_size
    dec = np.zeros(n, bool)
    ex = np.zeros(n, np.int64)
    answered = np.zeros(n, bool)
    done = np.zeros(n)
    flush_walls, flush_rows, backlog, flush_max = [], [], [], []
    i = 0
    with spans("window"):
        t0 = time.perf_counter()
        while i < n:
            now = time.perf_counter() - t0
            if due[i] > now:
                with spans("wait"):
                    time.sleep(due[i] - now)
                now = time.perf_counter() - t0
            j = i
            with spans("submit"):
                while j < n and j - i < cap and due[j] <= now:
                    srv.submit(pool[idx[j]])
                    j += 1
            a = time.perf_counter()
            with spans("flush"):
                srv.flush()
            b = time.perf_counter()
            with spans("drain"):
                d, e = _verdicts(srv.drain())
            t_done = time.perf_counter() - t0
            k = min(d.size, j - i)
            dec[i : i + k], ex[i : i + k] = d[:k], e[:k]
            answered[i : i + k] = True
            done[i:j] = t_done
            flush_walls.append(b - a)
            flush_rows.append(j - i)
            backlog.append(int(np.searchsorted(due, now, side="right")) - j)
            flush_max.append(int(e.max()) if e.size else 0)
            i = j
        elapsed = time.perf_counter() - t0
    return {
        "elapsed_s": elapsed, "idx": idx, "dec": dec, "ex": ex,
        "answered": answered, "latency_s": done - due,
        "flush_wall_s": np.asarray(flush_walls),
        "flush_rows": np.asarray(flush_rows),
        "backlog": np.asarray(backlog), "flush_max_exit": np.asarray(flush_max),
        "n_flush": len(flush_walls),
    }


DRIVERS = {"closed": closed_loop, "open": open_loop}


# --------------------------------------------------------------- check


def reference_verdicts(sess: Session, lower: bool = False):
    """(decisions, exit steps, ambiguous) of the plain cascade for every
    pool row, with the kind's rounding band; ``lower`` computes the
    control, with the weights rounded to the precision below the
    configuration's."""
    params = sess.ens.lower_precision(sess.params) if lower else sess.params
    plan = sess.plan
    scores = sess.ens.scores(params, sess.world.pool)
    rel_tol, _, _ = rounding_band(sess.ens, sess.cell.config)
    return reference.cascade(
        scores[:, np.asarray(plan["order"])], plan["eps_pos"], plan["eps_neg"],
        float(plan["beta"]), rel_tol=rel_tol,
    )


def check(sess: Session, run: dict) -> dict:
    """The numbers that decide ``correct``, each with its limit, in order.

    ``mismatched_rows``: served verdicts or exit steps that differ from the
    plain cascade's, over every request answered in the window, leaving
    out rows the reference marks ambiguous.  Outside that rounding band
    the comparison is exact, so its limit is 0.  ``ambiguous_share``: the
    rows so left out over the rows answered, against the band's
    ``max_ambiguous_share``, so that a wide band cannot empty the
    comparison.  ``unanswered``: requests with no verdict.
    ``calib_disagreement``: the plan's disagreement with the full ensemble
    on its calibration rows, against the configuration's ``alpha``.
    """
    ens, params, plan, cfg = sess.ens, sess.params, sess.plan, sess.cell.config
    order = np.asarray(plan["order"])
    beta = float(plan["beta"])
    rdec, rex, ramb = reference_verdicts(sess)
    answered = run.get("answered", np.ones(run["dec"].size, bool))
    idx = run["idx"][answered]
    amb = ramb[idx]
    bad = ((run["dec"][answered] != rdec[idx]) | (run["ex"][answered] != rex[idx])) & ~amb
    calib = ens.scores(params, sess.world.x_train)
    cdec, _, _ = reference.cascade(
        calib[:, order], plan["eps_pos"], plan["eps_neg"], beta, rel_tol=0.0, dtype=np.float64
    )
    disagree = float(np.mean(cdec != reference.full_decisions(calib, beta)))
    run["ambiguous_rows"] = int(amb.sum())
    run["mismatched"] = bad
    _, max_share, _ = rounding_band(ens, cfg)
    return {
        "mismatched_rows": {"value": int(bad.sum()), "limit": 0},
        "ambiguous_share": {"value": int(amb.sum()) / max(amb.size, 1), "limit": max_share},
        "unanswered": {"value": int((~answered).sum()), "limit": 0},
        "calib_disagreement": {"value": disagree, "limit": float(cfg["alpha"])},
    }


# --------------------------------------------------------------- metrics


def load_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks_for(kind: str) -> dict:
    table = read_json(BENCH / "peaks.json")
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))


def run_cell(
    cell: Cell,
    seed: int,
    seconds: float,
    trace: bool,
    t_start: float,
    log=print,
) -> dict:
    """Set up, measure one window, check it, and return the result line."""
    import jax

    t_open = time.time()
    sess = open_session(cell)
    t_warm = time.time()
    warm_up(sess)
    phases = (
        f"set-up s: start to harness {t_open - t_start:.3f}, load and compile "
        f"{t_warm - t_open:.3f}, warm-up {time.time() - t_warm:.3f}"
    )
    problem = executor_problem(sess)
    if problem:
        raise RuntimeError(f"{cell.name}: {problem}")
    devices = jax.devices()[: cell.chips]
    spans = Spans(trace)
    tdir = CACHE / "trace" / cell.name
    if trace:
        shutil.rmtree(tdir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tdir), profiler_options=opts)
    setup_s = time.time() - t_start
    built = programs_built()
    with GcPauses() as gcp:
        run = DRIVERS[cell.mix["loop"]](sess, seed, seconds, spans)
    built = programs_built() - built
    if trace:
        jax.profiler.stop_trace()
    mem = memory_peak(devices)
    events = degradation_events(sess)
    problem = executor_problem(sess)
    sess.srv = sess.compiled = None
    gc.collect()

    checks = check(sess, run)
    checks["degradation_events"] = {"value": events, "limit": 0}
    checks["wrong_executor"] = {"value": int(problem is not None), "limit": 0}
    attempted = int(run["idx"].size)
    failed = checks["mismatched_rows"]["value"] + checks["unanswered"]["value"]
    log(
        f"samples: {attempted} requests in {run['elapsed_s']:.6f} s, {run['n_flush']} "
        f"flushes, {run['ambiguous_rows']} rows within rounding of a threshold"
        + (f", built the fitted cascade (first run in this checkout)" if sess.built else "")
    )
    log(phases)
    log("rounding band: rel_tol {}, at most {} of answered rows; {}".format(
        *rounding_band(sess.ens, cell.config)))
    log(f"programs built in the window: {built}; gc in the window: {gcp.summary()}")
    if "latency_s" in run:
        q = np.percentile(run["latency_s"], [50, 90, 95, 99, 99.9, 100]) * 1e3
        log("latency ms p50 p90 p95 p99 p99.9 max: " + " ".join(f"{v:.3f}" for v in q))

    d0 = devices[0]
    ctx = SimpleNamespace(
        cell=cell, cfg=cell.config, mix=cell.mix, chips=cell.chips, ens=sess.ens,
        order=np.asarray(sess.plan["order"]), features=int(sess.world.pool.shape[1]),
        run=run, setup_s=setup_s, summary=None, peak=None,
    )
    device = {"platform": d0.platform, "kind": d0.device_kind, "count": len(devices),
              "memory_peak_bytes": mem}
    result = {}
    if trace:
        ctx.summary = devtrace.reduce(devtrace.load(devtrace.find_xplane(str(tdir))))
        ctx.peak = peaks_for(d0.device_kind)
        shutil.rmtree(tdir, ignore_errors=True)
        device["busy_s"] = ctx.summary.busy_mean_s
        device["window_s"] = ctx.summary.window_s
        log(f"device busy s per chip: {ctx.summary.busy_s} of a {ctx.summary.window_s} s window")
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in ctx.summary.device_ops],
            "idle_gaps": [[n, s] for n, s in ctx.summary.idle_gaps],
        }
    metrics = {}
    for m in cell.metrics["per_layer" if trace else "end_to_end"]:
        value = load_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    out.update(result)
    out["checks"] = checks
    return out


def emit(result: dict) -> None:
    """The compared numbers beside their limits, last on stderr; then the
    result line, last on stdout."""
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
