"""Serve the paper's T=500 cascades on a TPU through the public path.

    python chip_smoke.py                # one chip: GBT-500, then lattice-500
    python chip_smoke.py --four-chips   # four chips: the sharded path only

One process, the user's entry points (``api.fit -> compile -> serve``):

* GBT ``exp1_adult``: 500 oblivious trees of depth 5 on the Adult-shaped
  synthetic (D=14, 8,000 train / 2,000 test rows), fit at alpha=0.01.
* lattice ``exp4_rw2_joint``: 500 jointly trained lattices over S=8 of
  rw2's 30 features, ``neg_only``, fit at alpha=0.01.

Each is compiled onto the ``device`` backend with its lazy stage scorer and
served with a 4,096-row batch: all 2,000 test rows (one partial flush),
one full batch of 4,096 train rows, then the test rows again (so the first
wave's extra wall time is the compile).  Every verdict and exit step must
equal the host ``ChunkedExecutor`` oracle's, computed in the same process
from the same base-model scores with a float32 numpy decide.  The run
fails if a degradation event was recorded or the executor that ran is not
the device one.

``--four-chips`` runs only the sharded path and its comparison: the same
GBT plan and batch on a 4x1 and a 2x2 ("data", "model") mesh, against the
one-chip ``DeviceExecutor`` verdicts.

The script exits non-zero, printing no result line, where JAX finds no
TPU.  Its last line is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path
from typing import Callable

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import api  # noqa: E402
from repro.core.executor import (  # noqa: E402
    CascadePlan,
    ChunkedExecutor,
    decide_chunk_reference,
    matrix_producer,
)
from repro.data.synthetic import make_dataset  # noqa: E402
from repro.ensembles.gbt import train_gbt  # noqa: E402
from repro.ensembles.lattice import (  # noqa: E402
    init_lattice_ensemble,
    train_lattice_ensemble,
)
from repro.kernels import ops  # noqa: E402
from repro.kernels.device_executor import DeviceExecutor  # noqa: E402
from repro.kernels.sharded_executor import ShardedDeviceExecutor  # noqa: E402
from repro.launch.compile_cache import setup_compile_cache  # noqa: E402
from repro.launch.serve import SCORE_BLOCK_N  # noqa: E402

ALPHA = 0.01
BATCH = 4096
SEED = 0


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


@dataclasses.dataclass
class World:
    """One deployment: data, its trained ensemble's batched scorer (the
    calibration / oracle scores) and its lazy stage-scorer template."""

    name: str
    x_train: np.ndarray
    x_test: np.ndarray
    score_fn: Callable
    scorer: api.StageScorer
    beta: float
    mode: str
    T: int


def gbt_world(n_trees: int = 500, scale: float = 1.0) -> World:
    ds = make_dataset("adult", seed=SEED, scale=scale)
    gbt = train_gbt(ds.x_train, ds.y_train, n_trees=n_trees, depth=5, seed=SEED)
    st = {k: np.asarray(v) for k, v in gbt.stacked().items()}

    def score_fn(x):
        return ops.gbt_scores(st["feats"], st["thrs"], st["leaves"], jnp.asarray(x))

    return World(
        f"gbt{n_trees}", ds.x_train, ds.x_test, score_fn,
        api.TreeScorer(st["feats"], st["thrs"], st["leaves"], block_n=SCORE_BLOCK_N),
        beta=-gbt.base_score, mode="both", T=n_trees,
    )


def lattice_world(n_lattices: int = 500, scale: float = 1.0, steps: int = 300) -> World:
    ds = make_dataset("rw2", seed=SEED, scale=scale)
    lat = init_lattice_ensemble(n_lattices, ds.D, S=8, seed=SEED)
    lat = train_lattice_ensemble(lat, ds.x_train, ds.y_train, mode="joint", steps=steps)
    theta, feats = np.asarray(lat["theta"]), np.asarray(lat["feats"])

    def score_fn(x):
        return ops.lattice_scores(theta, feats, jnp.asarray(x))

    return World(
        f"lattice{n_lattices}", ds.x_train, ds.x_test, score_fn,
        api.LatticeScorer(theta, feats, block_n=SCORE_BLOCK_N),
        beta=0.0, mode="neg_only", T=n_lattices,
    )


def f32_decide(g0, chunk, eps_pos, eps_neg, t0):
    """The numpy reference decide at the device's float32."""
    f32 = np.float32
    return decide_chunk_reference(
        np.asarray(g0, f32), np.asarray(chunk, f32),
        np.asarray(eps_pos, f32), np.asarray(eps_neg, f32), t0,
    )


f32_decide.carry_dtype = np.float32


def host_oracle(world: World, model, x: np.ndarray):
    """Host ``ChunkedExecutor`` verdicts for ``x``: (decisions, exit steps)."""
    ordered = np.asarray(world.score_fn(x), np.float32)[:, model.order]
    res = ChunkedExecutor(
        CascadePlan.from_qwyc(model), matrix_producer(ordered), decide_fn=f32_decide
    ).run(x.shape[0])
    return res.decisions, res.exit_step


def fit(world: World):
    t = time.perf_counter()
    fitted = api.fit(
        world.score_fn, world.x_train, beta=world.beta, alpha=ALPHA, mode=world.mode
    )
    return fitted, time.perf_counter() - t


def serve_phase(world: World, batch: int = BATCH) -> dict:
    """api.fit -> compile('device') -> serve, checked against the host oracle."""
    fitted, fit_s = fit(world)
    compiled = fitted.compile("device", scorer=world.scorer)
    srv = compiled.serve(batch_size=batch)
    # test rows (compiles), one full train batch, the test rows again
    waves = [world.x_test, world.x_train[:batch], world.x_test]
    walls, verdicts = [], []
    for x in waves:
        t = time.perf_counter()
        for row in x:
            srv.submit(row)
        out = srv.drain()
        walls.append(time.perf_counter() - t)
        check(len(out) == x.shape[0], f"{world.name}: {len(out)} results for {x.shape[0]} rows")
        verdicts.append(out)
    events = srv.stats.degradation_events + compiled.degradation_events
    executor = srv._dev[0] if srv._dev is not None else None
    check(not events, f"{world.name}: degradation events {events}")
    check(srv.exec.name == "device", f"{world.name}: served by {srv.exec.name!r}")
    check(type(executor) is DeviceExecutor, f"{world.name}: executor {type(executor).__name__}")
    check(executor.traces == 1, f"{world.name}: {executor.traces} device traces")
    mismatched = 0
    for x, out in zip(waves, verdicts):
        dec, ex = host_oracle(world, fitted.model, x)
        got_dec = np.array([r["decision"] for r in out])
        got_ex = np.array([r["models_evaluated"] for r in out])
        mismatched += int((got_dec != dec).sum() + (got_ex != ex).sum())
    rows = sum(x.shape[0] for x in waves)
    check(mismatched == 0, f"{world.name}: {mismatched} verdicts/exit steps differ from the host oracle")
    mean_test = float(np.mean([r["models_evaluated"] for r in verdicts[0]]))
    report = {
        "ensemble": world.name, "T": world.T, "mode": world.mode, "alpha": ALPHA,
        "fit_s": fit_s, "first_wave_s": walls[0],
        "full_batch_wave_s": walls[1], "repeat_wave_s": walls[2],
        "compile_s": walls[0] - walls[2], "rows_served": rows,
        "mean_models_test": mean_test,
        "train_mean_models": float(fitted.model.train_mean_models),
        "backend": srv.exec.name, "executor": type(executor).__name__,
        "megakernel": executor.megakernel, "degradation_events": len(events),
        "parity": f"{rows}/{rows} verdicts and exit steps equal the host oracle",
    }
    log(json.dumps(report))
    return report


def four_chip_phase(world: World) -> list[dict]:
    """The sharded path at 4x1 and 2x2 against one-chip verdicts."""
    n_dev = len(jax.devices())
    check(n_dev >= 4, f"--four-chips needs 4 devices, JAX sees {n_dev}")
    fitted, _ = fit(world)
    x = world.x_test
    ref = fitted.compile("device", scorer=world.scorer).evaluate(x=x)
    reports = []
    for shards, model_shards in ((4, 1), (2, 2)):
        c = fitted.compile(
            "sharded", scorer=world.scorer, shards=shards, model_shards=model_shards
        )
        t = time.perf_counter()
        res = c.evaluate(x=x)
        wall = time.perf_counter() - t
        ex = c._executor
        used = {d.id for d in ex.mesh.devices.flat}
        check(type(ex) is ShardedDeviceExecutor, f"{shards}x{model_shards}: {type(ex).__name__}")
        check(len(used) == 4, f"{shards}x{model_shards} mesh spans devices {sorted(used)}")
        check(not c.degradation_events, f"{shards}x{model_shards}: {c.degradation_events}")
        same = bool(
            np.array_equal(res.decisions, ref.decisions)
            and np.array_equal(res.exit_step, ref.exit_step)
        )
        check(same, f"{shards}x{model_shards}: verdicts differ from the one-chip run")
        report = {
            "mesh": f"{shards}x{model_shards}", "devices": sorted(used),
            "rows": int(x.shape[0]), "first_run_s": wall,
            "megakernel": ex.megakernel, "matches_one_chip": same,
            "mean_models": float(res.exit_step.mean()),
        }
        log(json.dumps(report))
        reports.append(report)
    return reports


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--four-chips", action="store_true",
        help="run only the sharded path (4x1 and 2x2 meshes) against one chip",
    )
    args = ap.parse_args(argv)
    cache_dir = setup_compile_cache()
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        print(
            f"chip_smoke: JAX found no TPU (platform {d0.platform!r}); nothing was served",
            file=sys.stderr,
        )
        return 1
    log(
        f"platform={d0.platform} device_kind={d0.device_kind!r} "
        f"count={len(devices)} cache_dir={cache_dir}"
    )
    try:
        if args.four_chips:
            four_chip_phase(gbt_world())
        else:
            serve_phase(gbt_world())
            serve_phase(lattice_world())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({
        "ok": True,
        "device": {"platform": d0.platform, "kind": d0.device_kind, "count": len(devices)},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
