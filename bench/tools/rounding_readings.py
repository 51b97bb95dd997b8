"""The two readings a cell's rounding band is set between, on its pool.

    python3 bench/tools/rounding_readings.py --workload <cell> [--out <file.json>]

Builds (or loads) the cell's fitted cascade, then for every pool row and
cascade position compares three partial sums: the program's, through the
scorer's own stage calls at margin infinity (every position scored, in
flushes of the cell's batch size); the plain reference's at the
configuration's precision; and the control's, the reference with its
weights at the precision below.  Each distance is taken as a share of the
reference's ``sum |f|`` so far, the unit of the band's ``rel_tol``.

Prints one JSON line.  For the program and for the control: the least
band that would leave out every row where it and the reference fall on
two sides of a threshold the reference's row meets (``needed_band``), the
share of pool rows that band leaves out, the rows the current band lets
through with another verdict or exit step, and the largest and median
distance.  Then the share of pool rows the current band leaves out and
the plan's mean exit step on the pool.  A sound band lies above the
program's ``needed_band``, below the control's, and leaves out no more
rows than its ``max_ambiguous_share``.
"""

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def program_partials(scorer, plan: dict, rows: np.ndarray, batch: int) -> np.ndarray:
    """(N, T) partial sums in plan order from the program's stage calls."""
    import jax
    import jax.numpy as jnp

    from repro.core.executor import CascadePlan
    from repro.kernels.device_executor import DevicePlan

    T = len(plan["order"])
    cplan = CascadePlan(
        order=np.asarray(plan["order"]), eps_pos=np.full(T, np.inf),
        eps_neg=np.full(T, -np.inf), beta=float(plan["beta"]), costs=np.ones(T),
    )
    dplan = DevicePlan.from_plan(cplan)
    bound = scorer.bind(dplan)

    @jax.jit
    def stage(weights, state, t0, rows_, x, n):
        return dataclasses.replace(bound, weights=weights).stage(state, t0, t0 + dplan.W, rows_, x, n)

    out = []
    lanes = jnp.arange(batch, dtype=jnp.int32)
    for i in range(0, rows.shape[0], batch):
        blk = np.zeros((batch,) + rows.shape[1:], rows.dtype)
        m = min(batch, rows.shape[0] - i)
        blk[:m] = rows[i : i + m]
        x, state = bound.prepare(blk), bound.init_state(batch)
        cols = []
        for t0 in dplan.stage_t0:
            s, state = stage(bound.weights, state, jnp.int32(t0), lanes, x, jnp.int32(batch))
            cols.append(np.asarray(s, np.float64))
        out.append(np.cumsum(np.concatenate(cols, 1)[:m, :T], 1))
    return np.concatenate(out)


def readings(ens, cfg: dict, params: dict, plan: dict, pool: np.ndarray) -> dict:
    """The readings of one fitted cascade on ``pool`` (see the module's
    docstring)."""
    import harness
    import reference

    t = time.time()
    order = np.asarray(plan["order"])
    ref = np.asarray(ens.scores(params, pool), np.float64)[:, order]
    t_ref = time.time()
    g_ref = np.cumsum(ref, 1)
    mass = np.cumsum(np.abs(ref), 1)
    prog = program_partials(ens.program_scorer(params), plan, pool, int(cfg["batch_size"]))
    t_prog = time.time()
    ctrl = np.cumsum(np.asarray(ens.scores(ens.lower_precision(params), pool), np.float64)[:, order], 1)
    t_ctrl = time.time()
    rel_tol, max_share, _ = harness.rounding_band(ens, cfg)
    beta = float(plan["beta"])
    _, ex, amb = reference.cascade(ref, plan["eps_pos"], plan["eps_neg"], beta, rel_tol=rel_tol)

    def verdicts(g):
        dec, ex_, _ = reference.cascade(np.diff(g, axis=1, prepend=0.0), plan["eps_pos"],
                                        plan["eps_neg"], beta, rel_tol=0.0, dtype=np.float64)
        return dec, ex_

    def needed(g):
        """The least band that leaves out every row this side of a
        threshold where the reference is on the other, at the positions
        the reference reaches."""
        T = g.shape[1]
        reach = np.arange(T)[None, :] < ex[:, None]
        worst, at = 0.0, -1
        for eps in (plan["eps_neg"], plan["eps_pos"], np.r_[np.full(T - 1, np.inf), beta]):
            e = np.asarray(eps, np.float64)[None, :]
            split = reach & np.isfinite(e) & ((g >= e) != (g_ref >= e))
            dist = np.where(split, np.abs(g_ref - e) / mass, -1.0)
            if dist.max() > worst:
                worst, at = float(dist.max()), int(np.unravel_index(dist.argmax(), dist.shape)[1])
        return worst, at

    def shares(g):
        d = np.abs(g - g_ref) / np.maximum(mass, 1e-30)
        dec, ex_ = verdicts(g)
        rdec, rex = verdicts(g_ref)
        band, at = needed(g)
        _, _, amb_at = reference.cascade(ref, plan["eps_pos"], plan["eps_neg"], beta, rel_tol=band)
        return {"needed_band": band, "needed_at_position": at,
                "ambiguous_at_needed": float(amb_at.mean()),
                "mismatched_outside_band": int((((dec != rdec) | (ex_ != rex)) & ~amb).sum()),
                "max": float(d.max()), "median": float(np.median(d))}

    return {
        "rows": int(pool.shape[0]), "program": shares(prog), "control": shares(ctrl),
        "band": {"rel_tol": rel_tol, "max_ambiguous_share": max_share,
                 "ambiguous": float(amb.mean())},
        "mean_exit_step": float(ex.mean()), "positions": int(order.size),
        "seconds": {"reference": t_ref - t, "program": t_prog - t_ref, "control": t_ctrl - t_prog},
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--out")
    args = ap.parse_args()

    import harness

    cell = harness.load_cell(args.workload)
    harness.setup_compile_cache()
    ens = harness.load_ensemble(cell.config["ensemble"])
    params, plan, built = harness.fitted_artifact(cell, ens)
    pool = harness._world(ens, cell.config).pool
    line = {"workload": args.workload, "built": bool(built),
            **readings(ens, cell.config, params, plan, pool)}
    print(json.dumps(line), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
