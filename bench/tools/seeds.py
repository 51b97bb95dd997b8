"""Read the correctness numbers of a cell on many seeds in one process.

    python3 bench/tools/seeds.py --workload <cell> --seconds 3 --seeds 11 12 13 ...

One set-up, then for each seed a window at the cell's own load through the
same driver and check as ``bench/run.py``, and the control on the same
served rows: the plain cascade with its weights in bfloat16, put in the
program's place.  Prints one JSON line per seed.
"""

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()

    import harness

    cell = harness.load_cell(args.workload)
    harness.setup_compile_cache()
    t = time.time()
    sess = harness.open_session(cell)
    harness.warm_up(sess)
    print(json.dumps({"setup_s": time.time() - t, "problem": harness.executor_problem(sess)}))
    cdec, cex, _ = harness.reference_verdicts(sess, lower=True)
    for seed in args.seeds:
        run = harness.DRIVERS[cell.mix["loop"]](sess, seed, args.seconds, harness.Spans(False))
        prog = harness.check(sess, run)
        amb = run["ambiguous_rows"]
        ctrl_run = dict(run, dec=cdec[run["idx"]], ex=cex[run["idx"]])
        ctrl = harness.check(sess, ctrl_run)
        print(json.dumps({
            "seed": seed, "rows": int(run["idx"].size), "ambiguous": amb,
            "program": {k: v["value"] for k, v in prog.items()},
            "control_mismatched_rows": ctrl["mismatched_rows"]["value"],
            "degradation_events": harness.degradation_events(sess),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
