"""Sweep open-loop rates on a cell and report where the server keeps up.

    python3 bench/tools/knee_sweep.py --workload gbt500_adult_b256.online \
        --seconds 5 --rates 5000 10000 20000 40000

One set-up, then one window per rate through the cell's own driver, with
the mix's ``rate_per_s`` replaced.  Per rate it prints the completed rate,
the backlog (rows due but not yet submitted) at each flush over the first
and last quarter of the window, the rows per flush, the latency median,
95th and 99th percentiles, the programs built in the window and the
collector's pauses.  The knee is the highest rate whose completed rate
keeps up with the offered rate and whose backlog does not grow.
"""

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=4242)
    args = ap.parse_args()

    import harness

    cell = harness.load_cell(args.workload)
    harness.setup_compile_cache()
    t = time.time()
    sess = harness.open_session(cell)
    harness.warm_up(sess)
    print(json.dumps({"setup_s": time.time() - t, "flush_size": sess.srv.flush_size}))
    for rate in args.rates:
        sess.cell.mix = dict(cell.mix, rate_per_s=rate)
        built = harness.programs_built()
        with harness.GcPauses() as gcp:
            run = harness.open_loop(sess, args.seed, args.seconds, harness.Spans(False))
        built = harness.programs_built() - built
        q = max(run["n_flush"] // 4, 1)
        bl = run["backlog"]
        lat = run["latency_s"]
        print(json.dumps({
            "offered_per_s": rate,
            "completed_per_s": int(run["answered"].sum()) / run["elapsed_s"],
            "elapsed_s": run["elapsed_s"],
            "backlog_first_quarter": float(bl[:q].mean()),
            "backlog_last_quarter": float(bl[-q:].mean()),
            "backlog_max": int(bl.max()),
            "rows_per_flush": int(run["answered"].sum()) / run["n_flush"],
            "flush_ms": float(run["flush_wall_s"].mean() * 1e3),
            "p50_ms": float(np.percentile(lat, 50) * 1e3),
            "p95_ms": float(np.percentile(lat, 95) * 1e3),
            "p99_ms": float(np.percentile(lat, 99) * 1e3),
            "flush_rows_p5_p50_p95_max": [float(v) for v in np.percentile(run["flush_rows"], [5, 50, 95, 100])],
            "programs_built": built, "gc": gcp.summary(),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
