"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, mix and metrics come from ``BENCHMARK.json``
and the files it names.  The run needs as many TPU chips as the cell asks
for; on any other machine it exits with code 3 and prints no result.
``--trace 1`` records a profiler trace of the window and reports the
cell's per-layer metrics instead of its end-to-end ones.
"""

import time

T_START = time.time()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
# the TPU runtime's logs stay inside the checkout, not in a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", str(BENCH / ".cache" / "tpu_logs"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness

    cell = harness.load_cell(args.workload)
    harness.setup_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) != cell.chips:
        print(
            f"{args.workload} needs {cell.chips} TPU chip(s); JAX finds "
            f"{len(devices)} {devices[0].platform} device(s). Nothing was run.",
            file=sys.stderr,
        )
        return 3
    result = harness.run_cell(
        cell, args.seed, args.seconds, bool(args.trace), T_START,
        log=lambda s: print(s, file=sys.stderr, flush=True),
    )
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
