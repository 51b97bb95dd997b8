"""Record a short traced window of a cell and keep a small slice of it.

    python3 bench/tools/trace_sample.py --workload <cell> --seconds 1 --out <dir>

Prints the trace's planes, lines and busiest event names, and writes
``<dir>/trace_slice.json``: the device-operation events and benchmark
spans of the first flushes of the window, in ``devtrace.Events`` form,
for the trace-reduction test.
"""

import argparse
import collections
import json
import shutil
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--slice-ms", type=float, default=60.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()

    import harness
    import devtrace

    cell = harness.load_cell(args.workload)
    harness.setup_compile_cache()
    import jax
    from jax.profiler import ProfileData

    sess = harness.open_session(cell)
    harness.warm_up(sess)
    tdir = harness.CACHE / "trace" / "sample"
    shutil.rmtree(tdir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tdir), profiler_options=opts)
    harness.DRIVERS[cell.mix["loop"]](sess, 7, args.seconds, harness.Spans(True))
    jax.profiler.stop_trace()
    path = devtrace.find_xplane(str(tdir))
    print("xplane bytes", Path(path).stat().st_size)
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            names = collections.Counter(e.name for e in evs)
            dur = collections.Counter()
            for e in evs:
                dur[e.name] += e.duration_ns
            print(f"  LINE {line.name!r} events={len(evs)}")
            if plane.name.startswith("/device") or line.name == "python":
                for n, d in dur.most_common(12):
                    print(f"     {d / 1e6:10.3f} ms  x{names[n]:6d}  {n[:100]}")
    t = time.perf_counter()
    ev = devtrace.load(path)
    s = devtrace.reduce(ev)
    print("reduce seconds", time.perf_counter() - t)
    print("summary", s)
    # a slice from the window's start: enough flushes to hold each span kind
    win = [x for x in ev.spans if x[0] == "bench.window"][-1]
    lo, hi = win[1], win[1] + int(args.slice_ms * 1e6)
    sl = {
        "device_ops": {
            d: [e for e in evs if e[1] < hi and e[2] > lo] for d, evs in ev.device_ops.items()
        },
        "spans": [x for x in ev.spans if x[0] != "bench.window" and x[1] < hi and x[2] > lo]
        + [["bench.window", lo, hi]],
    }
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    (out / "trace_slice.json").write_text(json.dumps(sl))
    print("slice events", sum(len(v) for v in sl["device_ops"].values()), "spans", len(sl["spans"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
