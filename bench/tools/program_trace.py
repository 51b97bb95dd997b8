"""Record a traced window of a cell and break it down by the program's own
spans and device scopes.

    python3 bench/tools/program_trace.py --workload <cell> --seconds 10 --out <dir>

Runs the cell's set-up and warm-up, records one window with the profiler
on, and reduces the trace twice: by the benchmark's ``bench.*`` spans
(``devtrace``) and by the program's ``qwyc.*`` spans and scopes
(``progtrace``).  Prints, per flush, each program span's self time and the
device-idle time under it, the device time under each scope, the device
ops by self time with their scopes, the longest flushes phase by phase,
and how many program flushes lie outside a benchmark flush; writes the
same to ``<dir>/<cell>.json``.  With ``--slice-ms`` it also writes
``<dir>/<cell>.slice.json``: the spans and scoped device ops of the
window's first milliseconds, for the reduction's tests.
"""

import argparse
import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import devtrace  # noqa: E402
import progtrace  # noqa: E402


def phases(ev, flush) -> dict:
    """Milliseconds of one flush span: in all, device-idle, and in each
    program span nested inside it."""
    _, lo, hi = flush
    idle = 0
    for evs in ev.device_ops.values():
        busy = devtrace._union([(s, e) for _, s, e in evs if e > lo and s < hi], lo, hi)
        idle += hi - lo - sum(e - s for s, e in busy)
    out = {"flush": (hi - lo) / 1e6, "device_idle": idle / len(ev.device_ops) / 1e6}
    for name, s, e in ev.spans:
        if lo <= s and e <= hi and (name, s, e) != flush:
            out[name] = out.get(name, 0.0) + (e - s) / 1e6
    return out


def op_scopes(ev, dev_ev) -> list:
    """The device ops by self time, each with the scope it belongs to:
    ``devtrace``'s op names beside ``progtrace``'s op_name paths of the
    same events."""
    lo, hi = [(s, e) for n, s, e in ev.bench if n == "bench.window"][-1]
    out = {}
    for d, evs in dev_ev.device_ops.items():
        named = [(f"{devtrace.short_name(t)} [{progtrace.scope_of(n)}]", s, e)
                 for (t, s, e), (n, _, _) in zip(evs, ev.device_ops[d])]
        for k, v in devtrace.self_times(named, lo, hi).items():
            out[k] = out.get(k, 0) + v / 1e9
    return sorted(out.items(), key=lambda kv: -kv[1])[:15]


def report(ev, prog, bench, run) -> dict:
    lo, hi = [(s, e) for n, s, e in ev.bench if n == "bench.window"][-1]
    flushes = [s for s in ev.spans if s[0] == progtrace.FLUSH and lo <= s[1] < hi]
    bench_flushes = [s for s in ev.bench if s[0] == "bench.flush"]
    outside = sum(
        not any(b[1] <= f[1] and f[2] <= b[2] for b in bench_flushes) for f in flushes
    )
    n = max(len(flushes), 1)
    ft = prog.spans.get(progtrace.FLUSH)
    longest = sorted(flushes, key=lambda f: f[1] - f[2])[:5]
    out = {
        "window_s": prog.window_s,
        "busy_s": prog.busy_s,
        "devtrace_idle_gaps": dict(bench.idle_gaps),
        "flushes": len(flushes),
        "flushes_outside_bench_flush": outside,
        "flush_idle_ms": prog.idle_ms_per(progtrace.FLUSH),
        "compaction_share": prog.scope_share(progtrace.COMPACT),
        # the share of a flush its phases cover: 1 - the flush's own self time
        "phase_cover": (1 - ft.self_s / ft.total_s) if ft and ft.total_s else None,
        "idle_outside_ms_per_flush": prog.idle_outside_s / n * 1e3,
        "spans_ms_per_flush": {
            k: {"count": t.count, "total": t.total_s / n * 1e3, "self": t.self_s / n * 1e3,
                "idle": t.idle_s / n * 1e3, "idle_self": t.idle_self_s / n * 1e3}
            for k, t in sorted(prog.spans.items())
        },
        "scopes_ms_per_flush": {k: v / n * 1e3 for k, v in sorted(prog.scopes.items())},
        "longest_flushes_ms": [phases(ev, f) for f in longest],
    }
    if "flush_wall_s" in run:
        out["flush_ms_host_clock"] = float(run["flush_wall_s"].mean()) * 1e3
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=2**33 + 13)
    ap.add_argument("--slice-ms", type=float, default=0.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()

    import harness

    cell = harness.load_cell(args.workload)
    harness.setup_compile_cache()
    import jax

    sess = harness.open_session(cell)
    harness.warm_up(sess)
    tdir = harness.CACHE / "trace" / "program"
    shutil.rmtree(tdir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tdir), profiler_options=opts)
    run = harness.DRIVERS[cell.mix["loop"]](sess, args.seed, args.seconds, harness.Spans(True))
    jax.profiler.stop_trace()
    path = devtrace.find_xplane(str(tdir))
    ev = progtrace.load(path)
    dev_ev = devtrace.load(path)
    out = report(ev, progtrace.reduce(ev), devtrace.reduce(dev_ev), run)
    out["device_ops_s"] = op_scopes(ev, dev_ev)
    out["device"] = jax.devices()[0].device_kind
    dest = Path(args.out)
    dest.mkdir(parents=True, exist_ok=True)
    (dest / f"{cell.name}.json").write_text(json.dumps(out, indent=1))
    print(json.dumps(out, indent=1))
    if args.slice_ms > 0:
        write_slice(ev, cell.name, out["device"], args.slice_ms, dest)
    shutil.rmtree(tdir, ignore_errors=True)
    return 0


def write_slice(ev, name, device, slice_ms, dest) -> None:
    """The window's first ``slice_ms``, cut where a benchmark span ends:
    spans kept whole, device ops with their scope path only."""
    lo = [s for n, s, _ in ev.bench if n == "bench.window"][-1]
    hi = max(e for n, s, e in ev.bench if n != "bench.window" and s >= lo
             and e <= lo + slice_ms * 1e6)

    def within(spans):
        return [list(x) for x in spans if lo <= x[1] and x[2] <= hi]

    sl = {
        "recorded": f"{device}, {name} window start, {(hi - lo) / 1e6:.1f} ms",
        "spans": within(ev.spans),
        "bench": within(x for x in ev.bench if x[0] != "bench.window")
        + [["bench.window", lo, hi]],
        "device_ops": {
            d: [["/".join(progtrace._SCOPE.findall(n)), s, e]
                for n, s, e in evs if e > lo and s < hi]
            for d, evs in ev.device_ops.items()
        },
    }
    (dest / f"{name}.slice.json").write_text(json.dumps(sl))
    print("slice", sl["recorded"], sum(len(v) for v in sl["device_ops"].values()), "ops")


if __name__ == "__main__":
    sys.exit(main())
