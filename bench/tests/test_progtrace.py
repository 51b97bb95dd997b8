"""The reduction by program spans and device scopes (``progtrace``), on a
hand-made trace and on slices recorded on a TPU v5e: the benchmark's own
``gbt500_offline_trace_slice.json``, from before the program had spans,
and ``gbt500_online_program_slice.json``, the first flushes of a
``gbt500_adult_b256.online`` window with the program's spans and the
scope path of each device op (``tools/program_trace.py --slice-ms``)."""

import json
from pathlib import Path

import pytest

import devtrace
import progtrace

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000


def events(spans, bench, ops):
    key = lambda s: (s[1], -s[2])  # noqa: E731
    return progtrace.Events(
        sorted((tuple(s) for s in spans), key=key),
        sorted((tuple(s) for s in bench), key=key),
        {d: [tuple(e) for e in v] for d, v in ops.items()},
    )


def test_hand_made_trace():
    f = progtrace.FLUSH
    spans = [
        (f, 10 * MS, 40 * MS), ("qwyc.flush.stack", 10 * MS, 12 * MS),
        ("qwyc.flush.prepare", 12 * MS, 15 * MS), ("qwyc.flush.sort_key", 15 * MS, 20 * MS),
        ("qwyc.run.dispatch", 20 * MS, 22 * MS), ("qwyc.run.fetch", 22 * MS, 35 * MS),
        ("qwyc.run.stats", 35 * MS, 36 * MS), ("qwyc.flush.finish", 36 * MS, 39 * MS),
        ("qwyc.drain", 40 * MS, 41 * MS),
        (f, 60 * MS, 90 * MS), ("qwyc.run.fetch", 62 * MS, 88 * MS),
    ]
    bench = [("bench.window", 0, 100 * MS), ("bench.flush", 9 * MS, 41 * MS),
             ("bench.flush", 59 * MS, 91 * MS)]
    ops = {
        "/device:TPU:0": [
            ("jit(key_scores)/qwyc.sort_key/gather", 16 * MS, 18 * MS),
            ("jit(_program)/while", 23 * MS, 34 * MS),
            ("jit(_program)/while/body/qwyc.compact/qwyc.score_decide/pallas_call",
             23 * MS, 30 * MS),
            ("jit(_program)/while/body/qwyc.compact/scatter", 30 * MS, 33 * MS),
            ("jit(_program)/qwyc.finalize/scatter", 34 * MS, 35 * MS),
        ],
        "/device:TPU:1": [("", 0, 100 * MS)],
    }
    s = progtrace.reduce(events(spans, bench, ops))
    assert s.window_s == pytest.approx(0.1) and s.n_devices == 2
    assert s.busy_s == pytest.approx(0.014 + 0.100)
    fl = s.spans[f]
    assert fl.count == 2 and fl.total_s == pytest.approx(0.060)
    # the flushes' own time: 1 ms of the first after its phases, 2 + 2
    # ms of the second around its fetch
    assert fl.self_s == pytest.approx(0.005)
    # device 0 idles 16 ms in the first flush and 30 in the second; device
    # 1 never; averaged over 2 devices
    assert fl.idle_s == pytest.approx(0.023)
    assert s.idle_ms_per(f) == pytest.approx(11.5)
    assert fl.idle_self_s == pytest.approx(0.0025)
    # innermost attribution: the sort-key phase idles 15-16 and 18-20 ms
    ms = {k: t.idle_self_s * 1e3 for k, t in s.spans.items()}
    assert ms == pytest.approx({
        f: 2.5, "qwyc.flush.stack": 1.0, "qwyc.flush.prepare": 1.5,
        "qwyc.flush.sort_key": 1.5, "qwyc.run.dispatch": 1.0, "qwyc.run.fetch": 13.5,
        "qwyc.run.stats": 0.5, "qwyc.flush.finish": 1.5, "qwyc.drain": 0.5,
    })
    # idle under no program span: 0-10, 41-60 and 90-100 ms on device 0
    assert s.idle_outside_s == pytest.approx(0.0195)
    assert s.idle_outside_s + sum(ms.values()) / 1e3 == pytest.approx(
        s.window_s - s.busy_s / s.n_devices)
    # the loop op keeps its own 1 ms, without the ops nested in it
    assert s.scopes == pytest.approx({
        "qwyc.score_decide": 0.007, "qwyc.compact": 0.003, "qwyc.sort_key": 0.002,
        "qwyc.finalize": 0.001, progtrace.UNSCOPED: 0.101,
    })
    assert s.scope_share(progtrace.COMPACT) == pytest.approx(100 * 0.003 / 0.114)


def test_innermost_clips_a_child_to_its_parent():
    pieces = progtrace.innermost([("a", 0, 10), ("b", 5, 15)], 0, 20)
    assert pieces == [(0, 5, "a"), (5, 10, "b")]


def test_slice_without_program_spans():
    """The benchmark's slice from before the program had spans: the
    benchmark's own reduction reads what it always read, and this one
    finds no program span and no scope, only idle time outside them."""
    d = json.loads((DATA / "gbt500_offline_trace_slice.json").read_text())
    bench = devtrace.reduce(devtrace.Events(
        {k: [tuple(e) for e in v] for k, v in d["device_ops"].items()},
        sorted((tuple(s) for s in d["spans"]), key=lambda s: s[1]),
    ))
    assert bench.window_s == pytest.approx(0.045)
    assert bench.busy_s == pytest.approx([0.013344364])
    assert bench.device_ops[:3] == pytest.approx(
        [("%body.7 (custom-call)", 0.003227985), ("%fusion.45 (fusion)", 0.002404551),
         ("%fusion.39 (fusion)", 0.001839727)])
    assert dict(bench.idle_gaps) == pytest.approx({
        "submit": 0.01708481, "flush": 0.012554597, "drain": 0.00119049,
        "outside spans": 0.000825739})

    s = progtrace.reduce(events([], d["spans"], d["device_ops"]))
    assert s.spans == {} and s.idle_ms_per() is None and s.scope_share() is None
    assert s.busy_s == pytest.approx(sum(bench.busy_s))
    assert s.idle_outside_s == pytest.approx(bench.window_s - bench.busy_s[0])
    assert set(s.scopes) == {progtrace.UNSCOPED}


def test_op_names_of_a_recorded_program(tmp_path):
    """The scopes of a program's ops, read from the HLO the trace keeps:
    the CPU names each op event's program, a TPU only its module span.
    The scatter XLA wraps in a fusion of its own takes the scope of the
    fusion's root."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    @jax.jit
    def f(x, i):
        with jax.named_scope("qwyc.compact"):
            y = jnp.zeros_like(x).at[i].set(x * 2)
        with jax.named_scope("qwyc.finalize"):
            return jnp.sin(y) + 1

    x, i = jnp.arange(64.0), jnp.arange(64)[::-1]
    f(x, i).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    f(x, i).block_until_ready()
    jax.profiler.stop_trace()
    path = devtrace.find_xplane(str(tmp_path))
    with open(path, "rb") as fh:
        hlo = progtrace.hlo_op_names(fh.read())
    ops = [
        (e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats)["program_id"])
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines
        for e in line.events
        if "program_id" in dict(e.stats) and not e.name.startswith("end:")
    ]
    (pid,) = {p for *_, p in ops}
    modules = [(f"jit_f({pid})", min(s for _, s, _, _ in ops), max(e for _, _, e, _ in ops))]
    named = progtrace.op_names([o[:3] for o in ops], modules, hlo)
    assert {progtrace.scope_of(n) for n, _, _ in named} == {"qwyc.compact", "qwyc.finalize"}
    assert all(n.startswith("jit(f)/qwyc.") for n, _, _ in named)
    # an op outside every module span keeps no name
    assert progtrace.op_names([("%add.1 = f32[] add()", 0, 1)], modules, hlo) == [("", 0, 1)]


def test_recorded_online_slice():
    """Two online flushes recorded on a TPU v5e with the program's spans
    and scopes: each program flush inside a benchmark flush, its phases
    covering it, its device idle time under the benchmark's, and every
    scope of the stage program present."""
    d = json.loads((DATA / "gbt500_online_program_slice.json").read_text())
    ev = events(d["spans"], d["bench"], d["device_ops"])
    s = progtrace.reduce(ev)
    bench = devtrace.reduce(devtrace.Events(ev.device_ops, ev.bench))
    assert s.busy_s == pytest.approx(sum(bench.busy_s))
    flushes = [x for x in ev.spans if x[0] == progtrace.FLUSH]
    outer = [x for x in ev.bench if x[0] == "bench.flush"]
    assert len(flushes) == len(outer) == s.spans[progtrace.FLUSH].count == 2
    assert all(b[1] <= f[1] and f[2] <= b[2] for f, b in zip(flushes, outer))
    # the phases cover all but a sliver of each flush
    fl = s.spans[progtrace.FLUSH]
    assert fl.self_s < 0.05 * fl.total_s
    phases = {k: t for k, t in s.spans.items() if k.startswith("qwyc.flush.") or
              k.startswith("qwyc.run.")}
    assert len(phases) == 7 and all(t.count == 2 for t in phases.values())
    assert sum(t.self_s for t in phases.values()) + fl.self_s == pytest.approx(fl.total_s)
    # idle inside the program's flushes: within the benchmark's flush spans
    assert 0 < fl.idle_s <= dict(bench.idle_gaps)["flush"] + 1e-9
    assert s.idle_ms_per() == pytest.approx(fl.idle_s / 2 * 1e3)
    # idle attributed by innermost span adds up to all the device's idle time
    idle = sum(t.idle_self_s for t in s.spans.values()) + s.idle_outside_s
    assert idle == pytest.approx(s.window_s - s.busy_s)
    assert set(s.scopes) == {"qwyc.score_decide", "qwyc.compact", "qwyc.finalize",
                             "qwyc.sort_key", progtrace.UNSCOPED}
    assert sum(s.scopes.values()) == pytest.approx(s.busy_s)
    assert 30 < s.scope_share(progtrace.COMPACT) < 70
