"""The trace reduction, on a hand-made trace and on a slice recorded on a
TPU v5e (``data/gbt500_offline_trace_slice.json``: the first 45 ms of a
``gbt500_adult.offline`` window, op names shortened)."""

import json
from pathlib import Path

import numpy as np
import pytest

import devtrace

DATA = Path(__file__).resolve().parent / "data"


def events(ops, spans):
    return devtrace.Events({d: [tuple(e) for e in v] for d, v in ops.items()},
                           sorted((tuple(s) for s in spans), key=lambda s: s[1]))


def test_hand_made_trace():
    ms = 1_000_000
    ops = {
        "/device:TPU:0": [("loop", 10 * ms, 40 * ms), ("k", 12 * ms, 20 * ms),
                          ("k", 25 * ms, 30 * ms), ("copy", 60 * ms, 70 * ms)],
        "/device:TPU:1": [("loop", 0, 100 * ms)],
    }
    spans = [("bench.window", 0, 100 * ms), ("bench.submit", 0, 50 * ms),
             ("bench.flush", 50 * ms, 90 * ms), ("bench.drain", 90 * ms, 100 * ms)]
    s = devtrace.reduce(events(ops, spans))
    assert s.window_s == pytest.approx(0.1)
    assert s.busy_s == pytest.approx([0.040, 0.100])
    assert s.busy_mean_s == pytest.approx(0.070)
    # the loop's own time leaves out the 13 ms of its nested kernels
    assert dict(s.device_ops) == pytest.approx({"loop": 0.117, "k": 0.013, "copy": 0.010})
    # device 0 idles 0-10 and 40-50 (submit), 50-60 and 70-90 (flush),
    # 90-100 (drain); device 1 never idles; shares averaged over 2 devices
    assert dict(s.idle_gaps) == pytest.approx({"submit": 0.010, "flush": 0.015, "drain": 0.005})


def test_short_names():
    assert devtrace.short_name("%fusion.45 = pred[4096]{0:T(1024)} fusion(pred[4096] %a)") == (
        "%fusion.45 (fusion)")
    assert devtrace.short_name("%body.7 = (f32[4096,1]{1,0}, s32[4]) custom-call(f32[1] %x)") == (
        "%body.7 (custom-call)")
    assert devtrace.short_name("jit__program(123)") == "jit__program(123)"


def test_recorded_chip_slice():
    d = json.loads((DATA / "gbt500_offline_trace_slice.json").read_text())
    ev = events(d["device_ops"], d["spans"])
    s = devtrace.reduce(ev)
    (_, lo, hi), = [x for x in ev.spans if x[0] == "bench.window"]
    assert s.window_s == pytest.approx((hi - lo) / 1e9)
    # busy time by an independent sweep over interval end points
    (evs,) = ev.device_ops.values()
    pts = sorted([(max(a, lo), 1) for _, a, b in evs if b > lo and a < hi]
                 + [(min(b, hi), -1) for _, a, b in evs if b > lo and a < hi])
    depth, busy, prev = 0, 0, lo
    for t, step in pts:
        if depth > 0:
            busy += t - prev
        depth += step
        prev = t
    assert s.busy_s == pytest.approx([busy / 1e9])
    assert 0 < s.busy_s[0] < s.window_s
    gaps = dict(s.idle_gaps)
    assert sum(gaps.values()) == pytest.approx(s.window_s - s.busy_s[0])
    assert set(gaps) <= {"submit", "flush", "drain", "outside spans"} and gaps["submit"] > 0
    # the client's own bookkeeping between spans is a sliver of the idle time
    assert gaps.get("outside spans", 0) < 0.05 * sum(gaps.values())
    # self times add up to the busy time where ops do not overlap across
    # the device's one stream
    assert sum(t for _, t in s.device_ops) <= s.busy_s[0] * (1 + 1e-9) + 1e-3
    names = [n for n, _ in s.device_ops]
    assert any("custom-call" in n for n in names)
    assert np.all(np.diff([t for _, t in s.device_ops]) <= 0)
