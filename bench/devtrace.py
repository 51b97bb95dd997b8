"""Reduction of a profiler trace to device busy time, idle gaps and spans.

``load(path)`` reads an ``.xplane.pb`` with JAX's own ``ProfileData`` and
returns plain records; ``reduce(events, window)`` works on those alone, so
the arithmetic is checked on a small recorded trace without a chip.

Device activity is the union of the intervals of a device's operation
events: the ``XLA Ops`` line of each ``/device:<kind>:<n>`` plane.  Host
spans are the benchmark's own ``TraceAnnotation`` events, named
``bench.<what>``; the window is the ``bench.window`` span.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re

SPAN_PREFIX = "bench."
OPS_LINES = ("XLA Ops",)


@dataclasses.dataclass
class Events:
    device_ops: dict  # device name -> [(name, start_ns, end_ns)]
    spans: list  # [(name, start_ns, end_ns)] of the benchmark's spans


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: list  # per device, within the window
    device_ops: list  # [(op, self seconds)], summed over devices, longest first
    idle_gaps: list  # [(host span, seconds)], idle time by what the host did
    n_devices: int

    @property
    def busy_mean_s(self) -> float:
        return sum(self.busy_s) / max(len(self.busy_s), 1)


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str) -> Events:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            evs = []
            for line in plane.lines:
                if line.name in OPS_LINES:
                    evs += [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events]
            if evs:
                ops[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events
                    if e.name.startswith(SPAN_PREFIX)
                ]
    return Events(ops, sorted(spans, key=lambda s: s[1]))


_KIND = re.compile(r"[\]\}\)]\s([a-z][a-z0-9_-]*)\(")


def short_name(hlo: str) -> str:
    """``%fusion.45 = pred[4096]{0} fusion(...)`` -> ``%fusion.45 (fusion)``;
    names that are not HLO text pass through."""
    lhs, sep, rhs = hlo.partition(" = ")
    if not sep:
        return hlo
    m = _KIND.search(rhs)
    return f"{lhs} ({m.group(1)})" if m else lhs


def self_times(evs, lo, hi) -> collections.Counter:
    """Per-name time inside [lo, hi] not covered by an op nested inside it
    (a loop's own time, without its body's ops)."""
    out = collections.Counter()
    stack = []  # [name, end, child time]
    for name, s, e in sorted(evs, key=lambda x: (x[1], -x[2])):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        while stack and stack[-1][1] <= s:
            n, _, d = stack.pop()
            out[n] += d
        if stack:
            stack[-1][2] -= e - s
        stack.append([name, e, e - s])
    for n, _, d in stack:
        out[n] += d
    return out


def _union(intervals, lo, hi):
    """Merged, clipped (start, end) intervals."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _attribute(gaps: collections.Counter, spans, starts, a, b, weight) -> None:
    """Split the idle interval [a, b] over the benchmark spans it overlaps;
    the window's inner spans follow one another and do not nest."""
    covered = 0
    i = max(bisect.bisect_right(starts, a) - 1, 0)
    while i < len(spans) and spans[i][1] < b:
        name, s, e = spans[i]
        d = min(e, b) - max(s, a)
        if d > 0:
            gaps[name[len(SPAN_PREFIX):]] += d / 1e9 * weight
            covered += d
        i += 1
    if b - a > covered:
        gaps["outside spans"] += (b - a - covered) / 1e9 * weight


def reduce(ev: Events, top: int = 10) -> Summary:
    windows = [s for s in ev.spans if s[0] == SPAN_PREFIX + "window"]
    if not windows:
        raise ValueError("trace has no bench.window span")
    _, lo, hi = windows[-1]
    if not ev.device_ops:
        raise ValueError("trace has no device operation events")
    busy, op_time, gaps = [], collections.Counter(), collections.Counter()
    inner = [s for s in ev.spans if s[0] != SPAN_PREFIX + "window"]
    starts = [s[1] for s in inner]
    for dev in sorted(ev.device_ops):
        evs = ev.device_ops[dev]
        merged = _union([(s, e) for _, s, e in evs], lo, hi)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        for name, d in self_times(evs, lo, hi).items():
            op_time[short_name(name)] += d / 1e9
        prev = lo
        for s, e in merged + [[hi, hi]]:
            if s > prev:
                _attribute(gaps, inner, starts, prev, s, 1 / len(ev.device_ops))
            prev = max(prev, e)
    return Summary(
        window_s=(hi - lo) / 1e9,
        busy_s=busy,
        device_ops=op_time.most_common(top),
        idle_gaps=gaps.most_common(top),
        n_devices=len(ev.device_ops),
    )
