"""Reduction of the program's own spans and device scopes in a profiler trace.

The served path marks its host phases with ``qwyc.*`` ``TraceAnnotation``s
and its device regions with ``qwyc.*`` ``jax.named_scope``s (the names are
in ``src/repro/tracing.py``).  ``load(path)`` reads both from an
``.xplane.pb``, beside the benchmark's ``bench.*`` spans; ``reduce(events)``
works on those plain records alone, so the arithmetic is checked on a
small recorded trace without a chip.  ``devtrace`` reduces the same trace
to device busy time and idle time by benchmark span, and is not changed
by anything here.

Within the ``bench.window`` span, for every program span name: how many
there are, their total and self seconds (the duration less that of the
program spans nested inside), and the device-idle seconds inside them,
both in all (``idle_s``) and where the name is the innermost program span
covering the idle instant (``idle_self_s``).  Idle time is averaged over
devices, like ``devtrace``'s.  For every device scope: the self seconds
of the ops under it, summed over devices; an op belongs to the innermost
``qwyc.*`` scope on its ``op_name`` path, or to ``unscoped``.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import re

import devtrace

PREFIX = "qwyc."
UNSCOPED = "unscoped"
FLUSH = "qwyc.flush"
COMPACT = "qwyc.compact"
MODULES_LINE = "XLA Modules"
_SCOPE = re.compile(r"qwyc\.[a-z_]+")


@dataclasses.dataclass
class Events:
    spans: list  # [(name, start_ns, end_ns)] of the program's host spans
    bench: list  # [(name, start_ns, end_ns)] of the benchmark's host spans
    device_ops: dict  # device name -> [(op_name path, start_ns, end_ns)]


@dataclasses.dataclass
class SpanTime:
    count: int
    total_s: float
    self_s: float
    idle_s: float  # device idle inside the span, its children included
    idle_self_s: float  # device idle where the span is the innermost one


@dataclasses.dataclass
class Summary:
    window_s: float
    n_devices: int
    busy_s: float  # summed over devices
    spans: dict  # span name -> SpanTime
    scopes: dict  # scope -> device self seconds, summed over devices
    idle_outside_s: float  # idle under no program span, averaged over devices

    def idle_ms_per(self, name: str = FLUSH) -> float | None:
        """Device-idle milliseconds inside one ``name`` span, on average."""
        t = self.spans.get(name)
        return None if t is None or t.count == 0 else t.idle_s / t.count * 1e3

    def scope_share(self, scope: str = COMPACT) -> float | None:
        """Device self time under ``scope`` over device busy time, in
        percent; None where no op of the window carries a program scope."""
        if self.busy_s <= 0 or set(self.scopes) <= {UNSCOPED}:
            return None
        return 100.0 * self.scopes.get(scope, 0.0) / self.busy_s


def scope_of(op_name: str) -> str:
    """The innermost ``qwyc.*`` scope on an ``op_name`` path."""
    found = _SCOPE.findall(op_name or "")
    return found[-1] if found else UNSCOPED


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _message(buf) -> dict:
    """The fields of one serialized protobuf message: field number ->
    values, an int per varint and a memoryview per length-delimited field;
    fixed-width fields are skipped."""
    out, i, n = collections.defaultdict(list), 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i : i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {wire} before byte {i}")
        out[key >> 3].append(value)
    return out


def _text(fields, number: int) -> str:
    return bytes(fields[number][0]).decode() if fields.get(number) else ""


def _program_key(name: str) -> str:
    """``jit__program(11724057648416287298)`` -> ``11724057648416287298``."""
    return name[name.rfind("(") + 1 : -1] if name.endswith(")") else name


def hlo_op_names(raw: bytes) -> dict:
    """{program: {instruction: op_name}} from the HLO of every program a
    trace keeps: the ``Hlo Proto`` stats of its ``/host:metadata`` plane.

    A device's operation events name an instruction, not its scope; the
    ``op_name`` metadata is in these protos, read here field by field
    (XSpace, XPlane, XEventMetadata and XStat of
    ``tsl/profiler/protobuf/xplane.proto``; HloProto, HloModuleProto,
    HloComputationProto, HloInstructionProto and OpMetadata of XLA's
    ``hlo.proto`` and ``xla_data.proto``).  Programs are keyed by the id
    in their name.
    """
    out = {}
    for plane in _message(memoryview(raw)).get(1, []):
        plane = _message(plane)
        if _text(plane, 2) != "/host:metadata":
            continue
        stat_names = {}
        for entry in plane.get(5, []):
            meta = _message(_message(entry)[2][0])
            stat_names[meta[1][0] if meta.get(1) else 0] = _text(meta, 2)
        for entry in plane.get(4, []):
            event = _message(_message(entry)[2][0])
            for stat in event.get(5, []):
                stat = _message(stat)
                sid = stat[1][0] if stat.get(1) else 0
                if stat_names.get(sid) != "Hlo Proto" or not stat.get(6):
                    continue
                module = _message(_message(stat[6][0])[1][0])
                ops = _module_op_names(module)
                out[_program_key(_text(event, 2))] = ops
    return out


def _module_op_names(module) -> dict:
    """{instruction: op_name} of one HloModuleProto.  An instruction with
    no ``op_name`` of its own (a fusion XLA made, say) takes that of the
    root of the computation it calls, where that has one."""
    instrs, roots = {}, {}
    for comp in module.get(3, []):
        comp = _message(comp)
        if comp.get(5) and comp.get(6):
            roots[comp[5][0]] = comp[6][0]
        for instr in comp.get(2, []):
            instr = _message(instr)
            op = _text(_message(instr[7][0]), 2) if instr.get(7) else ""
            iid = instr[35][0] if instr.get(35) else None
            instrs[iid] = (_text(instr, 1), op, instr.get(38, []))
    out = {}
    for name, op, called in instrs.values():
        for _ in range(4):
            if op or not called:
                break
            _, op, called = instrs.get(roots.get(called[0]), ("", "", []))
        out[name] = op
    return out


def op_names(ops, modules, hlo: dict) -> list:
    """One device's ``ops`` [(HLO text, start, end)] with each op's
    ``op_name`` in place of its text, found through the program whose
    ``modules`` event [(name, start, end)] holds the op."""
    modules = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in modules]
    out = []
    for text, s, e in ops:
        k = bisect.bisect_right(starts, s) - 1
        inside = k >= 0 and s < modules[k][2]
        names = hlo.get(_program_key(modules[k][0]), {}) if inside else {}
        instr = text.partition(" = ")[0].strip().lstrip("%")
        out.append((names.get(instr, ""), s, e))
    return out


def load(path: str) -> Events:
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    hlo = hlo_op_names(raw)
    spans, bench, ops = [], [], {}
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {
                line.name: [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
                for line in plane.lines
            }
            evs = [e for name in devtrace.OPS_LINES for e in lines.get(name, [])]
            if evs:
                ops[plane.name] = op_names(evs, lines.get(MODULES_LINE, []), hlo)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    rec = (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    if e.name.startswith(PREFIX):
                        spans.append(rec)
                    elif e.name.startswith(devtrace.SPAN_PREFIX):
                        bench.append(rec)
    key = lambda s: (s[1], -s[2])  # noqa: E731
    return Events(sorted(spans, key=key), sorted(bench, key=key), ops)


def innermost(spans, lo, hi) -> list:
    """Disjoint ``(start, end, name)`` pieces of ``[lo, hi]`` covered by
    the nested ``spans``, each named by the innermost span over it."""
    pieces, stack, t = [], [], lo

    def advance(to):
        nonlocal t
        if stack and to > t:
            pieces.append((t, to, stack[-1][0]))
        t = max(t, to)

    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        while stack and stack[-1][1] <= s:
            advance(stack[-1][1])
            stack.pop()
        advance(s)
        stack.append((name, min(e, stack[-1][1]) if stack else e))
    while stack:
        advance(stack[-1][1])
        stack.pop()
    return pieces


def overlap(a, b) -> int:
    """Total length of the intersection of two sorted lists of disjoint
    ``(start, end, ...)`` intervals."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def reduce(ev: Events) -> Summary:
    windows = [s for s in ev.bench if s[0] == devtrace.SPAN_PREFIX + "window"]
    if not windows:
        raise ValueError("trace has no bench.window span")
    _, lo, hi = windows[-1]
    if not ev.device_ops:
        raise ValueError("trace has no device operation events")
    inside = [s for s in ev.spans if s[2] > lo and s[1] < hi]
    by_name = collections.defaultdict(list)
    for name, s, e in inside:
        by_name[name].append((max(s, lo), min(e, hi)))
    pieces = innermost(inside, lo, hi)
    own = collections.defaultdict(list)
    for p in pieces:
        own[p[2]].append(p)
    self_s = devtrace.self_times(inside, lo, hi)

    busy = 0
    idle = collections.Counter()
    idle_self = collections.Counter()
    idle_total = 0
    scopes = collections.Counter()
    for evs in ev.device_ops.values():
        merged = devtrace._union([(s, e) for _, s, e in evs], lo, hi)
        busy += sum(e - s for s, e in merged)
        gaps, prev = [], lo
        for s, e in merged + [[hi, hi]]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        idle_total += sum(e - s for s, e in gaps)
        for name, iv in by_name.items():
            idle[name] += overlap(gaps, devtrace._union(iv, lo, hi))
            idle_self[name] += overlap(gaps, own.get(name, []))
        for scope, d in devtrace.self_times(
            [(scope_of(n), s, e) for n, s, e in evs], lo, hi
        ).items():
            scopes[scope] += d / 1e9
    nd = len(ev.device_ops)
    spans = {
        name: SpanTime(
            count=len(iv),
            total_s=sum(e - s for s, e in iv) / 1e9,
            self_s=self_s[name] / 1e9,
            idle_s=idle[name] / 1e9 / nd,
            idle_self_s=idle_self[name] / 1e9 / nd,
        )
        for name, iv in by_name.items()
    }
    return Summary(
        window_s=(hi - lo) / 1e9,
        n_devices=nd,
        busy_s=busy / 1e9,
        spans=spans,
        scopes=dict(scopes),
        idle_outside_s=(idle_total - sum(idle_self.values())) / 1e9 / nd,
    )
