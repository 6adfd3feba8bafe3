"""Device idle time put down to what the host was doing, from one trace.

The program writes its host stages into the profiler's own trace as
``kta.*`` annotations (kafka_topic_analyzer_tpu/obs/trace.py), on the same
clock as the device's operations, and names the phases of its device step
with ``kta.*`` scopes.  ``attribute`` reads both from one ``.xplane.pb``
and splits every stretch in which no operation ran on the device by what
the host was doing, in this order:

1. the drive thread (the thread that runs the scan's stages) in
   ``kta.dispatch``, ``kta.snapshot`` or ``kta.finalize`` (or one of its
   ``kta.finalize.*`` children): that stage;
2. the drive thread in ``kta.ingest``, waiting for the ingest threads: if
   any of them is in ``kta.decode_pack`` (the non-fused ``kta.decode``,
   and the ``kta.stage`` child, count with it), ``decode_pack``; else if
   any is in ``kta.fetch``, ``fetch``; else ``ingest_other``;
3. between a scan's ``kta.finalize`` and the next scan's first stage, and
   outside every recorded ``kta.scan``: ``outside_scan``;
4. anything else (the drive thread between two stages of one scan):
   ``unattributed``.

The profiler records a span only if it starts and ends while it traces,
so the stage in progress when the trace started, or when it stopped, is
missing.  The span attributed is therefore the stretch the host record
covers, from the drive thread's first recorded span to the end of its
last; busy time is the union of device-op intervals clipped to it, and
the categories plus busy time sum to it exactly.  Device time per
``kta.*`` scope sums the durations of the ops whose scope names it.
"""

from __future__ import annotations

import bisect
import dataclasses
import re
import sys
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

PREFIX = "kta."
HOST_PLANE = "/host:CPU"
DEVICE_PLANE = re.compile(r"^/device:(?!CPU)[A-Za-z_]+:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: Device programs that fold batches: jitted ``step``/``superstep``.
STEP_MODULE = re.compile(r"^jit_(super)?step")
#: Drive-thread stages that take the whole stretch (rule 1), in order.
DRIVE_STAGES = ("dispatch", "snapshot", "finalize")
INGEST = "kta.ingest"
DECODE_SPANS = ("kta.decode_pack", "kta.decode", "kta.stage")
FETCH_SPANS = ("kta.fetch",)
SCAN = "kta.scan"
CATEGORIES = DRIVE_STAGES + ("decode_pack", "fetch", "ingest_other",
                             "outside_scan", "unattributed")
#: Where a device step counts as started by a ``kta.dispatch``: its start
#: lies at most this long after the dispatch span's start.
DISPATCH_LEAD_NS = 50e6


@dataclasses.dataclass
class Span:
    thread: int          # index of the profiler line (one per host thread)
    name: str
    start_ns: float
    end_ns: float


@dataclasses.dataclass
class Op:
    name: str
    start_ns: float
    end_ns: float
    scope: Optional[str] = None   # the first ``kta.*`` component of op_name


@dataclasses.dataclass
class Attribution:
    span_s: float                 # the stretch the host record covers
    busy_s: float                 # device busy inside it
    idle_s: Dict[str, float]      # CATEGORIES -> seconds of device idle
    scope_s: Dict[str, float]     # kta.* scope -> device seconds
    #: Per device step program in the stretch: its start minus the start
    #: of the nearest ``kta.dispatch`` (before or after it), seconds.
    step_offsets_s: List[float] = dataclasses.field(default_factory=list)

    def share(self, category: str) -> float:
        return self.idle_s[category] / self.span_s if self.span_s else 0.0

    @property
    def steps_led(self) -> int:
        """Device steps that start after a ``kta.dispatch`` starts, and at
        most 50 ms after it."""
        return sum(0 <= o <= DISPATCH_LEAD_NS / 1e9
                   for o in self.step_offsets_s)


def _scope(op_name: str) -> Optional[str]:
    """``jit(step_c)/kta.alive/or`` -> ``kta.alive``."""
    return next((p for p in op_name.split("/") if p.startswith(PREFIX)),
                None)


def _varint(buf, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf) -> Iterable[Tuple[int, object]]:
    """(field number, value) of one protobuf message: an int for varints,
    a memoryview for length-delimited fields, skipping fixed widths."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, value


def _map_values(entries: List[memoryview]) -> Iterable[memoryview]:
    for entry in entries:
        for num, value in _fields(entry):
            if num == 2:
                yield value


def op_scopes(data: bytes) -> Dict[str, str]:
    """HLO text of each device op -> its ``kta.*`` scope.

    A TPU op's scope is the ``tf_op`` stat of its event *metadata* (the
    instruction's ``op_name``, e.g. ``jit(step_c)/kta.alive/or``), which
    ``ProfileData`` does not expose; so this walks the serialized XSpace
    (tsl/profiler/protobuf/xplane.proto: XSpace.planes = 1; XPlane.name =
    2, event_metadata = 4, stat_metadata = 5; XEventMetadata.name = 2,
    stats = 5; XStat.metadata_id = 1, str_value = 5, ref_value = 7;
    XStatMetadata.id = 1, name = 2)."""
    out: Dict[str, str] = {}
    for num, plane in _fields(memoryview(data)):
        if num != 1:
            continue
        name, events, stat_md = "", [], []
        for f, value in _fields(plane):
            if f == 2:
                name = bytes(value).decode()
            elif f == 4:
                events.append(value)
            elif f == 5:
                stat_md.append(value)
        if not DEVICE_PLANE.match(name):
            continue
        stat_names = {}
        for md in _map_values(stat_md):
            fields = dict(_fields(md))
            stat_names[fields.get(1, 0)] = bytes(fields.get(2, b"")).decode()
        tf_op = {k for k, v in stat_names.items() if v == "tf_op"}
        for md in _map_values(events):
            text, scope = "", None
            for f, value in _fields(md):
                if f == 2:
                    text = bytes(value).decode()
                elif f == 5:
                    stat = dict(_fields(value))
                    if stat.get(1) in tf_op:
                        op = (bytes(stat[5]).decode() if 5 in stat
                              else stat_names.get(stat.get(7), ""))
                        scope = _scope(op)
            if scope is not None:
                out[text] = scope
    return out


def read(path: str) -> Tuple[List[Span], List[Op], List[Op]]:
    """(host ``kta.*`` spans, device ops, device step programs) of one
    ``.xplane.pb``."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        data = f.read()
    scopes = op_scopes(data)
    spans: List[Span] = []
    ops: List[Op] = []
    steps: List[Op] = []
    thread = 0
    for plane in ProfileData.from_serialized_xspace(data).planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                thread += 1
                spans.extend(
                    Span(thread, e.name, float(e.start_ns),
                         float(e.start_ns) + float(e.duration_ns))
                    for e in line.events if e.name.startswith(PREFIX))
        elif DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend(
                        Op(e.name.split(" = ", 1)[0].lstrip("%"),
                           float(e.start_ns),
                           float(e.start_ns) + float(e.duration_ns),
                           scopes.get(e.name))
                        for e in line.events)
                elif line.name == MODULES_LINE:
                    steps.extend(
                        Op(e.name, float(e.start_ns),
                           float(e.start_ns) + float(e.duration_ns))
                        for e in line.events if STEP_MODULE.match(e.name))
    return spans, ops, steps


class _Union:
    """A union of intervals, for point queries."""

    def __init__(self, intervals: Iterable[Tuple[float, float]]):
        merged: List[List[float]] = []
        for a, b in sorted(intervals):
            if merged and a <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], b)
            else:
                merged.append([a, b])
        self.starts = [m[0] for m in merged]
        self.ends = [m[1] for m in merged]

    def covers(self, t: float) -> bool:
        i = bisect.bisect_right(self.starts, t) - 1
        return i >= 0 and t < self.ends[i]

    def intervals(self) -> List[Tuple[float, float]]:
        return list(zip(self.starts, self.ends))


def _outside_scans(drive: List[Span]) -> List[Tuple[float, float]]:
    """From the end of each ``kta.finalize`` to the start of the next
    stage on the drive thread: between two scans."""
    out = []
    stages = sorted((s for s in drive if s.name != SCAN
                     and not s.name.startswith("kta.finalize.")),
                    key=lambda s: s.start_ns)
    for prev, nxt in zip(stages, stages[1:]):
        if prev.name == "kta.finalize" and nxt.start_ns > prev.end_ns:
            out.append((prev.end_ns, nxt.start_ns))
    return out


def attribute(spans: Sequence[Span], ops: Sequence[Op],
              steps: Sequence[Op] = ()) -> Optional[Attribution]:
    """None where the trace holds no drive-thread stage."""
    stage_names = {PREFIX + s for s in DRIVE_STAGES} | {INGEST}
    drive_threads = {s.thread for s in spans if s.name in stage_names}
    if not drive_threads:
        return None
    drive = [s for s in spans if s.thread in drive_threads]
    ingest = [s for s in spans if s.thread not in drive_threads]
    lo = min(s.start_ns for s in drive)
    hi = max(s.end_ns for s in drive)

    def union(names, among) -> _Union:
        return _Union((s.start_ns, s.end_ns) for s in among
                      if s.name in names
                      or any(s.name.startswith(n + ".") for n in names))

    rule1 = [(c, union((PREFIX + c,), drive)) for c in DRIVE_STAGES]
    in_ingest = union((INGEST,), drive)
    decoding = union(DECODE_SPANS, ingest)
    fetching = union(FETCH_SPANS, ingest)
    scans = union((SCAN,), spans)
    outside = _Union(_outside_scans(drive))

    def category(t: float) -> str:
        for c, u in rule1:
            if u.covers(t):
                return c
        if in_ingest.covers(t):
            if decoding.covers(t):
                return "decode_pack"
            if fetching.covers(t):
                return "fetch"
            return "ingest_other"
        if outside.covers(t) and not scans.covers(t):
            return "outside_scan"
        return "unattributed"

    busy = _Union((max(o.start_ns, lo), min(o.end_ns, hi)) for o in ops
                  if o.end_ns > lo and o.start_ns < hi)
    idle: List[Tuple[float, float]] = []
    t = lo
    for a, b in busy.intervals():
        if a > t:
            idle.append((t, a))
        t = max(t, b)
    if hi > t:
        idle.append((t, hi))
    cuts = sorted({p for s in spans for p in (s.start_ns, s.end_ns)
                   if lo < p < hi})
    idle_s: Dict[str, float] = {c: 0.0 for c in CATEGORIES}
    for a, b in idle:
        i = bisect.bisect_right(cuts, a)
        edges = [a] + cuts[i:bisect.bisect_left(cuts, b)] + [b]
        for x, y in zip(edges, edges[1:]):
            idle_s[category((x + y) / 2)] += (y - x) / 1e9
    scope_s: Dict[str, float] = defaultdict(float)
    for o in ops:
        if o.scope is not None:
            scope_s[o.scope] += (o.end_ns - o.start_ns) / 1e9
    dispatches = sorted(s.start_ns for s in drive
                        if s.name == "kta.dispatch")
    offsets = []
    for st in steps:
        if dispatches and lo <= st.start_ns < hi:
            i = bisect.bisect_right(dispatches, st.start_ns)
            near = min(dispatches[max(i - 1, 0):i + 1],
                       key=lambda d: abs(st.start_ns - d))
            offsets.append((st.start_ns - near) / 1e9)
    span_s = (hi - lo) / 1e9
    busy_s = sum(b - a for a, b in busy.intervals()) / 1e9
    return Attribution(span_s, busy_s, idle_s, dict(scope_s), offsets)


def attribute_file(path: str) -> Optional[Attribution]:
    return attribute(*read(path))


def table(a: Attribution) -> str:
    """The whole attribution, one line each, for standard error."""
    lines = [f"host_spans: span {a.span_s:.6f}s, device busy "
             f"{a.busy_s:.6f}s ({a.busy_s / a.span_s:.6f})"]
    for c in CATEGORIES:
        lines.append(f"host_spans: idle {c}: {a.idle_s[c]:.6f}s "
                     f"({a.share(c):.6f})")
    for scope, s in sorted(a.scope_s.items()):
        lines.append(f"host_spans: device {scope}: {s:.6f}s")
    offs = sorted(a.step_offsets_s)
    lines.append(f"host_spans: {a.steps_led} of {len(offs)} device steps "
                 f"start within {DISPATCH_LEAD_NS / 1e6:.0f} ms after a "
                 "kta.dispatch starts")
    if offs:
        lines.append(
            "host_spans: device step start minus nearest kta.dispatch "
            f"start: min {offs[0] * 1e3:.6f} ms, median "
            f"{offs[len(offs) // 2] * 1e3:.6f} ms, max "
            f"{offs[-1] * 1e3:.6f} ms")
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    """``python3 -m benchmark.host_spans <trace.xplane.pb>``"""
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(main.__doc__, file=sys.stderr)
        return 2
    a = attribute_file(argv[0])
    if a is None:
        print("host_spans: no drive-thread stage in the trace",
              file=sys.stderr)
        return 1
    print(table(a), file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
