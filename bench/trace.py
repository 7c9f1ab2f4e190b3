"""Reduction of a profiler trace to device time, idle share and a breakdown.

``extract`` reads the ``.xplane.pb`` of a traced run with
``jax.profiler.ProfileData``: on every ``/device:TPU:<n>`` plane the ``XLA
Ops`` line (one event per operation run on the chip) and the ``XLA Modules``
line (one event per program run), and on the host planes the benchmark's
own ``bench.*`` spans (``jax.profiler.TraceAnnotation``).  Each operation
also keeps its ``jax.named_scope`` path: the ``tf_op`` stat of its event
metadata (``jit(prefill_rows)/attention/dot_general:``), which
``ProfileData`` does not show and ``op_scopes`` reads from the file's
protobuf.  ``reduce`` then works on those plain events, so a recorded cut
of a trace tests it.

- busy: the union of the operation intervals of a device inside the
  window, averaged over the devices; idle share is one minus busy over the
  window.  The window is the ``bench.window`` span.
- program time: the summed durations of the program runs whose name holds
  a given part (``prefill``).
- scope time: the summed durations of the operations inside a run of
  the prefill program whose scope path holds a given scope as a whole
  component (``attention`` in ``.../attention/dot_general:``).
- top operations: device seconds summed by operation name (its first
  ``OP_NAME_CHARS`` characters).
- idle gaps: each gap between busy intervals is named by the innermost
  ``bench.*`` span open on the host at its midpoint, and the seconds are
  summed by name.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]  # name, start_s, duration_s
# an operation: name, start_s, duration_s and its scope path ("" where it has
# none; a cut recorded before operations kept it holds the first three alone)
OpEvent = Tuple[str, float, float, str]

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
# spans of other threads: open while the engine thread does something else
OFF_THREAD_SPANS = ("bench.fetch",)
# an operation is named by the head of its HLO text (name, first shapes);
# the whole text runs to thousands of characters
OP_NAME_CHARS = 100
SCOPE_STAT = "tf_op"  # the event metadata's stat that holds the scope path


@dataclass
class TraceEvents:
    ops: Dict[str, List[OpEvent]] = field(default_factory=dict)  # by device plane
    modules: Dict[str, List[Event]] = field(default_factory=dict)
    spans: List[Event] = field(default_factory=list)

    def to_json(self) -> Dict:
        return {"ops": self.ops, "modules": self.modules, "spans": self.spans}

    @classmethod
    def from_json(cls, d: Dict) -> "TraceEvents":
        tup = lambda evs: [tuple(e) for e in evs]  # noqa: E731
        return cls({k: tup(v) for k, v in d["ops"].items()},
                   {k: tup(v) for k, v in d["modules"].items()}, tup(d["spans"]))


@dataclass
class Reduction:
    window_s: float
    busy_s: float  # averaged over devices
    devices: int
    program_s: Dict[str, float]
    scope_s: Dict[str, float]  # of the prefill program's operations, by scope
    top_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"{len(found)} .xplane.pb files under {trace_dir}")
    return found[0]


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf: bytes, lo: int, hi: int):
    """``(number, value)`` of each field of the protobuf message in
    ``buf[lo:hi]``: an int for a varint, ``(start, end)`` for the rest."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            val, i = (i, i + n), i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            val, i = (i, i + n), i + n
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, val


def op_scopes(path: str) -> Dict[str, Dict[str, str]]:
    """Scope path of each operation, by device plane and operation name,
    from the ``XSpace`` protobuf (``tsl/profiler/protobuf/xplane.proto``):
    ``XSpace.planes`` (1) -> ``XPlane.name`` (2), ``event_metadata`` (4:
    ``XEventMetadata`` name 2, stats 5) and ``stat_metadata`` (5:
    ``XStatMetadata`` id 1, name 2); an ``XStat`` holds its metadata id (1)
    and a string (5) or a reference to a stat metadata's name (7).  Lines,
    the bulk of the file, are skipped unread."""
    with open(path, "rb") as f:
        buf = f.read()
    text = lambda v: buf[v[0]:v[1]].decode(errors="replace")  # noqa: E731
    out: Dict[str, Dict[str, str]] = {}
    for num, plane in _fields(buf, 0, len(buf)):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for n, v in _fields(buf, *plane):
            if n == 2:
                name = text(v)
            elif n == 4:
                events.append(v)
            elif n == 5:
                meta = dict(f for k, entry in _fields(buf, *v) if k == 2
                            for f in _fields(buf, *entry))
                stat_names[meta.get(1, 0)] = text(meta[2]) if 2 in meta else ""
        if not name.startswith("/device:TPU:"):
            continue
        scopes = out.setdefault(name, {})
        for entry in events:
            for k, meta in _fields(buf, *entry):
                if k != 2:
                    continue
                op, scope = "", ""
                for n, v in _fields(buf, *meta):
                    if n == 2:
                        op = text(v)
                    elif n == 5:
                        stat = dict(_fields(buf, *v))
                        if stat_names.get(stat.get(1)) == SCOPE_STAT:
                            scope = text(stat[5]) if 5 in stat else stat_names.get(stat.get(7), "")
                if scope:
                    scopes[op] = scope
    return out


def extract(path: str) -> TraceEvents:
    from jax.profiler import ProfileData

    out = TraceEvents()
    scopes = op_scopes(path)
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            where = scopes.get(plane.name, {})
            for line in plane.lines:
                if line.name == "XLA Ops":
                    out.ops.setdefault(plane.name, []).extend(
                        (ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9,
                         where.get(ev.name, "")) for ev in line.events)
                elif line.name == "XLA Modules":
                    out.modules.setdefault(plane.name, []).extend(
                        (ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                        for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out.spans.extend((ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                                 for ev in line.events if ev.name.startswith(SPAN_PREFIX))
    return out


def _union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], b))
        else:
            merged.append((a, b))
    return merged


def _clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def _innermost(spans: Sequence[Event], t: float) -> str:
    best: Optional[Event] = None
    for ev in spans:
        name, start, dur = ev
        if name in OFF_THREAD_SPANS or not (start <= t < start + dur):
            continue
        if best is None or start >= best[1]:
            best = ev
    return best[0] if best else "host"


def _in_runs(runs: Sequence[Tuple[float, float]], t: float) -> bool:
    """Whether ``t`` lies in one of the sorted, disjoint ``runs``."""
    i = bisect.bisect_right(runs, (t, float("inf"))) - 1
    return i >= 0 and t < runs[i][1]


def reduce(ev: TraceEvents, programs: Sequence[str] = ("prefill",), scopes: Sequence[str] = (),
           top: int = 10) -> Reduction:
    windows = [(s, s + d) for n, s, d in ev.spans if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"{len(windows)} {WINDOW_SPAN} spans in the trace")
    lo, hi = windows[0]
    inner = [e for e in ev.spans if e[0] != WINDOW_SPAN]
    busy_total = 0.0
    op_s: Dict[str, float] = {}
    gaps: Dict[str, float] = {}
    scope_s = {sc: 0.0 for sc in scopes}
    for plane, ops in ev.ops.items():
        busy = _union(_clip([(s, s + d) for _, s, d, *_ in ops], lo, hi))
        busy_total += sum(b - a for a, b in busy)
        runs = sorted((s, s + d) for name, s, d in ev.modules.get(plane, [])
                      if "prefill" in name and lo <= s < hi)
        for name, s, d, *path in ops:
            if s < hi and s + d > lo:
                name = name[:OP_NAME_CHARS]
                op_s[name] = op_s.get(name, 0.0) + d
            if scopes and path and path[0] and _in_runs(runs, s):
                parts = path[0].split("/")
                for sc in scopes:
                    if sc in parts:
                        scope_s[sc] += d
        edges = [lo] + [x for ab in busy for x in ab] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                name = _innermost(inner, (a + b) / 2)
                gaps[name] = gaps.get(name, 0.0) + (b - a)
    if not op_s:
        raise ValueError("no device operation inside the window: the trace holds no "
                         f"device plane the reduction knows ({sorted(ev.ops) or 'none'})")
    n = len(ev.ops)
    program_s = {p: sum(d for mods in ev.modules.values() for name, s, d in mods
                        if p in name and lo <= s < hi) / n
                 for p in programs}
    return Reduction(
        window_s=hi - lo,
        busy_s=busy_total / n,
        devices=len(ev.ops),
        program_s=program_s,
        scope_s={sc: v / n for sc, v in scope_s.items()},
        top_ops=sorted(((k, v / n) for k, v in op_s.items()), key=lambda kv: -kv[1])[:top],
        idle_gaps=sorted(((k, v / n) for k, v in gaps.items()), key=lambda kv: -kv[1])[:top],
    )


def summary(path: str, per_line: int = 3) -> str:
    """Planes, lines and a few events of a trace: for reading one by hand."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  line {line.name!r}: {len(evs)} events")
            for e in evs[:per_line] + evs[-1:]:
                out.append(f"    {e.name!r} start {e.start_ns} ns, {e.duration_ns} ns")
    return "\n".join(out)


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description="Summarise or cut a profiler trace.")
    ap.add_argument("xplane")
    ap.add_argument("--cut", help="write the extracted events of the window to this JSON file")
    ap.add_argument("--cut-seconds", type=float, default=0.5,
                    help="keep this many seconds from the start of the window")
    args = ap.parse_args()
    print(summary(args.xplane))
    if args.cut:
        ev = extract(args.xplane)
        (lo, dur), = [(s, d) for n, s, d in ev.spans if n == WINDOW_SPAN]
        hi = lo + min(dur, args.cut_seconds)
        keep = lambda evs: [e for e in evs if e[1] < hi and e[1] + e[2] > lo]  # noqa: E731
        spans = [e for e in keep(ev.spans) if e[0] != WINDOW_SPAN] + [(WINDOW_SPAN, lo, hi - lo)]
        cut = TraceEvents({k: keep(v) for k, v in ev.ops.items()},
                          {k: keep(v) for k, v in ev.modules.items()}, spans)
        with open(args.cut, "w") as f:
            json.dump(cut.to_json(), f)
