"""Reduction of a profiler trace to device time, idle share and a breakdown.

``extract`` reads the ``.xplane.pb`` of a traced run with
``jax.profiler.ProfileData``: on every ``/device:TPU:<n>`` plane the ``XLA
Ops`` line (one event per operation run on the chip) and the ``XLA Modules``
line (one event per program run), and on the host planes the benchmark's
own ``bench.*`` spans (``jax.profiler.TraceAnnotation``).  ``reduce`` then
works on those plain events, so a recorded cut of a trace tests it.

- busy: the union of the operation intervals of a device inside the
  window, averaged over the devices; idle share is one minus busy over the
  window.  The window is the ``bench.window`` span.
- program time: the summed durations of the program runs whose name holds
  a given part (``prefill``).
- top operations: device seconds summed by operation name (its first
  ``OP_NAME_CHARS`` characters).
- idle gaps: each gap between busy intervals is named by the innermost
  ``bench.*`` span open on the host at its midpoint, and the seconds are
  summed by name.
"""

from __future__ import annotations

import glob
import json
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]  # name, start_s, duration_s

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
# spans of other threads: open while the engine thread does something else
OFF_THREAD_SPANS = ("bench.fetch",)
# an operation is named by the head of its HLO text (name, first shapes);
# the whole text runs to thousands of characters
OP_NAME_CHARS = 100


@dataclass
class TraceEvents:
    ops: Dict[str, List[Event]] = field(default_factory=dict)  # by device plane
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


def extract(path: str) -> TraceEvents:
    from jax.profiler import ProfileData

    out = TraceEvents()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                dest = {"XLA Ops": out.ops, "XLA Modules": out.modules}.get(line.name)
                if dest is not None:
                    dest.setdefault(plane.name, []).extend(
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


def reduce(ev: TraceEvents, programs: Sequence[str] = ("prefill",), top: int = 10) -> Reduction:
    windows = [(s, s + d) for n, s, d in ev.spans if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"{len(windows)} {WINDOW_SPAN} spans in the trace")
    lo, hi = windows[0]
    inner = [e for e in ev.spans if e[0] != WINDOW_SPAN]
    busy_total = 0.0
    op_s: Dict[str, float] = {}
    gaps: Dict[str, float] = {}
    for plane, ops in ev.ops.items():
        busy = _union(_clip([(s, s + d) for _, s, d in ops], lo, hi))
        busy_total += sum(b - a for a, b in busy)
        for name, s, d in ops:
            if s < hi and s + d > lo:
                name = name[:OP_NAME_CHARS]
                op_s[name] = op_s.get(name, 0.0) + d
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
