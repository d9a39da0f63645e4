"""Reduction of a profiler trace to device busy time, per-op time and idle
gaps named by the harness's host spans.

The trace is the Chrome-trace JSON (``perfetto_trace.json.gz``) that
``jax.profiler.trace(..., create_perfetto_trace=True)`` writes:

- a device is a profiler *process* whose name marks it as one
  (``/device:TPU:0``); the host process is ``/host:CPU`` and carries the
  Python threads, with the harness's ``TraceAnnotation`` spans on them;
- a device shows the same work on several *lines*: on a TPU "XLA Modules"
  spans each program and "XLA Ops" each op inside it. Busy time is the
  **union** of the intervals on the "XLA Ops" line, each instant counted
  once; a device without such a line contributes the union over all of
  its lines.
"""

from __future__ import annotations

import dataclasses
import gzip
import json
from pathlib import Path

DEVICE_MARKERS = ("/device:tpu", "/device:gpu")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint, sorted ones."""
    out: list[list[float]] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(s, e) for s, e in out]


def union_length(intervals: list[tuple[float, float]]) -> float:
    return sum(e - s for s, e in union(intervals))


def load_events(trace_dir: str | Path) -> list[dict]:
    """Events of the newest ``perfetto_trace.json.gz`` under ``trace_dir``."""
    files = sorted(Path(trace_dir).rglob("perfetto_trace.json.gz"))
    if not files:
        raise FileNotFoundError(f"no perfetto_trace.json.gz under {trace_dir}")
    with gzip.open(files[-1], "rt", encoding="utf-8", errors="replace") as fh:
        events = json.load(fh).get("traceEvents")
    if not isinstance(events, list):
        raise ValueError(f"{files[-1]} holds no traceEvents list")
    return events


@dataclasses.dataclass
class Span:
    name: str
    start: float          # microseconds, the trace's clock
    end: float
    args: dict
    tid: object


@dataclasses.dataclass
class Trace:
    """A trace split into device op events, device module events and host
    spans. Times are microseconds on the trace's clock."""

    ops: dict[object, list[dict]]       # device pid -> "XLA Ops" events
    modules: dict[object, list[dict]]   # device pid -> "XLA Modules" events
    spans: list[Span]                   # host complete events

    @classmethod
    def from_events(cls, events: list) -> "Trace":
        devices, host = set(), set()
        line_names: dict[tuple, str] = {}
        for ev in events:
            if not isinstance(ev, dict) or ev.get("ph") != "M":
                continue
            name = str((ev.get("args") or {}).get("name", ""))
            if ev.get("name") == "process_name":
                if any(m in name.lower() for m in DEVICE_MARKERS):
                    devices.add(ev.get("pid"))
                elif name.lower().startswith("/host"):
                    host.add(ev.get("pid"))
            elif ev.get("name") == "thread_name":
                line_names[(ev.get("pid"), ev.get("tid"))] = name
        per_line: dict[tuple, list[dict]] = {}
        spans: list[Span] = []
        for ev in events:
            if not isinstance(ev, dict) or ev.get("ph") != "X":
                continue
            pid = ev.get("pid")
            if pid in devices:
                per_line.setdefault((pid, ev.get("tid")), []).append(ev)
            elif pid in host:
                start = float(ev["ts"])
                spans.append(Span(str(ev.get("name", "")), start,
                                  start + float(ev.get("dur", 0.0)),
                                  ev.get("args") or {}, ev.get("tid")))
        ops: dict[object, list[dict]] = {}
        modules: dict[object, list[dict]] = {}
        for pid in devices:
            lines = [key for key in per_line if key[0] == pid]
            named = [key for key in lines if line_names.get(key) == OPS_LINE]
            for key in named or lines:
                ops.setdefault(pid, []).extend(per_line[key])
            for key in lines:
                if line_names.get(key) == MODULES_LINE:
                    modules.setdefault(pid, []).extend(per_line[key])
        return cls(ops=ops, modules=modules, spans=spans)

    # -- the window ---------------------------------------------------------
    def span(self, name: str) -> Span:
        """The longest host span of this name (the traced session)."""
        found = [s for s in self.spans if s.name == name]
        if not found:
            raise ValueError(f"the trace holds no host span {name!r}")
        return max(found, key=lambda s: s.end - s.start)

    def busy(self, pid, start: float, end: float) -> list[tuple[float, float]]:
        """Disjoint busy intervals of one device, clipped to the window."""
        clipped = []
        for ev in self.ops.get(pid, ()):
            s = float(ev["ts"])
            e = s + float(ev.get("dur", 0.0))
            s, e = max(s, start), min(e, end)
            if e > s:
                clipped.append((s, e))
        return union(clipped)

    def busy_s(self, start: float, end: float) -> float:
        """Busy seconds in the window, averaged over the devices."""
        if not self.ops:
            return 0.0
        total = sum(union_length(self.busy(pid, start, end))
                    for pid in self.ops)
        return total / len(self.ops) * 1e-6

    def op_seconds(self, start: float, end: float) -> dict[str, float]:
        """Seconds per op name in the window, summed over devices."""
        out: dict[str, float] = {}
        for events in self.ops.values():
            for ev in events:
                s = float(ev["ts"])
                if start <= s < end:
                    name = str(ev.get("name", ""))
                    out[name] = (out.get(name, 0.0)
                                 + float(ev.get("dur", 0.0)) * 1e-6)
        return out

    def events_named(self, start: float, end: float, match,
                     line: str = OPS_LINE) -> list[dict]:
        """Device events in the window whose name satisfies ``match``."""
        source = self.ops if line == OPS_LINE else self.modules
        return [ev for events in source.values() for ev in events
                if start <= float(ev["ts"]) < end
                and match(str(ev.get("name", "")))]

    # -- idle gaps ----------------------------------------------------------
    def gaps(self, start: float, end: float) -> list[tuple[float, float]]:
        """Idle intervals of the first device inside the window."""
        if not self.ops:
            return [(start, end)]
        pid = sorted(self.ops, key=str)[0]
        gaps, cursor = [], start
        for s, e in self.busy(pid, start, end):
            if s > cursor:
                gaps.append((cursor, s))
            cursor = max(cursor, e)
        if end > cursor:
            gaps.append((cursor, end))
        return gaps

    def host_activity(self, t: float, names: tuple[str, ...]) -> str:
        """The innermost host span among ``names`` that covers ``t``."""
        best = None
        for s in self.spans:
            if s.name in names and s.start <= t <= s.end:
                if best is None or s.end - s.start < best.end - best.start:
                    best = s
        if best is None:
            return "outside_spans"
        return best.name

    def longest_gaps(self, start: float, end: float, names: tuple[str, ...],
                     limit: int = 10) -> list[list]:
        """The ``limit`` longest idle gaps, each named by the host span that
        covers its middle: ``[[name, seconds], ...]``."""
        gaps = sorted(self.gaps(start, end), key=lambda g: g[0] - g[1])[:limit]
        return [[self.host_activity((s + e) / 2.0, names), (e - s) * 1e-6]
                for s, e in gaps]
