"""From a profiler trace (``.xplane.pb``) to busy/idle time, per-program device
time, the operations that took most time and the longest idle gaps.

The reduction works on a flat list of events ``(plane, line, name, start_ns,
duration_ns)`` so that it can be checked on a small recorded trace kept as JSON
beside this file (``testdata/``), against hand-counted values. ``load`` reads an
``.xplane.pb`` with nothing but JAX (``jax.profiler.ProfileData``).

What the TPU's planes look like (seen on a v5e, jax 0.9): one plane per chip
named ``/device:TPU:<n>`` with the lines ``XLA Modules`` (one event per executed
program, named ``jit_<function>(<fingerprint>)``), ``XLA Ops`` (one event per HLO
operation) and ``Steps``; host threads live on ``/host:CPU``, where
``jax.profiler.TraceAnnotation`` spans appear under their own names.
"""

from __future__ import annotations

import dataclasses
import json
import re
from typing import Any, Dict, Iterable, List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: int
    duration_ns: int

    @property
    def end_ns(self) -> int:
        return self.start_ns + self.duration_ns


_LAYOUT = re.compile(r"\{[^{}]*\}")


def short_name(name: str, limit: int = 96) -> str:
    """An HLO operation's event name is its whole text; keep ``%name = shape kind``
    without layouts and operands."""
    if " = " not in name:
        return name[:limit]
    head, _, rest = name.partition(" = ")
    rest = _LAYOUT.sub("", rest.split("(%", 1)[0] if rest.startswith("(") else rest)
    match = re.match(r"^(\(?[^ ]*\)?(?:, [^ ]*)*\)?) ([a-z\-]+)\(", rest)
    text = f"{head} = {match.group(2)} {match.group(1)}" if match else f"{head} = {rest}"
    return text[:limit]


def load(path: str, host_names: Sequence[str] = ()) -> List[Event]:
    """Events of every device plane, plus host events whose name is in
    ``host_names`` (host planes hold millions of events nobody reads)."""
    if path.endswith(".json"):
        with open(path) as f:
            return [Event(*row) for row in json.load(f)["events"]]
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    wanted = set(host_names)
    out: List[Event] = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and not wanted:
            continue
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if device or ev.name in wanted:
                    name = short_name(ev.name) if line.name == OPS_LINE else ev.name
                    out.append(Event(plane.name, line.name, name, int(ev.start_ns), int(ev.duration_ns)))
    return out


def device_ids(events: Iterable[Event]) -> List[str]:
    return sorted({e.plane for e in events if DEVICE_PLANE.match(e.plane)})


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[Tuple[int, int]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def busy_intervals(events: Iterable[Event], plane: str) -> List[Tuple[int, int]]:
    """Union of the intervals in which an operation ran on ``plane``."""
    ops = [(e.start_ns, e.end_ns) for e in events if e.plane == plane and e.line == OPS_LINE and e.duration_ns > 0]
    if not ops:  # a trace without the per-op line still has the programs
        ops = [(e.start_ns, e.end_ns) for e in events if e.plane == plane and e.line == MODULES_LINE]
    return _union(ops)


def busy_seconds(events: Sequence[Event]) -> float:
    """Seconds in which an operation ran, averaged over the chips in the trace."""
    planes = device_ids(events)
    if not planes:
        return 0.0
    total = sum(end - start for p in planes for start, end in busy_intervals(events, p))
    return total / len(planes) / 1e9


def traced_seconds(events: Sequence[Event]) -> float:
    """Length of the traced window on the device's own clock: from the first device event's start to the last
    one's end, over all chips. The host's clock around ``start_trace``/``stop_trace`` also counts the profiler's
    start-up, in which the device runs and nothing is recorded, so an idle share taken over it reads too high."""
    spans = [(e.start_ns, e.end_ns) for e in events if DEVICE_PLANE.match(e.plane) and e.line in (OPS_LINE, MODULES_LINE)]
    if not spans:
        return 0.0
    return (max(end for _, end in spans) - min(start for start, _ in spans)) / 1e9


def program_name(event_name: str) -> str:
    """``jit_decode_steps(1234567)`` -> ``decode_steps``."""
    name = event_name.split("(", 1)[0].strip()
    return name[4:] if name.startswith("jit_") else name


def _clipped(modules: List[Event]) -> List[Event]:
    """Module events cut by the trace's edges: the first and the last event of a chip's line, where that program
    ran three times or more there and the edge event is under nine tenths of the median of its other runs. A trace
    that starts or stops inside a program records only the part it saw."""
    import statistics

    out: List[Event] = []
    for plane in {e.plane for e in modules}:
        line = sorted((e for e in modules if e.plane == plane), key=lambda e: e.start_ns)
        for edge in {line[0], line[-1]}:
            others = [e.duration_ns for e in line if e.name == edge.name and e is not edge]
            if len(others) >= 2 and edge.duration_ns < 0.9 * statistics.median(others):
                out.append(edge)
    return out


def program_seconds(events: Iterable[Event]) -> Dict[str, Dict[str, float]]:
    """Per program (XLA module), whole executions and their device seconds, summed over chips and divided by the
    number of chips; executions cut by the trace's edges are left out."""
    events = list(events)
    planes = device_ids(events) or [""]
    modules = [e for e in events if e.line == MODULES_LINE]
    cut = set(map(id, _clipped(modules)))
    out: Dict[str, Dict[str, float]] = {}
    for e in modules:
        if id(e) in cut:
            continue
        entry = out.setdefault(program_name(e.name), {"calls": 0.0, "seconds": 0.0})
        entry["calls"] += 1.0 / len(planes)
        entry["seconds"] += e.duration_ns / 1e9 / len(planes)
    return out


def top_operations(events: Iterable[Event], n: int = 10) -> List[List[Any]]:
    """The device operations that took most time, ``[[name, seconds], ...]``
    (per chip: summed over chips, divided by their number)."""
    events = list(events)
    planes = device_ids(events) or [""]
    totals: Dict[str, float] = {}
    for e in events:
        if e.line == OPS_LINE:
            totals[e.name] = totals.get(e.name, 0.0) + e.duration_ns / 1e9 / len(planes)
    return [[name, seconds] for name, seconds in sorted(totals.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(events: Sequence[Event], host_spans: Sequence[str] = (), n: int = 10) -> List[List[Any]]:
    """The longest idle gaps of the first chip, ``[[what, seconds], ...]``. A gap
    is named for the host span (of ``host_spans``) that covers most of it, or
    for the programs on either side of it where no span on the profiler's clock does."""
    planes = device_ids(events)
    if not planes:
        return []
    busy = busy_intervals(events, planes[0])
    modules = sorted((e for e in events if e.plane == planes[0] and e.line == MODULES_LINE), key=lambda e: e.start_ns)
    spans = [e for e in events if e.name in set(host_spans) and not DEVICE_PLANE.match(e.plane)]

    def name_for(start: int, end: int) -> str:
        best, best_overlap = "", 0
        for s in spans:
            overlap = min(end, s.end_ns) - max(start, s.start_ns)
            if overlap > best_overlap:
                best, best_overlap = s.name, overlap
        if best and best_overlap * 2 >= end - start:
            return f"host:{best}"
        before = next((program_name(m.name) for m in reversed(modules) if m.end_ns <= start + 1), "start")
        after = next((program_name(m.name) for m in modules if m.start_ns >= end - 1), "end")
        return f"between:{before}>{after}"

    gaps = [(b_start - a_end, a_end, b_start) for (_, a_end), (b_start, _) in zip(busy, busy[1:]) if b_start > a_end]
    totals: Dict[str, float] = {}
    for length, start, end in sorted(gaps, reverse=True)[:2000]:
        key = name_for(start, end)
        totals[key] = totals.get(key, 0.0) + length / 1e9
    return [[k, v] for k, v in sorted(totals.items(), key=lambda kv: -kv[1])[:n]]


def summarize(events: Sequence[Event], host_spans: Sequence[str] = ()) -> Dict[str, Any]:
    return {
        "chips": len(device_ids(events)),
        "busy_s": busy_seconds(events),
        "window_s": traced_seconds(events),
        "programs": program_seconds(events),
        "device_ops": top_operations(events),
        "idle_gaps": idle_gaps(events, host_spans),
    }
