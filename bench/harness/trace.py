"""Reduction of a profiler trace to device busy time, program time and the
``breakdown`` of the result line.

``events(path)`` flattens an ``.xplane.pb`` into ``Event`` tuples; the
reduction works on those alone, so a test can feed it a small hand-made
list. Device planes are those named ``/device:<platform>:<n>``; on each,
busy time is the union of the intervals of the events on its op line
(``XLA Ops``), and a program's time is the sum of its events on the module
line (``XLA Modules``). Idle gaps between busy intervals are labelled with
the benchmark's host span (``bench.*``) that covers the gap's midpoint.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, NamedTuple, Optional, Tuple

OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
HOST_PREFIX = "bench."


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float


def find_xplane(directory: str) -> str:
    hits = sorted(glob.glob(os.path.join(directory, "plugins", "profile",
                                         "*", "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return hits[-1]


def _is_device(plane: str) -> bool:
    return plane.startswith("/device:") and not plane.startswith("/device:CPU")


def events(path: str) -> List[Event]:
    """Every device event of the op and module lines, and every host event
    named ``bench.*``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        dev = _is_device(plane.name)
        for line in plane.lines:
            if dev and line.name not in (OP_LINE, MODULE_LINE):
                continue
            for e in line.events:
                if dev or e.name.startswith(HOST_PREFIX):
                    out.append(Event(plane.name, line.name, e.name,
                                     float(e.start_ns), float(e.duration_ns)))
    return out


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


@dataclass
class TraceSummary:
    busy_s: float                 # mean over device planes
    window_s: float
    devices: int
    programs: Dict[str, Tuple[float, int]] = field(default_factory=dict)
    top_ops: List[Tuple[str, float]] = field(default_factory=list)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)

    def breakdown(self) -> dict:
        return {"device_ops": [list(x) for x in self.top_ops],
                "idle_gaps": [list(x) for x in self.idle_gaps]}

    @property
    def program_s(self) -> float:
        """Device seconds of every program in the trace, over the chips."""
        return sum(t for t, _ in self.programs.values()) / max(1, self.devices)


def _label(mid: float, spans: List[Tuple[float, float, str]]) -> str:
    best = None
    for s, e, name in spans:
        if s <= mid <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "no bench span"


def reduce(evs: List[Event], window_s: float, top: int = 10) -> TraceSummary:
    """Busy time, program times, top ops and labelled idle gaps."""
    by_plane: Dict[str, List[Event]] = defaultdict(list)
    spans = []
    for e in evs:
        if _is_device(e.plane):
            by_plane[e.plane].append(e)
        elif e.name.startswith(HOST_PREFIX):
            spans.append((e.start_ns, e.start_ns + e.dur_ns, e.name))
    programs: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0])
    ops: Dict[str, float] = defaultdict(float)
    busy, gaps = [], []
    for plane, pe in sorted(by_plane.items()):
        op_iv = []
        for e in pe:
            if e.line == MODULE_LINE:
                programs[e.name][0] += e.dur_ns * 1e-9
                programs[e.name][1] += 1
            else:
                ops[e.name] += e.dur_ns * 1e-9
                op_iv.append((e.start_ns, e.start_ns + e.dur_ns))
        u = _union(op_iv)
        busy.append(sum(e - s for s, e in u) * 1e-9)
        for (_, e0), (s1, _) in zip(u, u[1:]):
            gaps.append((s1 - e0, (s1 + e0) / 2))
    gaps.sort(reverse=True)
    n = max(1, len(by_plane))
    return TraceSummary(
        busy_s=sum(busy) / n, window_s=window_s, devices=len(by_plane),
        programs={k: (v[0], int(v[1])) for k, v in programs.items()},
        top_ops=sorted(ops.items(), key=lambda kv: -kv[1])[:top],
        idle_gaps=[(_label(mid, spans), g * 1e-9) for g, mid in gaps[:top]])


def summarize(directory: str, window_s: float) -> Optional[TraceSummary]:
    return reduce(events(find_xplane(directory)), window_s)
