"""Per-layer metrics read from the program's own spans in a traced run.

While the profiler collects, every ``repro.obs.trace.span`` of the program
is also a host event of the profile, on the clock of the device ops. This
module reads the traced run's ``.xplane.pb`` (written by ``main.measure``
under ``bench_out/trace/<cell>``) for the spans named ``engine.*``,
``load.*`` and ``archive.*`` and the device op events, and reduces them:

    step_host_ms        mean of engine.step less its engine.readback child
    readback_wait_ms    mean engine.readback
    probe_step_ms       mean engine.step
    idle_in_engine_share  % of the device's idle time under an engine.* span
                        other than engine.readback
    engine_init_ms      mean per scale-out of archive.open +
                        engine.load_weights + engine.init_pool

The step means count only steps that served rows, those that hold an
engine.readback. Only spans that lie wholly inside the traced window
count. The drivers start and stop the profiler between steps and between
scale-outs, and the profiler keeps no span it did not see open and close,
so in a run every step and scale-out of the trace is whole. Each reduction returns ``None``
when its spans are absent: a program without them, or a trace with no
device plane (a CPU run, whose times are not the device's).
"""
from __future__ import annotations

import functools
from typing import Callable, List, Optional, Sequence, Tuple

from harness.trace import OP_LINE, Event, _is_device, _union, find_xplane

PREFIXES = ("engine.", "load.", "archive.")
STEP = "engine.step"
READBACK = "engine.readback"
INIT = ("archive.open", "engine.load_weights", "engine.init_pool")

Window = Tuple[float, float]
Intervals = List[Tuple[float, float]]


def _end(e: Event) -> float:
    return e.start_ns + e.dur_ns


@functools.lru_cache(maxsize=1)
def events(path: str) -> Tuple[Tuple[Event, ...], Optional[Window]]:
    """The program's spans and the device op events of a profile, and the
    traced window in the events' nanoseconds: from the start of the first
    of the program's spans to the end of the last (None without any). The
    profiler's own start and stop, while the program waits, lie outside."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        dev = _is_device(plane.name)
        for line in plane.lines:
            if dev and line.name != OP_LINE:
                continue
            for e in line.events:
                if dev or e.name.startswith(PREFIXES):
                    out.append(Event(plane.name, line.name, e.name,
                                     float(e.start_ns), float(e.duration_ns)))
    spans = [e for e in out if not _is_device(e.plane)]
    window = (min(e.start_ns for e in spans),
              max(_end(e) for e in spans)) if spans else None
    return tuple(out), window


def traced(run) -> Optional[Tuple[Sequence[Event], Window]]:
    """The run's events and window; None for a run with no profile, or
    whose profile has no device plane."""
    directory = run.cell.bench.parent / "bench_out" / "trace" / run.cell.name
    try:
        path = find_xplane(str(directory))
    except FileNotFoundError:
        return None
    evs, window = events(path)
    if window is None or not any(_is_device(e.plane) for e in evs):
        return None
    return evs, window


def reader(reduce: Callable[[Sequence[Event], Window], Optional[float]]):
    """``read(run)`` of a metric file from a reduction of the events."""
    def read(run) -> Optional[float]:
        t = traced(run)
        return None if t is None else reduce(*t)
    return read


def whole(evs: Sequence[Event], name: str, window: Window) -> List[Event]:
    """The host events named ``name`` that lie wholly inside ``window``."""
    t0, t1 = window
    return [e for e in evs if e.name == name and not _is_device(e.plane)
            and e.start_ns >= t0 and _end(e) <= t1]


def _inside(child: Event, parent: Event) -> bool:
    return (child.plane, child.line) == (parent.plane, parent.line) \
        and child.start_ns >= parent.start_ns and _end(child) <= _end(parent)


def mean_ms(evs: Sequence[Event], name: str,
            window: Window) -> Optional[float]:
    spans = whole(evs, name, window)
    return sum(e.dur_ns for e in spans) / len(spans) / 1e6 if spans else None


def served_steps(evs: Sequence[Event],
                 window: Window) -> List[Tuple[Event, float]]:
    """The window's engine.step spans that served rows, each with the
    nanoseconds of its engine.readback. A tick that admitted nothing from
    its queue leaves an engine.step with no readback: it is left out."""
    reads = whole(evs, READBACK, window)
    out = []
    for s in whole(evs, STEP, window):
        mine = [r.dur_ns for r in reads if _inside(r, s)]
        if mine:
            out.append((s, sum(mine)))
    return out


def step_host_ms(evs: Sequence[Event], window: Window) -> Optional[float]:
    """Mean over served engine.step of its duration less its
    engine.readback child: the host work the program does each step."""
    steps = served_steps(evs, window)
    if not steps:
        return None
    return sum(s.dur_ns - r for s, r in steps) / len(steps) / 1e6


def readback_wait_ms(evs: Sequence[Event], window: Window) -> Optional[float]:
    return mean_ms(evs, READBACK, window)


def probe_step_ms(evs: Sequence[Event], window: Window) -> Optional[float]:
    """Mean duration of the served engine.step spans."""
    steps = served_steps(evs, window)
    if not steps:
        return None
    return sum(s.dur_ns for s, _ in steps) / len(steps) / 1e6


def _complement(u: Intervals, t0: float, t1: float) -> Intervals:
    out, at = [], t0
    for s, e in u:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if at < t1:
        out.append((at, t1))
    return out


def _intersect(a: Intervals, b: Intervals) -> Intervals:
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _length(u: Intervals) -> float:
    return sum(e - s for s, e in u)


def idle_in_engine_share(evs: Sequence[Event],
                         window: Window) -> Optional[float]:
    """% of the device's idle time in the window that falls under an
    engine.* span other than engine.readback (the part of engine.step that
    its readback covers counts as readback). Averaged over device planes
    by summing their idle time."""
    t0, t1 = window
    names = {e.name for e in evs if e.name.startswith("engine.")} - {READBACK}
    spans = _union([(e.start_ns, _end(e)) for n in sorted(names)
                    for e in whole(evs, n, window)])
    if not spans:
        return None
    reads = _union([(e.start_ns, _end(e)) for e in whole(evs, READBACK, window)])
    engine = _intersect(spans, _complement(reads, t0, t1))
    idle = under = 0.0
    for plane in sorted({e.plane for e in evs if _is_device(e.plane)}):
        busy = _union([(max(e.start_ns, t0), min(_end(e), t1)) for e in evs
                       if e.plane == plane and e.line == OP_LINE
                       and e.start_ns < t1 and _end(e) > t0])
        gaps = _complement(busy, t0, t1)
        idle += _length(gaps)
        under += _length(_intersect(gaps, engine))
    return under / idle * 100.0 if idle > 0 else None


def engine_init_ms(evs: Sequence[Event], window: Window) -> Optional[float]:
    """Mean over the window's scale-outs of the summed archive.open,
    engine.load_weights and engine.init_pool: the engine's set-up outside
    LOAD's critical path. A scale-out runs from one archive.open to the
    next; one that lacks any of the three spans in the window is left out."""
    spans = sorted((e for n in INIT for e in whole(evs, n, window)),
                   key=lambda e: e.start_ns)
    groups: List[List[Event]] = []
    for e in spans:
        if e.name == INIT[0]:
            groups.append([e])
        elif groups:
            groups[-1].append(e)
    full = [g for g in groups if {e.name for e in g} == set(INIT)]
    if not full:
        return None
    return sum(e.dur_ns for g in full for e in g) / len(full) / 1e6
