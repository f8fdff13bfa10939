"""Structured trace spans exported as Chrome/Perfetto trace-event JSON.

The LOAD pipeline runs fetch, deserialize, and install on three distinct
threads (``restore._TemplatePipeline``); a reshard overlaps a DUAL window
with live serving.  Wall-clock reports cannot show *where* that time
overlaps — a timeline can.  This module collects spans with explicit
thread attribution and writes the Chrome trace-event JSON object format
(``{"traceEvents": [...]}``) that both ``chrome://tracing`` and
https://ui.perfetto.dev load directly.

Discipline mirrors :mod:`repro.obs.metrics`: a single module-global read
(``_TRACING``) gates every emission, so instrumented code can leave span
context managers in place permanently.  :class:`span` *always* measures
its duration (callers such as ``restore.foundry_load`` reuse
``span.seconds`` to fill the legacy report dataclasses — one measurement,
two consumers) but only records an event when tracing is on.

A span has a second sink: while a JAX profiler session collects
(``jax.profiler.trace``, ``start_trace`` or a ``start_server`` capture),
it also opens a ``jax.profiler.TraceAnnotation`` of the same name, with its
args as metadata.  The profiler stamps it on the clock of the device ops
in the same ``.xplane.pb``, so TensorBoard or Perfetto show the program's
spans above the device timeline and a device idle gap can be put down to
the code the host was running.  With no session collecting, the sink
costs one ``is_enabled()`` call per span.  :func:`complete` and
:func:`instant` go to the JSON collector only.

Event vocabulary used here (a small, valid subset of the format):

- ``"X"`` complete events — spans with ``ts``/``dur`` in microseconds
- ``"i"`` instant events — crashes, cutovers, shed decisions
- ``"M"`` metadata events — ``thread_name`` / ``process_name``

Stdlib only, apart from ``jax.profiler``, imported on the first span;
must not import from the rest of ``repro``.
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Dict, Iterator, List, Optional, Union

__all__ = [
    "TraceCollector",
    "span",
    "instant",
    "complete",
    "set_thread_name",
    "start",
    "stop",
    "active",
    "collector",
    "save",
    "validate_trace",
]

# One global read on the hot path.  Flipped only by start()/stop().
_TRACING = False

#: default cap on buffered events; beyond it events are counted as
#: dropped rather than growing memory without bound
MAX_EVENTS = 500_000

_VALID_PHASES = {"X", "B", "E", "i", "I", "M", "C"}

# jax.profiler.TraceAnnotation, imported by the first span's _collecting()
_Annotation: Any = None


def _collecting() -> bool:
    """Whether a profiler session collects. The first call imports
    ``jax.profiler`` and rebinds this name to ``is_enabled`` itself."""
    global _Annotation, _collecting
    from jax.profiler import TraceAnnotation
    _Annotation = TraceAnnotation
    _collecting = TraceAnnotation.is_enabled
    return _collecting()


class TraceCollector:
    """Bounded, thread-safe buffer of Chrome trace events.

    Timestamps are ``time.perf_counter()`` seconds rebased to the
    collector's epoch and converted to microseconds, so events recorded
    from any thread share one clock.
    """

    def __init__(self, max_events: int = MAX_EVENTS):
        self._lock = threading.Lock()
        self._events: List[Dict[str, Any]] = []
        self._named_tids: Dict[int, str] = {}
        self.max_events = max_events
        self.dropped = 0
        self.epoch = time.perf_counter()
        self.pid = os.getpid()

    def _ts_us(self, t: float) -> float:
        return (t - self.epoch) * 1e6

    def _append(self, ev: Dict[str, Any]) -> None:
        with self._lock:
            if len(self._events) >= self.max_events:
                self.dropped += 1
                return
            self._events.append(ev)

    def set_thread_name(self, name: str, tid: Optional[int] = None) -> None:
        tid = threading.get_ident() if tid is None else tid
        with self._lock:
            if self._named_tids.get(tid) == name:
                return
            self._named_tids[tid] = name
            if len(self._events) >= self.max_events:
                self.dropped += 1
                return
            self._events.append({
                "name": "thread_name", "ph": "M", "pid": self.pid,
                "tid": tid, "args": {"name": name},
            })

    def add_complete(self, name: str, cat: str, t0: float, dur_s: float,
                     args: Optional[Dict[str, Any]] = None,
                     tid: Optional[int] = None) -> None:
        """Record a finished span; ``t0`` is a perf_counter timestamp."""
        ev: Dict[str, Any] = {
            "name": name, "cat": cat or "default", "ph": "X",
            "ts": self._ts_us(t0), "dur": max(dur_s, 0.0) * 1e6,
            "pid": self.pid,
            "tid": threading.get_ident() if tid is None else tid,
        }
        if args:
            ev["args"] = args
        self._append(ev)

    def add_instant(self, name: str, cat: str,
                    args: Optional[Dict[str, Any]] = None,
                    t: Optional[float] = None) -> None:
        ev: Dict[str, Any] = {
            "name": name, "cat": cat or "default", "ph": "i",
            "ts": self._ts_us(time.perf_counter() if t is None else t),
            "pid": self.pid, "tid": threading.get_ident(), "s": "t",
        }
        if args:
            ev["args"] = args
        self._append(ev)

    def events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._events)

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "traceEvents": self.events(),
            "displayTimeUnit": "ms",
            "otherData": {"producer": "repro.obs.trace",
                          "dropped_events": self.dropped},
        }

    def save(self, path: str) -> str:
        doc = self.to_dict()
        tmp = path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
        return path


_COLLECTOR = TraceCollector()


def collector() -> TraceCollector:
    return _COLLECTOR


def start(max_events: int = MAX_EVENTS, fresh: bool = True) -> TraceCollector:
    """Begin tracing; by default into a fresh collector."""
    global _TRACING, _COLLECTOR
    if fresh or not isinstance(_COLLECTOR, TraceCollector):
        _COLLECTOR = TraceCollector(max_events=max_events)
    _TRACING = True
    return _COLLECTOR


def stop() -> TraceCollector:
    """Stop tracing; the collector (and its events) remain readable."""
    global _TRACING
    _TRACING = False
    return _COLLECTOR


def active() -> bool:
    return _TRACING


def save(path: str) -> str:
    return _COLLECTOR.save(path)


def set_thread_name(name: str) -> None:
    if not _TRACING:
        return
    _COLLECTOR.set_thread_name(name)


def instant(name: str, cat: str = "", **args: Any) -> None:
    if not _TRACING:
        return
    _COLLECTOR.add_instant(name, cat, args or None)


def complete(name: str, cat: str, t0: float, t1: float, **args: Any) -> None:
    """Record a span from two perf_counter timestamps (for windows whose
    endpoints are observed at different call sites, e.g. reshard DUAL)."""
    if not _TRACING:
        return
    _COLLECTOR.add_complete(name, cat, t0, t1 - t0, args or None)


class span:
    """Context manager that times a block and records it when tracing.

    ``seconds`` is always populated on exit, so call sites can feed the
    same measurement into legacy reports and histograms::

        with span("load.parse", cat="load") as sp:
            manifest = archive.manifest
        rep.phases["parse_s"] = sp.seconds

    While a profiler session collects, the block is also a
    ``TraceAnnotation`` of the same name (module docstring).
    """

    __slots__ = ("name", "cat", "args", "seconds", "_t0", "_ann")

    def __init__(self, name: str, cat: str = "", **args: Any):
        self.name = name
        self.cat = cat
        self.args = args or None
        self.seconds = 0.0
        self._t0 = 0.0
        self._ann = None

    def set(self, **args: Any) -> None:
        """Add args known only partway through the block; call at most
        once per key (the profiler keeps every value it is given)."""
        if self.args is None:
            self.args = args
        else:
            self.args.update(args)
        if self._ann is not None:
            self._ann.set_metadata(**args)

    def __enter__(self) -> "span":
        if _collecting():
            self._ann = _Annotation(self.name, **(self.args or {}))
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.seconds = time.perf_counter() - self._t0
        if exc_type is not None:
            self.set(error=exc_type.__name__)
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
            self._ann = None
        if _TRACING:
            _COLLECTOR.add_complete(self.name, self.cat, self._t0,
                                    self.seconds, self.args)
        return False


# ---------------------------------------------------------------------------
# schema check — shared by fig18, tests, and .github/analysis_gate.py
# ---------------------------------------------------------------------------

def validate_trace(doc: Union[Dict[str, Any], List[Any]]) -> List[str]:
    """Validate Chrome trace-event JSON; return a list of problems.

    Accepts both the object format (``{"traceEvents": [...]}``) and the
    bare array format.  Checks per-event structure: known phase, string
    name, numeric non-negative ``ts``, integral ``pid``/``tid``, ``dur``
    present and non-negative on ``"X"`` events, and well-formed
    ``thread_name``/``process_name`` metadata events.
    """
    problems: List[str] = []
    if isinstance(doc, dict):
        events = doc.get("traceEvents")
        if not isinstance(events, list):
            return ["traceEvents missing or not a list"]
    elif isinstance(doc, list):
        events = doc
    else:
        return ["trace document is neither an object nor an array"]
    if not events:
        problems.append("trace contains no events")
    for i, ev in enumerate(events):
        where = f"event[{i}]"
        if not isinstance(ev, dict):
            problems.append(f"{where}: not an object")
            continue
        ph = ev.get("ph")
        if ph not in _VALID_PHASES:
            problems.append(f"{where}: unknown phase {ph!r}")
            continue
        name = ev.get("name")
        if not isinstance(name, str) or not name:
            problems.append(f"{where}: missing name")
        for fld in ("pid", "tid"):
            if not isinstance(ev.get(fld), int):
                problems.append(f"{where} ({name}): {fld} not an int")
        if ph == "M":
            if name not in ("thread_name", "process_name",
                            "thread_sort_index", "process_sort_index"):
                problems.append(f"{where}: unknown metadata event {name!r}")
            elif name in ("thread_name", "process_name") and not isinstance(
                    (ev.get("args") or {}).get("name"), str):
                problems.append(f"{where} ({name}): args.name missing")
            continue
        ts = ev.get("ts")
        if not isinstance(ts, (int, float)):
            problems.append(f"{where} ({name}): ts not a number")
        elif ts < 0:
            problems.append(f"{where} ({name}): negative ts {ts}")
        if ph == "X":
            dur = ev.get("dur")
            if not isinstance(dur, (int, float)):
                problems.append(f"{where} ({name}): X event without dur")
            elif dur < 0:
                problems.append(f"{where} ({name}): negative dur {dur}")
    return problems


def spans_named(doc: Union[Dict[str, Any], List[Any]], name: str
                ) -> List[Dict[str, Any]]:
    """All ``"X"`` events with the given name (fig18/test helper)."""
    events = doc.get("traceEvents", []) if isinstance(doc, dict) else doc
    return [e for e in events
            if isinstance(e, dict) and e.get("ph") == "X"
            and e.get("name") == name]


def overlapping(a: Dict[str, Any], b: Dict[str, Any]) -> bool:
    """True if two ``"X"`` events overlap in time."""
    return (a["ts"] < b["ts"] + b["dur"]) and (b["ts"] < a["ts"] + a["dur"])
