"""What the metric readers under ``bench/metrics/`` share.

A reader is ``read(run) -> float | None``: ``None`` when the run holds
nothing to read, and the harness then leaves the metric out of the line.
"""
from __future__ import annotations

from typing import Optional

from harness import work


def step_ms(run) -> Optional[float]:
    """Window over the engine steps that served at least one row."""
    n = run.window.decode_steps
    return run.window.seconds / n * 1e3 if n else None


def idle_share(run) -> Optional[float]:
    t = run.trace
    if t is None or not t.devices or t.window_s <= 0:
        return None
    return (1.0 - t.busy_s / t.window_s) * 100.0


def traced_steps(run):
    w = run.window
    if w.trace_t0 is None:
        return []
    return [s for s in w.steps if s.t0 >= w.trace_t0 and s.t1 <= w.trace_t1]


def roofline(run) -> Optional[float]:
    """Summed least time of the traced steps over the device time of the
    programs in the trace, in %. In a serving window each engine step
    dispatches one decode program (the LOADed template, or an exact bucket
    realized from the archive under another name) and a few small copies,
    so every program in the trace counts; a step that straddles the trace's
    start or end adds device time and no step, which reads low, never high."""
    if run.trace is None or run.peak is None:
        return None
    steps = traced_steps(run)
    if not steps or run.trace.program_s <= 0:
        return None
    least = sum(work.least_time(run.dims, run.peak, s.rows, s.ctx)[0]
                for s in steps)
    return least / run.trace.program_s * 100.0


def mfu(run) -> Optional[float]:
    """Model FLOPs of every row-token the window's steps processed, fill
    and decode alike, over the window times the chip's peak."""
    w = run.window
    if run.peak is None or not w.steps or w.seconds <= 0:
        return None
    flops = sum(work.step_flops(run.dims, s.rows, s.ctx) for s in w.steps)
    return flops / (w.seconds * run.peak["bf16_flops_per_s"]) * 100.0
