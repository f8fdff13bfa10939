"""One run of one cell: set-up, the measured window, the check, the line.

    run(workload, seed, seconds, trace) -> the result dict

Set-up makes the weights on the device from the seed, SAVEs the decode
capture set to ``bench_out/``, and lets the cell's driver LOAD and warm up.
``setup_s`` runs from the process's start to the window's. After the window
the device's peak memory is read, the program's state is freed, and the
plain reference checks a sample of what the window served.
"""
from __future__ import annotations

import gc
import math
import os
import shutil
import sys
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, Optional

from harness import check, drivers, spec, system
from harness import trace as trace_mod
from harness import weights
from harness.spec import BENCH, Cell


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclass
class Run:
    """What a metric's reader reads."""
    cell: Cell
    dims: Any  # the sizes of the cell's family
    peak: Optional[Dict[str, Any]]
    setup_s: float
    window: drivers.Window
    trace: Optional[trace_mod.TraceSummary]


def process_start() -> float:
    """This process's start on the ``perf_counter`` clock (Linux)."""
    with open("/proc/self/stat") as f:
        ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    age = uptime - ticks / os.sysconf("SC_CLK_TCK")
    return time.perf_counter() - max(0.0, age)


class CompileCounter:
    """Counts lowerings and backend compiles while it is entered."""

    def __init__(self):
        self.lowered = self.compiled = 0

    def _event(self, event: str, duration: float, **kw):
        if event.endswith("jaxpr_to_mlir_module_duration"):
            self.lowered += 1
        elif event.endswith("backend_compile_duration"):
            self.compiled += 1

    def __enter__(self):
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._event)
        return self

    def __exit__(self, *exc):
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._event)


class GcPauses:
    """Python's garbage collections while it is entered: count and time."""

    def __init__(self):
        self.n, self.total, self.longest, self._t0 = 0, 0.0, 0.0, 0.0

    def _cb(self, phase: str, info: dict):
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            dt = time.perf_counter() - self._t0
            self.n += 1
            self.total += dt
            self.longest = max(self.longest, dt)

    def __enter__(self):
        gc.callbacks.append(self._cb)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._cb)


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def _served(window: drivers.Window) -> list:
    if window.scaleouts:
        return [s.req for s in window.scaleouts]
    return [r.req for r in window.requests]


def run(workload: str, seed: int, seconds: float, trace: bool, **kw) -> dict:
    return measure(workload, seed, seconds, trace, **kw)[0]


def measure(workload: str, seed: int, seconds: float, trace: bool, *,
            bench: Path = BENCH, benchmark: Optional[Path] = None,
            require_tpu: bool = True, cache: bool = True,
            t_start: Optional[float] = None) -> tuple:
    """``run``'s result, and the cell and the sample of served requests
    that the reference compared (for a control read on the same ones)."""
    t_start = process_start() if t_start is None else t_start
    import jax
    devs = jax.devices()
    if require_tpu and jax.default_backend() != "tpu":
        raise NoChip(f"JAX backend is {jax.default_backend()!r}, not tpu")
    cell = spec.load_cell(workload, bench, benchmark)
    if require_tpu and len(devs) < cell.chips:
        raise NoChip(f"{len(devs)} chips, the cell asks for {cell.chips}")
    dev = devs[0]
    root = bench.parent
    system.use_program(root)
    if cache:
        cache_dir, empty = system.configure_cache(root)
        log(f"[cache] {cache_dir} ({'empty' if empty else 'warm'} at start)")
    peak = spec.peaks(dev.device_kind, bench) if require_tpu else None
    d = cell.dims
    out = root / "bench_out"
    out.mkdir(exist_ok=True)
    archive = out / f"{cell.config_name}.fndry"

    t0 = time.perf_counter()
    params = weights.make(d, seed)
    jax.block_until_ready(params)
    log(f"[weights] {cell.config_name} from seed {seed} in "
        f"{time.perf_counter() - t0:.3f}s")
    t0 = time.perf_counter()
    srep = system.save(cell.config_name, d, params, archive)
    log(f"[save] {archive.name}: {srep['specs']['decode']['n_templates']} "
        f"templates over {srep['specs']['decode']['n_buckets']} buckets, "
        f"{archive.stat().st_size} bytes in {time.perf_counter() - t0:.3f}s")
    t0 = time.perf_counter()
    run_window = drivers.DRIVERS[cell.traffic["driver"]](
        cell, d, params, archive, seed, seconds)
    log(f"[warm-up] {time.perf_counter() - t0:.3f}s")

    tracer = None
    trace_dir = out / "trace" / workload
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        tracer = drivers.Tracer(
            trace_dir, seconds * float(cell.params.get("trace_from", 0.5)))
    setup_s = time.perf_counter() - t_start
    with CompileCounter() as compiles, GcPauses() as gcp:
        window = run_window(seconds, tracer)
    log(f"[window] {window.seconds:.3f}s, {len(window.steps)} steps, "
        f"{len(window.scaleouts)} scale-outs, {len(window.requests)} "
        f"requests; {compiles.lowered} lowerings and {compiles.compiled} "
        f"backend compiles inside it")
    if window.steps:
        ms = sorted((s.t1 - s.t0) * 1e3 for s in window.steps)
        log(f"[steps] ms p10 {ms[len(ms) // 10]:.2f} p50 {ms[len(ms) // 2]:.2f} "
            f"p90 {ms[len(ms) * 9 // 10]:.2f} max {ms[-1]:.2f}; "
            f"{sum(s.tokens for s in window.steps)} output tokens")
        slow = [m for m in ms if m > 2 * ms[len(ms) // 2]]
        log(f"[stalls] {len(slow)} steps over twice the median, "
            f"{sum(slow) / 1e3:.3f}s; outside steps "
            f"{window.seconds - sum(ms) / 1e3:.3f}s; {gcp.n} collections "
            f"{gcp.total * 1e3:.1f}ms (longest {gcp.longest * 1e3:.1f}ms)")
    if window.scaleouts:
        so = window.scaleouts
        log("[scale-outs] cold_s " + " ".join(f"{s.cold_s:.3f}" for s in so))
        log("[scale-outs] to LOAD's return s " + " ".join(
            f"{s.t_loaded - s.t0:.3f}" for s in so))
        log("[scale-outs] archive open / engine / LOAD ms " + " ".join(
            "/".join(f"{x * 1e3:.0f}" for x in s.parts_s) for s in so))
        log("[scale-outs] probe steps, slowest ms " + " ".join(
            f"{len(s.probe_steps_s)}/{max(s.probe_steps_s, default=0) * 1e3:.1f}"
            for s in so))
        log(f"[stalls] {gcp.n} collections {gcp.total * 1e3:.1f}ms "
            f"(longest {gcp.longest * 1e3:.1f}ms)")
    stats = dev.memory_stats() or {}
    mem_peak = int(stats.get("peak_bytes_in_use", 0))

    summary = None
    if trace:
        summary = trace_mod.summarize(
            str(trace_dir), window.trace_t1 - window.trace_t0)
    run_window = params = None
    gc.collect()

    served = _served(window)
    reqs = check.sample(served, seed, int(cell.params["check_requests"]))
    t0 = time.perf_counter()
    gap, n_tok = check.gaps(cell, seed, reqs)
    log(f"[check] {len(reqs)} requests, {n_tok} served tokens against the "
        f"reference in {time.perf_counter() - t0:.3f}s")
    in_window = [r for r in window.requests if r.in_window]
    if window.scaleouts:
        attempted = len(window.scaleouts)
        failed = sum(1 for s in window.scaleouts
                     if s.req.state.value != "done")
    else:
        attempted = len(in_window)
        failed = sum(1 for r in in_window if r.req.state.value == "failed")
    checks = {
        "max_logit_gap": (gap, float(cell.params["limits"]["max_logit_gap"])),
        "failed_requests": (failed, 0),
        "ids_outside_vocab": (sum(1 for r in served for t in r.generated
                                  if not 0 <= t < d.vocab), 0),
    }
    if window.scaleouts:
        checks["scaleouts_not_exact"] = (sum(
            1 for s in window.scaleouts
            if s.restore_path != "exact" or s.fallback_compiles
            or s.background_errors), 0)
    correct = bool(reqs) and all(v <= lim for v, lim in checks.values())

    data = Run(cell, d, peak, setup_s, window, summary)
    metrics = {}
    for m in cell.metrics(trace):
        v = spec.metric_reader(m["name"], bench)(data)
        if v is not None and math.isfinite(v):  # inf: a request never served
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": mem_peak}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
    for name, (v, lim) in checks.items():
        log(f"check {name} {v} limit {lim}")
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result, cell, reqs
