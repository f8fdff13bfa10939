"""The drivers that time each kind of traffic, and what they record.

A mix names its driver (``"driver"`` in ``bench/traffic/<mix>.json``):

    coldstart   scale-outs back to back: open the archive, build an engine,
                LOAD, serve one probe to its first token
    saturate    an offline batch: the queue always holds at least
                ``max_batch`` waiting requests

Each driver warms up every shape its window uses during set-up, then runs
the window for ``seconds`` and returns a ``Window``. Host spans that label
the device trace's idle gaps are ``bench.load``, ``bench.probe``,
``bench.submit`` and ``bench.step``.
"""
from __future__ import annotations

import gc
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

from jax.profiler import TraceAnnotation

from harness import system, traffic


@dataclass
class ReqRec:
    """One request as the benchmark saw it."""
    req: Any              # the program's Request
    seen: int = 0         # output tokens counted so far
    in_window: bool = True


@dataclass
class StepRec:
    t0: float
    t1: float
    rows: int      # live rows the step served
    ctx: int       # positions those rows attend to, the new ones included
    tokens: int    # output tokens the step produced


@dataclass
class ScaleOut:
    t0: float
    t_loaded: float
    first_token_t: float
    critical_path_s: float
    restore_path: str
    fallback_compiles: int
    background_errors: int
    req: Any
    probe_steps_s: List[float] = field(default_factory=list)  # to 1st token
    parts_s: List[float] = field(default_factory=list)  # open, engine, LOAD

    @property
    def cold_s(self) -> float:
        return self.first_token_t - self.t0


@dataclass
class Window:
    t0: float
    t1: float = 0.0
    requests: List[ReqRec] = field(default_factory=list)
    steps: List[StepRec] = field(default_factory=list)
    scaleouts: List[ScaleOut] = field(default_factory=list)
    decode_steps: int = 0      # engine.decode_steps counted in the window
    trace_t0: Optional[float] = None
    trace_t1: Optional[float] = None

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class Stepper:
    """Runs engine steps and records each one's rows, work and tokens."""

    def __init__(self, eng, window: Optional[Window] = None):
        self.eng = eng
        self.window = window
        self.recs: Dict[int, ReqRec] = {}

    def track(self, rec: ReqRec):
        self.recs[rec.req.req_id] = rec

    def step(self) -> int:
        eng = self.eng
        sched, pool = eng.scheduler, eng.pool
        n_done = len(sched.done)
        ctx = sum(pool.host_len)  # pre-step lengths; admissions start at 0
        t0 = time.perf_counter()
        with TraceAnnotation("bench.step"):
            rows = eng.step()
        t1 = time.perf_counter()
        tokens = 0
        for req in (*sched.running.values(), *sched.done[n_done:]):
            rec = self.recs.get(req.req_id)
            if rec is not None:
                tokens += len(req.generated) - rec.seen
                rec.seen = len(req.generated)
        if rows and self.window is not None:
            self.window.steps.append(StepRec(t0, t1, rows, ctx + rows, tokens))
        return rows


def warm_buckets(eng, gens: List[traffic.Gen]):
    """Pass every bucket once, and every change of bucket that serving
    makes: admit ``max_batch`` requests together, so the batch grows
    through each bucket, and let them finish one by one (request ``i``
    asks for ``i + 1`` tokens), so that it shrinks with a step at every
    size; then, for each bucket ``b``, ``b`` requests that finish together
    (the pool shrinks by halves as they leave, ending 2 -> 1)."""
    for i, g in enumerate(gens[:eng.max_batch]):
        eng.submit(g.prompt[:8], i + 1)
    eng.run_until_drained()
    for b in eng.buckets:
        for g in gens[:b]:
            eng.submit(g.prompt[:8], 2)
        eng.run_until_drained()


class Tracer:
    """Starts the profiler at ``start_at`` seconds into the window and stops
    it at the window's close (``--trace 1`` only)."""

    def __init__(self, directory, start_at: float):
        self.directory = directory
        self.start_at = start_at
        self.on = False

    def poll(self, window: Window, now: float):
        if not self.on and window.trace_t0 is None \
                and now - window.t0 >= self.start_at:
            import jax
            jax.profiler.start_trace(str(self.directory))
            self.on = True
            window.trace_t0 = time.perf_counter()

    def stop(self, window: Window):
        if self.on:
            import jax
            window.trace_t1 = time.perf_counter()
            jax.profiler.stop_trace()
            self.on = False


# what a driver's set-up returns: ``run(seconds, tracer)``, the window
WindowFn = Callable[[float, Optional[Tracer]], Window]


def coldstart(cell, d, params, archive, seed: int,
              seconds: float) -> WindowFn:
    """Scale-outs of the chat replica's shape, one after another. Each is
    timed from opening the archive to its probe's first token; joining the
    background exact-bucket realization and dropping the engine happen
    after that, outside the timing. Set-up makes one untimed scale-out."""
    n_max = int(cell.params.get("max_scaleouts", 10_000))
    gens = traffic.probes(cell.traffic, seed, n_max + 1, d.vocab)

    def scale_out(g: traffic.Gen) -> ScaleOut:
        t0 = time.perf_counter()
        with TraceAnnotation("bench.load"):
            parts = []
            eng, rep = system.cold_start(cell.config_name, d, params,
                                         archive, parts)
        t_loaded = time.perf_counter()
        with TraceAnnotation("bench.probe"):
            req = eng.submit(g.prompt, g.max_new)
            steps = []
            while req.first_token_t is None and eng.scheduler.pending:
                t = time.perf_counter()
                eng.step()
                steps.append(time.perf_counter() - t)
            eng.run_until_drained()
        first = req.first_token_t if req.first_token_t is not None \
            else float("nan")
        system.join_background(rep)
        out = ScaleOut(t0, t_loaded, first, rep.critical_path_s,
                       rep.restore_path, rep.fallback_compiles,
                       rep.background_errors, req, steps, parts)
        del eng
        gc.collect()  # engine and pool refer to each other: free its HBM now
        return out

    scale_out(gens[0])

    def run(secs: float, tracer: Optional[Tracer]) -> Window:
        w = Window(time.perf_counter())
        end = w.t0 + secs
        for g in gens[1:]:
            now = time.perf_counter()
            if now >= end:
                break
            if tracer:
                tracer.poll(w, now)
            w.scaleouts.append(scale_out(g))
        w.t1 = time.perf_counter()
        if tracer:
            tracer.stop(w)
        return w

    return run


def _served_engine(cell, d, params, archive, seed: int):
    eng, rep = system.cold_start(cell.config_name, d, params, archive)
    system.join_background(rep)
    if (rep.restore_path != "exact" or rep.fallback_compiles
            or rep.background_errors):
        raise RuntimeError(f"LOAD took path {rep.restore_path} with "
                           f"{rep.fallback_compiles} fallback compiles and "
                           f"{rep.background_errors} background errors")
    warm_buckets(eng, traffic.probes(cell.traffic, seed ^ 0x5EED,
                                     eng.max_batch, d.vocab))
    return eng


def saturate(cell, d, params, archive, seed: int,
             seconds: float) -> WindowFn:
    """An offline batch: before each step the queue is topped up to
    ``max_batch`` waiting requests. Set-up fills the batch and runs until
    the first requests complete, so the window opens in steady state."""
    eng = _served_engine(cell, d, params, archive, seed)
    gens = traffic.stream(cell.traffic, seed, d.vocab)
    sched = eng.scheduler
    recs: List[ReqRec] = []
    stepper = Stepper(eng)

    def top_up():
        while len(sched.queue) < eng.max_batch:
            g = next(gens)
            with TraceAnnotation("bench.submit"):
                req = eng.submit(g.prompt, g.max_new)
            rec = ReqRec(req, in_window=False)
            recs.append(rec)
            stepper.track(rec)

    n_warm = len(sched.done)  # warm_buckets' requests
    while len(sched.done) == n_warm:
        top_up()
        stepper.step()

    def run(secs: float, tracer: Optional[Tracer]) -> Window:
        w = Window(time.perf_counter())
        stepper.window = w
        steps0 = eng.decode_steps
        end = w.t0 + secs
        n_before = len(recs)
        while True:
            now = time.perf_counter()
            if now >= end:
                break
            if tracer:
                tracer.poll(w, now)
            top_up()
            stepper.step()
        w.t1 = time.perf_counter()
        w.decode_steps = eng.decode_steps - steps0
        if tracer:
            tracer.stop(w)
        stepper.window = None
        for rec in recs[n_before:]:
            rec.in_window = True
        w.requests = recs
        return w

    return run


DRIVERS = {"coldstart": coldstart, "saturate": saturate}
