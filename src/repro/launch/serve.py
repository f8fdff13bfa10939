"""Serving launcher.

    # offline SAVE (one capture host; archive is rank-independent)
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-14b-reduced \
        --save /tmp/qwen.fndry

    # online LOAD + serve a synthetic request stream
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-14b-reduced \
        --load /tmp/qwen.fndry --requests 16

    # the same at smollm-360m's published widths, on the accelerator
    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m \
        --save bench_out/s.fndry
    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m \
        --load bench_out/s.fndry

    # autoscaling fleet replaying a load spike against one shared archive
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-14b-reduced \
        --load /tmp/qwen.fndry --fleet --max-replicas 4 \
        --trace 10:25:30:1:6

    # multi-model gateway: a zoo of models behind one front door, each
    # scaling to zero when idle and reactivating from one shared depot
    PYTHONPATH=src python -m repro.launch.serve \
        --models qwen3-14b-reduced,smollm-360m-reduced --depot /tmp/depot \
        --zoo-rounds 2

    # phase-disaggregated fleet: wide prefill pool + narrow decode pool,
    # per-request KV handoff after the first token (docs §14); both pools
    # LOAD the same archive (the wide pool via rank stamping)
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-14b-reduced \
        --load /tmp/qwen.fndry --fleet \
        --pools prefill=2:wide,decode=1:narrow --trace 10:25:30:1:6
"""
from __future__ import annotations

import argparse
import json
import os
import random
import time
from pathlib import Path

import jax

from repro.configs.registry import get_arch
from repro.core import Archive, TemplateDepot
from repro.models.model import Model
from repro.obs import configure_logging
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.serving.engine import ServingEngine
from repro.serving.fleet import AutoscalePolicy, Fleet, spike_trace
from repro.serving.router import ModelPolicy, ModelRouter


#: The checkout this module runs from (``src/repro/launch/serve.py``).
CHECKOUT = Path(__file__).resolve().parents[3]


def configure_compile_cache() -> tuple[str, bool]:
    """Place JAX's persistent compilation cache; returns ``(dir,
    was_empty)``. A set ``JAX_COMPILATION_CACHE_DIR`` is read by JAX itself
    and used as is. Otherwise the cache is ``<checkout>/.jax_cache``: one
    fixed path, so every later run from this checkout finds it again."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path, not (os.path.isdir(path) and os.listdir(path))


def build(arch: str, max_batch: int, max_seq: int,
          mesh=None, seed: int = 0) -> ServingEngine:
    """An engine for ``arch`` with random weights from ``PRNGKey(seed)``."""
    cfg = get_arch(arch)
    if mesh is None:
        model = Model(cfg)
    else:
        from repro.launch.mesh import ShardCtx, resolve_mesh
        model = Model(cfg, ShardCtx(mesh=resolve_mesh(mesh)))
    eng = ServingEngine(model, max_batch=max_batch, max_seq=max_seq,
                        bucket_mode="pow2")
    eng.load_weights(rng=jax.random.PRNGKey(seed))
    return eng


def parse_pools(spec: str):
    """``prefill=2:wide,decode=1:narrow`` -> [PoolSpec, ...].

    Each entry is ``phase=count[:mesh]`` where mesh is ``wide`` (every
    local device, via make_host_mesh — LOADed from the shared archive by
    rank stamping), ``narrow`` (un-meshed single device, the exact LOAD
    path), or an explicit ``AxB`` data x model shape."""
    from repro.launch.mesh import MeshSpec, make_host_mesh
    from repro.serving.fleet import PoolSpec
    pools = []
    for entry in spec.split(","):
        entry = entry.strip()
        if not entry:
            continue
        phase, eq, rest = entry.partition("=")
        count_s, _, mesh_s = rest.partition(":")
        if not eq or not count_s.isdigit():
            raise ValueError(
                f"bad --pools entry {entry!r}: want phase=count[:mesh]")
        n = int(count_s)
        mesh_s = mesh_s.strip().lower()
        if mesh_s in ("", "narrow"):
            mesh = None
        elif mesh_s == "wide":
            mesh = make_host_mesh()
        elif "x" in mesh_s:
            a, _, b = mesh_s.partition("x")
            mesh = MeshSpec((int(a), int(b)))
        else:
            raise ValueError(f"bad --pools mesh {mesh_s!r}: want "
                             f"wide | narrow | AxB")
        pools.append(PoolSpec(
            phase.strip(),
            AutoscalePolicy(min_replicas=n, max_replicas=n), mesh))
    if not pools:
        raise ValueError("--pools parsed to an empty pool list")
    return pools


def run_fleet(args):
    """--fleet: replay a spike trace against an autoscaling replica fleet.

    With --load, replicas cold-start from the shared (lazily-opened)
    archive; without it, a SAVE runs first in-process so the fleet still
    exercises the foundry path. --fleet-mode vanilla/eager selects the
    baseline cold starts instead."""
    if args.fleet_mode == "foundry":
        if args.load:
            archive = Archive.load(args.load)  # lazy: manifest-only parse
        else:
            print("[fleet] no --load given: running offline SAVE first")
            archive, _ = build(args.arch, args.max_batch,
                               args.max_seq).save_archive()
    else:
        archive = None

    warm, spike, cool, base, rate = (int(x) for x in args.trace.split(":"))
    trace = spike_trace(warm_ticks=warm, spike_ticks=spike, cool_ticks=cool,
                        base_rate=base, spike_rate=rate)
    if args.pools:
        # phase-disaggregated pools (docs §14): requests enter on the
        # prefill pool and migrate to decode via per-request KV handoff
        fleet = Fleet(
            factory_for_mesh=lambda m: build(args.arch, args.max_batch,
                                             args.max_seq, mesh=m),
            mode=args.fleet_mode, archive=archive,
            pools=parse_pools(args.pools), verbose=True)
    else:
        fleet = Fleet(lambda: build(args.arch, args.max_batch, args.max_seq),
                      mode=args.fleet_mode, archive=archive,
                      policy=AutoscalePolicy(min_replicas=args.min_replicas,
                                             max_replicas=args.max_replicas),
                      verbose=True)
    if args.chaos > 0:
        # supervised-fleet demo: kill N decode steps spread over the trace
        # and watch the fleet salvage + respawn (serving/faults.py)
        from repro.serving.faults import FaultPlan, FaultSpec
        span = max(1, (len(trace) * 2) // (args.chaos + 1))
        plan = FaultPlan(*[
            FaultSpec(site="engine.decode_step", nth=span * (k + 1), times=1,
                      message=f"chaos kill #{k + 1}")
            for k in range(args.chaos)])
        plan.activate()
        print(f"[fleet] chaos: {args.chaos} decode-step faults armed")
    try:
        fleet.run_trace(trace, seed=0)
    finally:
        if args.chaos > 0:
            plan.deactivate()
    fleet.drain_background()  # then re-report to pick up background_errors
    rep = fleet.report()
    s = rep.summary()
    print(json.dumps(s, indent=1, default=str))
    if fleet.disaggregated:
        w50, w95 = s["handoff_wait_p50_s"], s["handoff_wait_p95_s"]
        print(f"  handoffs: {rep.handoffs} adopted, "
              f"{rep.handoff_requeued} requeued"
              + (f", wait p50={w50 * 1e3:.1f}ms p95={w95 * 1e3:.1f}ms"
                 if w50 is not None else ""))
        for p in s["pools"]:
            p99 = p["step_wall_p99_s"]
            tail = f" step_p99={p99 * 1e3:.2f}ms" if p99 is not None else ""
            print(f"  pool {p['phase']}: replicas={p['ready']} "
                  f"mesh={p['mesh']} steps={p['steps']}{tail}")
    for r in rep.replicas:
        cs = r.cold_start_to_first_token_s
        print(f"  replica {r.replica_id}: mode={r.mode} "
              f"provision={r.provision_s and f'{r.provision_s:.2f}s'} "
              f"cold-start->first-token="
              f"{cs and f'{cs:.2f}s'} served={r.served_requests}")


def run_zoo(args):
    """--models a,b,c --depot PATH: multi-model gateway with scale-to-zero.

    Each model's archive is SAVEd into the depot if not already there
    (content-addressed: blobs shared across models are stored once), then a
    popularity-shifting workload runs through the ModelRouter as
    completion-paced phases with a post-phase quiet gap longer than the
    idle threshold — the hot model rotates, idle models deterministically
    drain to zero, and the next round's request for a cold model
    reactivates it from the shared depot (run_phases docstring)."""
    models = [m.strip() for m in args.models.split(",") if m.strip()]
    depot = TemplateDepot(args.depot)
    for name in models:
        if name not in depot:
            print(f"[zoo] SAVE {name} -> depot")
            ar, _ = build(name, args.max_batch, args.max_seq).save_archive()
            depot.put_archive(name, ar)
    st = depot.stats()
    print(f"[zoo] depot: {st['archives']} archives, {st['blobs']} blobs, "
          f"dedup {st['dedup_ratio']:.2f}x "
          f"({st['physical_comp_bytes'] / 1e6:.2f} MB on disk)")

    router = ModelRouter(verbose=True)
    for name in models:
        router.add_model(
            name, lambda n=name: build(n, args.max_batch, args.max_seq),
            archive=depot.open(name),
            policy=ModelPolicy(
                autoscale=AutoscalePolicy(min_replicas=args.min_replicas,
                                          max_replicas=args.max_replicas),
                idle_ticks_to_zero=args.zoo_idle_ticks))
    phases = [(name, args.zoo_requests) for _ in range(args.zoo_rounds)
              for name in models]
    router.run_phases(phases, seed=0, gap_ticks=args.zoo_idle_ticks + 20)
    router.deactivate_all()  # fold every fleet's accounting into the report
    print(json.dumps(router.report().summary(), indent=1, default=str))


def _serve_metrics_http(port: int):
    """Serve the live Prometheus exposition at /metrics on a daemon thread.
    Stdlib only; dies with the process (this is a demo endpoint, not a
    production server)."""
    import threading
    from http.server import BaseHTTPRequestHandler, HTTPServer

    class Handler(BaseHTTPRequestHandler):
        def do_GET(self):
            if self.path.split("?")[0].rstrip("/") in ("", "/metrics"):
                body = obs_metrics.render().encode()
                self.send_response(200)
                self.send_header("Content-Type",
                                 "text/plain; version=0.0.4; charset=utf-8")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
            else:
                self.send_error(404)

        def log_message(self, *a):  # keep serving output clean
            pass

    srv = HTTPServer(("127.0.0.1", port), Handler)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    print(f"metrics endpoint: http://127.0.0.1:{srv.server_address[1]}"
          f"/metrics")
    return srv


def _obs_setup(args):
    if args.metrics_port is not None and args.metrics is None:
        args.metrics = "-"
    if args.metrics is not None:
        obs_metrics.enable()
    if args.trace_out and not obs_trace.active():
        obs_trace.start()
    if args.metrics is not None or args.trace_out:
        configure_logging()
    if args.metrics_port is not None:
        _serve_metrics_http(args.metrics_port)


def _obs_finish(args):
    if args.trace_out and obs_trace.active():
        obs_trace.save(args.trace_out)
        obs_trace.stop()
        print(f"trace -> {args.trace_out}")
    if args.metrics is not None:
        text = obs_metrics.render()
        if args.metrics == "-":
            print("---- metrics ----")
            print(text, end="")
        else:
            with open(args.metrics, "w") as f:
                f.write(text)
            print(f"metrics -> {args.metrics}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch",
                    help="single-model serving (one of the registry names)")
    ap.add_argument("--save", default=None, help="write archive and exit")
    ap.add_argument("--load", default=None, help="archive to LOAD")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-seq", type=int, default=96)
    ap.add_argument("--fleet", action="store_true",
                    help="autoscaling replica fleet replaying --trace")
    ap.add_argument("--fleet-mode", default="foundry",
                    choices=("foundry", "vanilla", "eager"))
    ap.add_argument("--min-replicas", type=int, default=1)
    ap.add_argument("--max-replicas", type=int, default=4)
    ap.add_argument("--pools", default=None, metavar="SPEC",
                    help="with --fleet: phase-disaggregated pools, e.g. "
                         "'prefill=2:wide,decode=1:narrow' "
                         "(phase=count[:mesh]; mesh is wide | narrow | AxB; "
                         "requests prefill on one pool and migrate to the "
                         "other via per-request KV handoff, overriding "
                         "--min/--max-replicas)")
    ap.add_argument("--trace", default="10:25:30:1:6",
                    help="warm:spike:cool:base_rate:spike_rate ticks")
    ap.add_argument("--chaos", type=int, default=0,
                    help="with --fleet: inject N decode-step crashes spread "
                         "over the trace (supervision demo; replicas are "
                         "salvaged and respawned from the shared archive)")
    ap.add_argument("--models", default=None,
                    help="comma-separated model names: multi-model gateway "
                         "with per-model scale-to-zero (needs --depot)")
    ap.add_argument("--depot", default=None,
                    help="template depot directory (content-addressed, "
                         "shared across models)")
    ap.add_argument("--zoo-rounds", type=int, default=2,
                    help="popularity cycles over the model list (round 2+ "
                         "reactivates scaled-to-zero models)")
    ap.add_argument("--zoo-requests", type=int, default=4,
                    help="requests per hot-model phase")
    ap.add_argument("--zoo-idle-ticks", type=int, default=20,
                    help="idle ticks before a model scales to zero")
    ap.add_argument("--check", action="store_true",
                    help="run the static verifier (repro.analysis.check) "
                         "over --load/--depot before serving; refuse to "
                         "serve artifacts with error findings")
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="enable the metrics registry and dump the "
                         "Prometheus text exposition to PATH at exit "
                         "('-' for stdout)")
    ap.add_argument("--metrics-port", type=int, default=None, metavar="N",
                    help="also serve the live exposition at "
                         "http://127.0.0.1:N/metrics (implies --metrics -)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="record structured spans and write a "
                         "Chrome/Perfetto trace-event JSON to PATH at exit "
                         "(open in ui.perfetto.dev or chrome://tracing)")
    args = ap.parse_args()

    configure_compile_cache()
    _obs_setup(args)
    try:
        _run(args, ap)
    finally:
        _obs_finish(args)


def _run(args, ap):
    if args.check:
        from repro.analysis.check import main as check_main
        targets = [t for t in (args.load, args.depot) if t]
        if not targets:
            ap.error("--check needs --load and/or --depot")
        code = check_main(targets + (["--depot", args.depot]
                                     if args.depot and args.load else []))
        if code >= 2:
            raise SystemExit(f"refusing to serve: static verification "
                             f"found errors (exit {code}); see findings "
                             f"above")
        print(f"[check] static verification passed ({len(targets)} "
              f"target(s))")

    if args.models:
        if not args.depot:
            ap.error("--models needs --depot")
        run_zoo(args)
        return
    if not args.arch:
        ap.error("--arch is required (or use --models/--depot)")

    if args.save:
        eng = build(args.arch, args.max_batch, args.max_seq)
        ar, rep = eng.save_archive(args.save, verbose=True)
        print(f"archive -> {args.save} "
              f"({rep['specs']['decode']['n_templates']} templates)")
        return

    if args.fleet:
        run_fleet(args)
        return

    eng = build(args.arch, args.max_batch, args.max_seq)
    t0 = time.perf_counter()
    if args.load:
        eng.cold_start_foundry(Archive.load(args.load), verbose=True)
        mode = "foundry"
    else:
        eng.cold_start_vanilla()
        mode = "vanilla"
    print(f"cold start ({mode}): {time.perf_counter() - t0:.3f}s")

    rng = random.Random(0)
    cfg = eng.cfg
    for _ in range(args.requests):
        prompt = [rng.randrange(1, cfg.vocab_size)
                  for _ in range(rng.randrange(2, 10))]
        eng.submit(prompt, rng.randrange(4, 12))
    t0 = time.perf_counter()
    steps = eng.run_until_drained()
    done = eng.scheduler.done
    toks = sum(len(r.generated) for r in done)
    print(f"served {len(done)} requests / {toks} tokens in "
          f"{time.perf_counter() - t0:.2f}s ({steps} steps); "
          f"dispatch={eng.programs.stats if eng.programs else {}}")


if __name__ == "__main__":
    main()
