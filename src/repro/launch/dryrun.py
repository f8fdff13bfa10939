"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh) cell.

A CPU tool. It proves the distribution config is coherent without the
hardware: the SPMD program for the production mesh is traced, lowered and
compiled for 512 CPU placeholder devices (this single-host capture is also
exactly Foundry's offline SAVE topology — see DESIGN.md §1). It pins itself
to the CPU backend before JAX loads, so it never takes an accelerator.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-9b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out f.json]

Per cell it records memory_analysis, cost_analysis, and the HLO-derived
roofline terms (repro.analysis.roofline) into a JSON report consumed by
EXPERIMENTS.md §Dry-run / §Roofline.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = " ".join(filter(None, (
    os.environ.get("XLA_FLAGS"), "--xla_force_host_platform_device_count=512")))
import argparse
import json
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import SHAPE_CELLS
from repro.configs.registry import REGISTRY, ASSIGNED, get_arch
from repro.launch.mesh import ShardCtx, make_production_mesh
from repro.models.model import Model
from repro.training.optimizer import OptConfig
from repro.training.train_loop import make_train_step, train_state_specs


def build_step_and_specs(cfg, shape_name: str, ctx: ShardCtx):
    """Returns (step_fn, kwargs_of_specs, donate_argnums)."""
    model = Model(cfg, ctx)
    cell = SHAPE_CELLS[shape_name]
    if cell.kind == "train":
        opt_cfg = OptConfig(state_dtype=cfg.opt_state_dtype)
        step = make_train_step(model, opt_cfg)
        specs = {"state": train_state_specs(model, opt_cfg),
                 "batch": model.input_specs(shape_name)}
        return step, specs, (0,)
    if cell.kind == "prefill":
        def prefill_step(params, batch):
            return model.prefill(params, batch)
        specs = {"params": model.param_specs(),
                 "batch": model.input_specs(shape_name)}
        return prefill_step, specs, ()
    # decode
    def serve_step(params, cache, tokens):
        return model.decode_step(params, cache, tokens)
    dec = model.input_specs(shape_name)
    specs = {"params": model.param_specs(), "cache": dec["cache"],
             "tokens": dec["tokens"]}
    return serve_step, specs, (1,)


def run_cell(arch: str, shape_name: str, mesh, *, verbose: bool = True,
             compute_roofline: bool = True) -> dict:
    cfg = get_arch(arch)
    skip = cfg.skip_reason(shape_name)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": dict(zip(mesh.axis_names, mesh.devices.shape))}
    if skip:
        rec["status"] = "skip"
        rec["skip_reason"] = skip
        return rec
    ctx = ShardCtx(mesh=mesh)
    step, specs, donate = build_step_and_specs(cfg, shape_name, ctx)
    args = tuple(specs.values())
    t0 = time.perf_counter()
    with mesh:
        lowered = jax.jit(step, donate_argnums=donate).lower(*args)
        t_lower = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    rec.update({
        "status": "ok",
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory_analysis": {
            "argument_size_bytes": ma.argument_size_in_bytes,
            "output_size_bytes": ma.output_size_in_bytes,
            "temp_size_bytes": ma.temp_size_in_bytes,
            "alias_size_bytes": ma.alias_size_in_bytes,
            "generated_code_size_bytes": ma.generated_code_size_in_bytes,
        },
        "cost_analysis_raw": {
            "flops": ca.get("flops", 0.0),
            "bytes_accessed": ca.get("bytes accessed", 0.0),
            "transcendentals": ca.get("transcendentals", 0.0),
        },
    })
    # per-device live bytes (args are donated where possible)
    live = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    rec["memory_analysis"]["live_bytes_per_device"] = live
    rec["fits_16g_hbm"] = bool(live <= 16 * 1024**3)
    if compute_roofline:
        from repro.analysis.roofline import roofline_from_compiled
        rec["roofline"] = roofline_from_compiled(
            compiled, cfg, SHAPE_CELLS[shape_name], mesh)
    if verbose:
        print(f"[{arch} x {shape_name}] lower {t_lower:.1f}s "
              f"compile {t_compile:.1f}s live/dev "
              f"{live / 1e9:.2f} GB fits16G={rec['fits_16g_hbm']}")
        print("  memory_analysis:", rec["memory_analysis"])
        print("  cost_analysis:", rec["cost_analysis_raw"])
        if compute_roofline:
            r = rec["roofline"]
            print(f"  roofline: compute {r['compute_s']:.3e}s "
                  f"memory {r['memory_s']:.3e}s collective "
                  f"{r['collective_s']:.3e}s dominant={r['dominant']}")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="architecture id")
    ap.add_argument("--shape", default=None, choices=list(SHAPE_CELLS))
    ap.add_argument("--all", action="store_true",
                    help="sweep all assigned (arch x shape) cells")
    ap.add_argument("--multi-pod", action="store_true",
                    help="use the 2-pod (2,16,16) mesh")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=None, help="JSON report path")
    ap.add_argument("--no-roofline", action="store_true")
    args = ap.parse_args()

    meshes = []
    if args.both_meshes:
        meshes = [make_production_mesh(), make_production_mesh(multi_pod=True)]
    else:
        meshes = [make_production_mesh(multi_pod=args.multi_pod)]

    cells = []
    if args.all:
        for cfg in ASSIGNED:
            for shape in SHAPE_CELLS:
                cells.append((cfg.name, shape))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all required"
        cells.append((args.arch, args.shape))

    records = []
    failures = 0
    for mesh in meshes:
        for arch, shape in cells:
            try:
                rec = run_cell(arch, shape, mesh,
                               compute_roofline=not args.no_roofline)
            except Exception as e:  # a failure here is a bug in the system
                traceback.print_exc()
                rec = {"arch": arch, "shape": shape,
                       "mesh": dict(zip(mesh.axis_names, mesh.devices.shape)),
                       "status": "fail", "error": f"{type(e).__name__}: {e}"}
                failures += 1
            records.append(rec)
            if args.out:
                with open(args.out, "w") as f:
                    json.dump(records, f, indent=1)
    ok = sum(1 for r in records if r["status"] == "ok")
    sk = sum(1 for r in records if r["status"] == "skip")
    print(f"\ndry-run: {ok} ok, {sk} documented skips, {failures} failures")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
