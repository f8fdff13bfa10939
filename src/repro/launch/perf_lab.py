"""§Perf hillclimb driver: lower one (arch x shape) cell under a tuning-flag
configuration and print the roofline terms.

  PYTHONPATH=src python -m repro.launch.perf_lab --arch yi-9b \
      --shape decode_32k --flags mixed_precision_attn=1

Each EXPERIMENTS.md §Perf iteration is one baseline/flagged pair of runs.
A CPU tool: it compiles for 512 CPU placeholder devices and pins itself to
the CPU backend before JAX loads, so it never takes an accelerator.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = " ".join(filter(None, (
    os.environ.get("XLA_FLAGS"), "--xla_force_host_platform_device_count=512")))
import argparse
import json

import jax

from repro.configs.base import SHAPE_CELLS
from repro.configs.registry import get_arch
from repro.launch.mesh import ShardCtx, make_production_mesh
from repro.models import tuning


def measure(arch: str, shape: str, flag_spec: str = "") -> dict:
    tuning.baseline()
    if flag_spec:
        for item in flag_spec.split(","):
            if item.strip():
                k, _, v = item.partition("=")
                tuning.set_flags(**{k.strip(): int(v)})
    jax.clear_caches()
    from repro.launch.dryrun import run_cell
    mesh = make_production_mesh()
    rec = run_cell(arch, shape, mesh, verbose=False)
    assert rec["status"] == "ok", rec
    r = rec["roofline"]
    return {
        "arch": arch, "shape": shape, "flags": flag_spec or "baseline",
        "compute_s": r["compute_s"], "memory_s": r["memory_s"],
        "collective_s": r["collective_s"], "dominant": r["dominant"],
        "bound_s": r["step_time_lower_bound_s"],
        "roofline_fraction": r["roofline_fraction"],
        "live_gb": rec["memory_analysis"]["live_bytes_per_device"] / 1e9,
        "fits_16g": rec["fits_16g_hbm"],
        "wire_by_kind": r["wire_bytes_by_kind"],
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=list(SHAPE_CELLS))
    ap.add_argument("--flags", default="")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()
    rec = measure(args.arch, args.shape, args.flags)
    if args.json:
        print(json.dumps(rec, indent=1))
    else:
        print(f"[{rec['arch']} x {rec['shape']}] flags={rec['flags']}")
        print(f"  compute {rec['compute_s']:.4e}s  memory {rec['memory_s']:.4e}s"
              f"  collective {rec['collective_s']:.4e}s  -> dominant "
              f"{rec['dominant']}, bound {rec['bound_s']:.4e}s, "
              f"roofline {100 * rec['roofline_fraction']:.2f}%, "
              f"live {rec['live_gb']:.1f}GB fits16G={rec['fits_16g']}")


if __name__ == "__main__":
    main()
