"""Benchmark of Foundry serving on a TPU.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` in this one process on the chips JAX
finds. With ``--trace 0`` the last line of standard output is a JSON object
with the cell's end-to-end metrics; with ``--trace 1`` a profiler trace of
part of the window gives its per-layer metrics, device busy time and the
``breakdown``. Each number compared to decide ``correct`` is printed beside
its limit, as the last lines of standard error and under ``checks`` in the
result. Exits nonzero, printing no result, when JAX finds no TPU or fewer
chips than the cell asks for. Artifacts go under ``bench_out/``, the
compilation cache under ``.jax_cache/``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # libtpu logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(BENCH))
    from harness.main import NoChip, run
    try:
        result = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
