"""Readings that set a cell's limit on the widest logit gap.

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,13 --seconds 15

For each seed, in this one process: a run of the cell as ``run.py`` makes
it (at the cell's sizes and load, with a shorter window), then the gap of
the program's served tokens and, on the same sample, the gap of the tokens
that the reference computed in float8 puts first (the control). The
program's readings set the limit's lower end, the control's its upper end.
One JSON line per seed on standard output, and all of them in
``bench_out/calibrate-<cell>.jsonl``. Exits nonzero without a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--no-control", action="store_true")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(BENCH))
    from harness import check
    from harness.main import NoChip, measure, process_start
    out = BENCH.parent / "bench_out"
    out.mkdir(exist_ok=True)
    t_start = process_start()
    lines = []
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            result, cell, reqs = measure(args.workload, seed, args.seconds,
                                         False, t_start=t_start)
        except NoChip as e:
            print(f"calibrate: {e}", file=sys.stderr)
            return 2
        line = {"seed": seed, "correct": result["correct"],
                "program_gap": result["checks"]["max_logit_gap"]["value"],
                "tokens": sum(len(r.generated) for r in reqs),
                "requests": len(reqs)}
        if not args.no_control:
            t0 = time.perf_counter()
            line["control_gap"] = check.gaps(cell, seed, reqs, "fp8")[0]
            line["control_s"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)
        lines.append(line)
        t_start = time.perf_counter()
    with open(out / f"calibrate-{args.workload}.jsonl", "a") as f:
        f.writelines(json.dumps(x) + "\n" for x in lines)
    return 0


if __name__ == "__main__":
    sys.exit(main())
