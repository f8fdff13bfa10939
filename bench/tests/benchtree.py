"""A bench tree in a temporary directory with a cell of its own, for tests.

The tree holds only data and readers: a tiny dense GQA configuration
(d_model 256, 2 layers, vocab 4096) beside a copy of the real family
module, tiny batch and coldstart mixes and cells, copies of the real metric
readers and peaks, and a ``BENCHMARK.json`` that names the tiny cells
alongside the real metrics. The harness code that runs it is the
repository's own, unedited.
"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

TINY = {
    "source": "test configuration", "hidden_size": 256,
    "intermediate_size": 512, "num_attention_heads": 8,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 4096,
    "max_position_embeddings": 128, "rms_norm_eps": 1e-05,
    "rope_theta": 10000.0, "tie_word_embeddings": False,
    "torch_dtype": "bfloat16", "reference": "dense_gqa",
    "engine": {"max_batch": 4, "max_seq": 128, "kv_block_size": 16}}

LENGTHS = {"prompt": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                      "min": 4, "max": 48},
           "output": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                      "min": 2, "max": 24}}
MIXES = {"tinybatch": {"driver": "saturate", "block": 8, **LENGTHS},
         "tinycold": {"driver": "coldstart",
                      "prompt": {"dist": "fixed", "value": 12},
                      "output": {"dist": "fixed", "value": 1}}}
# program gap at this size is <= 0.004 and the float8 control's >= 0.04
# on the seeds the tests use (test_bench_reference): 0.02 separates them
LIMITS = {"max_logit_gap": 0.02}
CELLS = {"tiny.batch": "tinybatch", "tiny.coldstart": "tinycold"}


def _write(path: Path, obj):
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj, indent=1))


def make(tmp: Path, family: str = "dense_gqa") -> Path:
    """Build the tree under ``tmp``; returns its bench root. The tiny
    configuration names the family module ``family``, a copy of
    ``dense_gqa.py``: under another name it exists in this tree alone."""
    b = tmp / "bench"
    shutil.copytree(BENCH / "metrics", b / "metrics",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (b / "configs").mkdir()
    shutil.copy(BENCH / "configs" / "dense_gqa.py",
                b / "configs" / f"{family}.py")
    shutil.copy(BENCH / "peaks.json", b)
    _write(b / "configs" / "tiny.json", {**TINY, "reference": family})
    for name, mix in MIXES.items():
        _write(b / "traffic" / f"{name}.json", mix)
    for name in CELLS:
        _write(b / "cells" / f"{name}.json",
               {"params": {"check_requests": 64, "limits": LIMITS}})
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny", "source": "test",
                        "file": "bench/configs/tiny.json", "reduced": [],
                        "why": "test"}]
    spec["workloads"] = [{"name": n, "config": "tiny", "traffic": mix,
                          "chips": 1, "why": "test"}
                         for n, mix in CELLS.items()]
    cell_of = {"batch": "tiny.batch", "coldstart": "tiny.coldstart"}
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = sorted({cell_of[w.split(".")[-1]]
                                     for w in m["workloads"]})
    _write(tmp / "BENCHMARK.json", spec)
    (tmp / "src").symlink_to(REPO / "src")
    return b


def run(bench: Path, workload: str, seed: int = 7, seconds: float = 2.0,
        trace: bool = False) -> dict:
    """One run of a tiny cell on the CPU: the harness without its look
    for a chip and without the persistent compilation cache."""
    from harness.main import run as run_cell
    return run_cell(workload, seed, seconds, trace, bench=bench,
                    require_tpu=False, cache=False)
