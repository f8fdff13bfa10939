"""Discovery: everything a cell needs is found by name under the bench root.

    BENCHMARK.json                  cells, metrics and bounds (repo root)
    bench/configs/<config>.json     sizes as run, source, reduced, engine shape
    bench/configs/<reference>.py    the plain reference a config names
    bench/traffic/<traffic>.json    parameters of one traffic mix
    bench/cells/<workload>.json     the cell's own parameters (rate, limits)
    bench/metrics/<metric>.py       one reader per metric: read(run) -> float|None
    bench/peaks.json                published peaks, keyed by device_kind

Adding a cell, a mix, a configuration or a metric adds files and entries;
no code here changes.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH = Path(__file__).resolve().parents[1]


@dataclass(frozen=True)
class Dims:
    """The sizes of a dense GQA decoder as its configuration file states
    them (Hugging Face ``config.json`` keys), plus the engine shape."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool
    rope_theta: float
    norm_eps: float
    dtype: str
    max_batch: int
    max_seq: int
    kv_block_size: int

    @property
    def padded_vocab(self) -> int:
        """Rows of the embedding table: the vocab rounded up to 256."""
        return -(-self.vocab // 256) * 256

    @property
    def dtype_bytes(self) -> int:
        return {"bfloat16": 2, "float16": 2, "float32": 4}[self.dtype]


def dims(config: Dict[str, Any]) -> Dims:
    """``Dims`` of a configuration file's contents."""
    D, H = config["hidden_size"], config["num_attention_heads"]
    eng = config["engine"]
    return Dims(
        layers=config["num_hidden_layers"], d_model=D, heads=H,
        kv_heads=config["num_key_value_heads"],
        head_dim=config.get("head_dim") or D // H,
        d_ff=config["intermediate_size"], vocab=config["vocab_size"],
        tied=bool(config["tie_word_embeddings"]),
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        dtype=config["torch_dtype"], max_batch=eng["max_batch"],
        max_seq=eng["max_seq"], kv_block_size=eng["kv_block_size"])


@dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its files loaded."""
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    params: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    bench: Path = field(default=BENCH)

    @property
    def dims(self) -> Dims:
        return dims(self.config)

    def metrics(self, trace: bool) -> List[Dict[str, Any]]:
        """The metrics this cell reports: end-to-end ones with ``trace``
        off, per-layer ones with it on."""
        return self.per_layer if trace else self.end_to_end


def _json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _applies(metric: Dict[str, Any], cell: str, e2e_names: List[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" in metric:  # per-layer without a list: every cell of its metric
        return metric["moves"] in e2e_names
    return True


def load_cell(workload: str, bench: Path = BENCH,
              benchmark: Optional[Path] = None) -> Cell:
    """The cell named ``workload``; KeyError when BENCHMARK.json has none."""
    spec = _json(benchmark or bench.parent / "BENCHMARK.json")
    entry = next((w for w in spec["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    conf = next(c for c in spec["configs"] if c["name"] == entry["config"])
    config = _json(bench.parent / conf["file"])
    traffic = _json(bench / "traffic" / f"{entry['traffic']}.json")
    params = _json(bench / "cells" / f"{workload}.json")["params"]
    e2e = [m for m in spec["end_to_end"] if _applies(m, workload, [])]
    names = [m["name"] for m in e2e]
    per_layer = [m for m in spec["per_layer"] if _applies(m, workload, names)]
    return Cell(workload, int(entry["chips"]), entry["config"], config,
                entry["traffic"], traffic, params, e2e, per_layer, bench)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(
        "bench_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench: Path = BENCH):
    """``read(run)`` of ``bench/metrics/<name>.py``."""
    return load_module(bench / "metrics" / f"{name}.py", name).read


def reference_module(config: Dict[str, Any], bench: Path = BENCH):
    """The plain reference the configuration names (``"reference"``)."""
    name = config["reference"]
    return load_module(bench / "configs" / f"{name}.py", name)


def peaks(device_kind: str, bench: Path = BENCH) -> Dict[str, Any]:
    """Published peaks of ``device_kind``; an unknown device is an error."""
    table = _json(bench / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table)})")
    return table[device_kind]
