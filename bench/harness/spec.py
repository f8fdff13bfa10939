"""Discovery: everything a cell needs is found by name under the bench root.

    BENCHMARK.json                  cells, metrics and bounds (repo root)
    bench/configs/<config>.json     sizes as run, source, reduced, engine shape
    bench/configs/<reference>.py    the family module a config names
    bench/traffic/<traffic>.json    parameters of one traffic mix
    bench/cells/<workload>.json     the cell's own parameters (rate, limits)
    bench/metrics/<metric>.py       one reader per metric: read(run) -> float|None
    bench/peaks.json                published peaks, keyed by device_kind

Adding a cell, a mix, a configuration or a metric adds files and entries;
no code here changes. A configuration of a new architecture brings its
family module, and no harness file names one.

A family module provides everything that depends on the architecture:

    dims(config)                the family's sizes object (below)
    program_config(name, d)     keyword arguments of the program's ArchConfig
    top_shapes(d)               {leaf: shape} outside the layers, in draw order
    layer_groups(d)             [(tree key, number of layers, {leaf: shape})],
                                layers numbered globally across the groups
    step_flops(d, rows, ctx)    work of one decode step of ``rows`` live rows
    step_bytes(d, rows, ctx)    attending to ``ctx`` positions in all
    logits(d, layer, top, seqs, quant=None)   the plain float32 reference

The sizes object ``d`` carries ``family``, its module, and the basics that
the generic harness (drivers, check, main, weights, readers) reads:
``vocab``, ``padded_vocab``, ``dtype``, ``max_batch``, ``max_seq`` and
``kv_block_size``. Everything else in it is the family's own.
"""
from __future__ import annotations

import hashlib
import importlib.util
import json
import re
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH = Path(__file__).resolve().parents[1]


def dims(config: Dict[str, Any], bench: Path = BENCH):
    """The sizes of a configuration file's contents, as its family gives
    them."""
    return reference_module(config, bench).dims(config)


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
    def dims(self):
        return dims(self.config, self.bench)

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
    """The module of ``path``, executed once per process. It is registered
    under a name unique to its path, so that a family's sizes object can
    find its module (``sys.modules``) and two bench trees never share one."""
    path = Path(path).resolve()
    tag = hashlib.sha1(str(path).encode()).hexdigest()[:12]
    key = "bench_" + re.sub(r"\W", "_", name) + "_" + tag
    if key in sys.modules:
        return sys.modules[key]
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[key]
        raise
    return mod


def metric_reader(name: str, bench: Path = BENCH):
    """``read(run)`` of ``bench/metrics/<name>.py``."""
    return load_module(bench / "metrics" / f"{name}.py", name).read


def reference_module(config: Dict[str, Any], bench: Path = BENCH):
    """The family module the configuration names (``"reference"``): its
    plain reference and everything else that depends on the architecture."""
    name = config["reference"]
    return load_module(bench / "configs" / f"{name}.py", name)


def peaks(device_kind: str, bench: Path = BENCH) -> Dict[str, Any]:
    """Published peaks of ``device_kind``; an unknown device is an error."""
    table = _json(bench / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table)})")
    return table[device_kind]
