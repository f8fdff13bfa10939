"""The harness finds cells, configurations, mixes and metrics by name: a
cell added in a temporary directory, as data alone, runs end to end on the
CPU with no code edited. And the command refuses to run without a TPU."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

import benchtree
from harness import spec


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return benchtree.make(tmp_path_factory.mktemp("benchtree"))


def test_real_benchmark_cells_resolve():
    """Every cell of BENCHMARK.json finds its configuration, mix, cell file,
    reference and a reader for each of its metrics."""
    bm = json.loads((benchtree.REPO / "BENCHMARK.json").read_text())
    for w in bm["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.dims.layers == cell.config["num_hidden_layers"]
        assert spec.reference_module(cell.config).logits
        for trace in (False, True):
            names = [m["name"] for m in cell.metrics(trace)]
            assert names, (w["name"], trace)
            for n in names:
                assert callable(spec.metric_reader(n))
        assert "setup_s" in [m["name"] for m in cell.metrics(False)]
    with pytest.raises(KeyError):
        spec.load_cell("no-such.cell")


@pytest.mark.parametrize("workload,metrics", [
    ("tiny.batch", {"setup_s", "output_tokens_per_s"}),
    ("tiny.coldstart", {"setup_s", "cold_start_s"}),
])
def test_added_cell_runs_with_no_code_edit(tree, workload, metrics):
    r = benchtree.run(tree, workload)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == metrics
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["device"]["platform"] == "cpu"
    assert list(r)[-1] == "checks"
    assert all(v["value"] <= v["limit"] for v in r["checks"].values())


def test_traced_run_reports_per_layer_metrics(tree):
    """With --trace 1 the line holds per-layer metrics and the device's
    busy and window seconds; a CPU trace has no device plane, so the
    device-trace metrics are left out rather than read as 0."""
    r = benchtree.run(tree, "tiny.batch", trace=True)
    assert set(r["metrics"]) == {"step_ms.batch"}
    assert r["device"]["window_s"] > 0 and "breakdown" in r
    assert r["correct"]


def _bench_cmd(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "smollm-360m.coldstart",
         "--seed", str(2**33 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_result():
    p = _bench_cmd(benchtree.REPO)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "tpu" in p.stderr.lower()


def test_bare_checkout_exits_nonzero_without_result(tmp_path):
    """A directory with only BENCHMARK.json and the bench files."""
    import shutil
    shutil.copy(benchtree.REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(benchtree.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    p = _bench_cmd(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
