"""``chip_smoke.py`` on the CPU: it refuses to report without a TPU, and its
phases run end to end at a reduced size (one device in-process; the TP=4
leg on four CPU placeholder devices in a child process)."""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
SMOKE = ROOT / "chip_smoke.py"


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_exits_nonzero_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(SMOKE)], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    assert '"ok"' not in r.stdout


def test_one_chip_phases_reduced(tmp_path):
    smoke = _load_smoke()
    reqs = smoke.seeded_requests(0, 256, prompt_lens=(8, 60), new_tokens=8)
    out = smoke.one_chip("smollm-360m-reduced", 0, tmp_path / "s.fndry",
                         max_seq=128, requests=reqs)
    assert len(out["streams"]) == len(reqs)
    assert all(len(toks) == 8 for _, toks in out["streams"])


FOUR_CHIPS = """
import importlib.util, json
from pathlib import Path
spec = importlib.util.spec_from_file_location("chip_smoke", {smoke!r})
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
reqs = smoke.seeded_requests(0, 256, prompt_lens=(8, 60), new_tokens=8)
streams = smoke.four_chips("smollm-360m-reduced", 0,
                           Path({archive!r}), max_seq=128, requests=reqs)
print("RESULT", json.dumps(len(streams["stamped"])))
"""


def test_four_chip_phase_on_placeholders(tmp_path):
    from repro.core.collective_stub import run_in_capture_process
    r = run_in_capture_process(
        FOUR_CHIPS.format(smoke=str(SMOKE), archive=str(tmp_path / "a.fndry")),
        4, timeout=900, pythonpath=str(ROOT / "src"))
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "mode=foundry-stamped" in r.stdout
    assert "[stamped] after step 1: params on [0, 1, 2, 3]" in r.stdout
    assert "[fallback] after step 1: params on [0, 1, 2, 3]" in r.stdout
    line = [x for x in r.stdout.splitlines() if x.startswith("RESULT")][-1]
    assert json.loads(line.split(" ", 1)[1]) == 8
