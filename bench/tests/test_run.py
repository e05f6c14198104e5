"""The command refuses to measure without a TPU, and the benchmark file
is well formed."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from .smoke import ROOT

CMD = ["bench/run.py", "--workload", "olmo-1b.decode", "--seed",
       str(2**33 + 3), "--seconds", "1", "--trace", "0"]


def run_in(cwd):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, *CMD], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    p = run_in(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def test_benchmark_files_alone_do_not_measure(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run_in(tmp_path)
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def test_every_cell_finds_its_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    configs = {c["name"]: c for c in bench["configs"]}
    for wl in bench["workloads"]:
        cfg = configs[wl["config"]]
        spec = json.loads((ROOT / cfg["file"]).read_text())
        assert spec["name"] == cfg["name"] and "max_logit_gap" in \
            spec["limits"]
        mix = json.loads((ROOT / "bench" / "traffic" /
                          f"{wl['traffic']}.json").read_text())
        assert (ROOT / "bench" / "loops" / f"{mix['kind']}.py").exists()
    for m in bench["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}


@pytest.mark.parametrize("key", ["end_to_end", "per_layer"])
def test_metric_cells_exist(key):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench[key]:
        assert set(m.get("workloads", cells)) <= cells
