"""BENCHMARK.json and the files it names."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness, peaks

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
WORKLOADS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_each_metric_has_a_reader_found_by_name(metric):
    mod = harness.load_module("metrics", metric)
    assert callable(mod.read)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_each_cell_loads_with_its_files(workload):
    spec = harness.load_spec(workload, ROOT)
    assert spec.config["name"] == {w["name"]: w for w in BENCH["workloads"]}[
        workload]["config"]
    assert set(spec.cell["limits"]) >= {"loss_gap", "grad_gap",
                                        "change_gap"}
    harness.load_module("sources", spec.traffic["source"])
    names = {m["name"] for m in spec.per_layer}
    assert {"mfu", "device_idle_share"} <= names


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_config_files_are_the_programs_configs(name):
    from repro.configs import get_config

    c = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
    assert harness.program_config(c) == get_config(c["program_arch"])


def test_unknown_device_kind_has_no_peaks():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v99")


def run_bench(cwd):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", WORKLOADS[0],
         "--seed", str(2 ** 33 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_run_exits_nonzero_without_a_tpu():
    p = run_bench(ROOT)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run_bench(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""
