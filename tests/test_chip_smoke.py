"""CPU rehearsal of ``chip_smoke.py``: its phases at ``reduced()`` widths
with interpret-mode kernels, and its refusal to report without a TPU.

Only the phase functions are imported; the script's ``main`` runs solely
in subprocesses that must refuse, so no CPU run ever prints its ``ok``
line."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from _subproc import ROOT, run_py

sys.path.insert(0, ROOT)
import chip_smoke as cs  # noqa: E402

SMALL = ["--arch", "bert-mlm-120m", "--reduced", "--seq", "64",
         "--batch", "8", "--n-functions", "300"]


def test_train_and_resume_phases(tmp_path):
    first = cs.phase_train(tmp_path, SMALL)
    assert sorted(first) == list(range(1, cs.TRAIN_STEPS + 1))
    # the CPU resumes bit-exactly
    assert cs.phase_resume(tmp_path, first, SMALL) == 0.0


def test_kernel_phase_matches_oracles():
    errs = cs.phase_kernels(small=True)
    assert sorted(errs) == ["flash_attention", "fused_xent",
                            "paged_attention", "ssd_scan"]
    assert all(e <= 1.0 for e in errs.values()), errs


def test_multichip_phase_on_four_virtual_devices(tmp_path):
    out = run_py(f"""
        import json, sys
        from pathlib import Path
        sys.path.insert(0, {ROOT!r})
        import chip_smoke as cs
        rel = cs.phase_multichip(Path({str(tmp_path)!r}), {SMALL!r}, 4)
        print('REL', json.dumps(rel))
    """, n_devices=4)
    assert "[smoke] ddp: params on 4 devices, 1.000" in out
    assert "[smoke] fsdp: params on 4 devices" in out
    rel = json.loads(out.split("REL ")[-1])
    assert sorted(rel) == ["ddp", "fsdp"]
    assert all(r <= cs.MULTICHIP_RTOL for r in rel.values()), rel


@pytest.mark.parametrize("alone", [False, True])
def test_script_refuses_without_a_tpu(tmp_path, alone):
    script = os.path.join(ROOT, "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if alone:  # a directory holding the script and nothing else
        script = shutil.copy(script, tmp_path / "chip_smoke.py")
        env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, str(script)], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_location(tmp_path, env_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cc")
        env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    body = """
import os, jax
from repro.launch.compile_cache import init_compile_cache
where = init_compile_cache()
assert jax.config.jax_compilation_cache_dir == where, where
if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.jit(lambda x: x * 2.0)(1.0).block_until_ready()
    assert os.listdir(where), "nothing cached"
print(where)
"""
    out = subprocess.run([sys.executable, "-c", body], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    where = out.stdout.strip().splitlines()[-1]
    if env_dir:
        assert where == str(tmp_path / "cc")
    else:  # a fixed directory in the checkout, kept out of git
        assert where == os.path.join(ROOT, ".jax_cache")
        with open(os.path.join(ROOT, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
