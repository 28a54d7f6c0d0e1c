"""A second model is new files only: a copy of ``bench/`` and
``BENCHMARK.json`` takes a configuration whose ``reference`` names a
module of its own, that module and a cell, edits none of its files, and
runs the cell through set-up, the window, the comparison and the
``mfu`` and ``matmul_roofline`` readers.  The module wraps BERT-MLM's
reference under its own name, with counts of its own, and records what
the harness called of it."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from .conftest import LOOSE, small_config

ROOT = Path(__file__).resolve().parents[2]
NAME = "mlm-wrapped"
CELL = f"{NAME}.train.resident"

MODULE = '''"""BERT-MLM's reference under another name, with counts of its own."""
import math

import jax

from bench.reference import bert_mlm
from bench.reference.core import is_shape

USED = set()


def _recorded(name, fn):
    def call(*args, **kw):
        USED.add(name)
        return fn(*args, **kw)
    return call


param_shapes = _recorded("param_shapes", bert_mlm.param_shapes)
init_params = _recorded("init_params", bert_mlm.init_params)
nll_sum = _recorded("nll_sum", bert_mlm.nll_sum)
program_config = _recorded("program_config", bert_mlm.program_config)


def n_params(c):
    return sum(math.prod(shape) for shape, _ in jax.tree_util.tree_leaves(
        bert_mlm.param_shapes(c), is_leaf=is_shape))


def model_flops_per_step(c, B, S):
    USED.add("model_flops_per_step")
    return 6.0 * n_params(c) * B * S


def step_matmuls(c, B, S):
    USED.add("step_matmuls")
    return 8.0 * n_params(c) * B * S, 4.0 * n_params(c)
'''

SCRIPT = r"""
import json, sys
from pathlib import Path
root = Path(sys.argv[1])
sys.path[:0] = [str(root), sys.argv[2]]
from bench import harness
from bench import trace as tr
from bench.tests.conftest import drive

spec = harness.load_spec(sys.argv[3], root)
values, correct = drive(spec, root=root)
m = harness.model_of(spec.config)
c, B, S = spec.config, spec.cell["batch_per_chip"], spec.config["seq_len"]
# one chip, 2 steps, a window of 100 ns with 20 ns of matmul
ctx = tr.Context(config=c, batch=B, chips=1, steps=2,
                 peak={"bf16_flops": 1e9, "hbm_bytes_per_s": 1e9},
                 trace=tr.Trace((0, 100), {"/device:TPU:0": [
                     tr.Op("fusion.1", 10, 30, "fusion:matmul",
                           "jit(step)/dot")]}, []))
read = {n: harness.load_module("metrics", n).read(ctx)
        for n in ("mfu", "matmul_roofline")}
print(json.dumps({
    "values": values, "correct": correct, "used": sorted(m.USED),
    "module": m.__file__, "harness": harness.__file__, **read,
    "flops_per_step": m.model_flops_per_step(c, B, S),
    "step_matmuls": m.step_matmuls(c, B, S)}))
"""


def digests(root: Path):
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def add_model(root: Path):
    """The new files, and the new entries appended to BENCHMARK.json."""
    c = small_config()
    c.update(name=NAME, reference="mlm_wrapped")
    (root / "bench" / "configs" / f"{NAME}.json").write_text(json.dumps(c))
    (root / "bench" / "reference" / "mlm_wrapped.py").write_text(MODULE)
    limits = {k: v for k, v in LOOSE.items() if k != "batch_mismatch"}
    (root / "bench" / "workloads" / f"{CELL}.json").write_text(json.dumps(
        {"batch_per_chip": 4, "step_s": 0.1, "reference_block_rows": 2,
         "limits": limits}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": NAME, "source": "a test", "reduced": [],
        "file": f"bench/configs/{NAME}.json", "why": "a test"})
    bench["workloads"].append({"name": CELL, "config": NAME,
                               "traffic": "train.resident", "chips": 1,
                               "why": "a test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def test_second_model_as_new_files_only(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root)
    before = digests(root / "bench")
    add_model(root)

    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "-c", SCRIPT, str(root), str(ROOT / "src"), CELL],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])

    after = digests(root / "bench")
    assert {k: after[k] for k in before} == before     # nothing edited
    bench = json.loads((root / "BENCHMARK.json").read_text())
    parent = json.loads((ROOT / "BENCHMARK.json").read_text())
    for key in ("configs", "workloads"):
        assert bench[key][:len(parent[key])] == parent[key]
    assert {k: v for k, v in bench.items()
            if k not in ("configs", "workloads")} == {
        k: v for k, v in parent.items() if k not in ("configs", "workloads")}

    assert Path(out["harness"]).parent == root / "bench"
    assert Path(out["module"]) == root / "bench" / "reference" / \
        "mlm_wrapped.py"
    assert out["correct"], out["values"]
    assert out["used"] == sorted(
        ["param_shapes", "init_params", "nll_sum", "program_config",
         "model_flops_per_step", "step_matmuls"])
    # the readers' numbers are the new module's counts, not BERT's
    from bench import flops

    c = small_config()
    f, b = out["step_matmuls"]
    assert out["mfu"] == pytest.approx(
        100.0 * out["flops_per_step"] * 2 / (1e-7 * 1e9), rel=1e-12)
    assert out["matmul_roofline"] == pytest.approx(
        100.0 * max(f * 2 / 1e9, b * 2 / 1e9) / 20e-9, rel=1e-12)
    assert out["flops_per_step"] != flops.model_flops_per_step(
        c, 4, c["seq_len"])
    assert f != flops.step_matmuls(c, 4, c["seq_len"])[0]
