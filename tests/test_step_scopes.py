"""The train step names its parts on the device.

The step runs what it differentiates under ``step_forward``, each
layer's sublayers under ``attention`` and ``ffn``, the loss head under
``loss_head`` and AdamW under ``optimizer``; JAX adds ``jvp(...)`` on
the forward pass, ``transpose(jvp(...))`` on the backward pass and
``rematted_computation`` on what the backward pass recomputes.  The
device trace carries these in each compiled operation's ``op_name``,
and the benchmark's per-layer metrics (``bench/scopes.py``) read them
there.  Compiled here for a small BERT-MLM on one CPU device, and as
the bucketed data-parallel step on four, so that a refactor that drops
a scope fails before the chip sees it.
"""
import json

import pytest

from _subproc import run_py
from repro.distributed.sharding import GRAD_SYNC_BUCKETED, GRAD_SYNC_NONE

BODY = """
import dataclasses, json, re
import jax, numpy as np
from repro.configs import get_config, reduced
from repro.configs.base import RunConfig, ShapeConfig
from repro.launch.mesh import make_host_mesh
from repro.models import build_model
from repro.train.optimizer import AdamWConfig
from repro.train.runner import StepRunner

n = len(jax.devices())
B, S, V = 4 * n, 32, 256
cfg = dataclasses.replace(reduced(get_config("bert-mlm-120m"), d_model=32),
                          vocab_size=V, max_position=S)
run = RunConfig(model=cfg, shape=ShapeConfig("t", S, B, "train"),
                sharding="ddp", param_dtype="float32",
                activation_dtype="float32", remat=True)
runner = StepRunner(build_model(cfg), run, AdamWConfig(),
                    make_host_mesh(n, 1))
toks = np.random.default_rng(0).integers(4, V, (B, S)).astype(np.int32)
batch = {"tokens": toks, "labels": toks,
         "loss_mask": np.ones((B, S), np.float32)}
runner.compile(runner.init_state(0), batch)
ops, matmuls = [], []
for line in runner.compiled.as_text().splitlines():
    m = re.search(r'op_name="([^"]*)"', line)
    if m:
        ops.append(m.group(1))
        if re.search(r"= \\S+ (dot|convolution)\\(", line):
            matmuls.append(m.group(1))
print(json.dumps({"grad_sync": runner.grad_sync_info()["grad_sync"],
                  "ops": ops, "matmuls": matmuls}))
"""

PHASES = ("jvp(step_forward)", "transpose(jvp(step_forward))",
          "rematted_computation", "optimizer")
PARTS = ("attention", "ffn", "loss_head")


def components(op_name):
    return set(op_name.split("/"))


@pytest.mark.parametrize("devices, grad_sync",
                         [(1, GRAD_SYNC_NONE), (4, GRAD_SYNC_BUCKETED)])
def test_step_scopes_reach_the_compiled_step(devices, grad_sync):
    got = json.loads(run_py(BODY, n_devices=devices).splitlines()[-1])
    assert got["grad_sync"] == grad_sync
    names = set().union(*map(components, got["ops"]))
    for scope in PHASES + PARTS:
        assert scope in names, scope
    if grad_sync == GRAD_SYNC_BUCKETED:
        assert any(n.startswith("gradsync_bucket_") for n in names)
    assert got["matmuls"]
    for op_name in got["matmuls"]:
        assert components(op_name) & set(PARTS), op_name
