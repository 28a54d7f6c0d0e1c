"""The four-chip cell's path on four virtual CPU devices: the bucketed
ddp step agrees with the reference, and with the gradient exchange left
out it does not."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]

SCRIPT = r"""
import json, sys
sys.path[:0] = [{root!r}, {root!r} + "/src"]
from bench.tests.conftest import drive, small_spec
if {fault!r}:
    from repro.distributed import gradsync
    gradsync.bucketed_psum = lambda grads, axis, buckets: grads
spec = small_spec("train.resident", batch=2, chips=4)
values, correct = drive(spec)
print(json.dumps({{"values": values, "correct": correct}}))
"""


def run(fault: bool):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c",
                        SCRIPT.format(root=str(ROOT), fault=fault)],
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault", [False, True],
                         ids=["synced", "exchange_left_out"])
def test_ddp_on_four_devices(fault):
    out = run(fault)
    assert out["correct"] is (not fault), out
