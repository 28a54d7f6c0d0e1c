"""Small specs for rehearsing the benchmark's phases on the CPU."""
import copy
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402

from bench import harness  # noqa: E402


def small_config():
    """bert-mlm-120m's file at a size the CPU runs in seconds."""
    c = json.loads((ROOT / "bench" / "configs" / "bert-mlm-120m.json")
                   .read_text())
    c.update(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
             intermediate_size=128, vocab_size=512,
             max_position_embeddings=32, seq_len=32)
    return c


LOOSE = {"loss_gap": 1e-3, "grad_gap": 1e-2, "change_gap": 1e-2,
         "batch_mismatch": 0.0}


def small_spec(traffic: str, *, batch: int = 4, chips: int = 1,
               corpus=None) -> harness.Spec:
    t = json.loads((ROOT / "bench" / "traffic" / f"{traffic}.json")
                   .read_text())
    t = copy.deepcopy(t)
    t["warmup_steps"] = 1
    if corpus is not None:
        t["n_functions"] = corpus
    cell = {"batch_per_chip": batch, "loader_workers": 2,
            "device_prefetch": 2, "step_s": 0.1, "reference_block_rows": 2 * chips,
            "limits": dict(LOOSE)}
    return harness.Spec(name=f"small.{traffic}", chips=chips,
                        config=small_config(), traffic=t, cell=cell,
                        end_to_end=[], per_layer=[])


@pytest.fixture
def spec_of():
    return small_spec


SEED = 2 ** 31 + 12345   # wider than 32 signed bits


def drive(spec, *, seed: int = SEED, fault=None, root=None,
          devices=None):
    """A whole run of ``spec`` on the CPU, less the look for a chip:
    set-up, a 2-step window, the comparison.  ``fault(setup)`` breaks the
    timed path before the first step.  Returns (values, correct)."""
    import jax

    devices = devices or jax.devices()[:spec.chips]
    n_ref = spec.traffic["reference_steps"]
    s = harness.build(spec, devices, seed, peak_flops=float("nan"),
                      root=root or ROOT, keep_steps=[*range(n_ref), 5])
    if fault is not None:
        fault(s)
    harness.setup_steps(s, n_ref, spec.traffic["warmup_steps"])
    win = harness.measure(s, 2)
    assert win.compiles == 0 and win.traces == 0
    batches = s.source.reference_batches(n_ref)
    values = s.source.check(s.feed.kept)
    prog = s.readings
    harness.release(s)
    ref = harness.reference_readings(spec, seed, batches, devices)
    values = {**harness.gaps(prog, ref), **values}
    return values, harness.is_correct(harness.checks(values,
                                                     spec.cell["limits"]))
