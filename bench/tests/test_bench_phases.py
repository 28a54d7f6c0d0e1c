"""A CPU rehearsal of the benchmark's phases at small widths (never
``main``, so no result line exists off the chip), and the control."""
import json

import jax.numpy as jnp
import pytest

from bench import harness

from .conftest import ROOT, SEED, drive, small_spec


@pytest.mark.parametrize("traffic", ["train.resident", "train.pipeline"])
def test_sound_run_matches_the_reference(traffic):
    values, correct = drive(small_spec(traffic, corpus=40))
    assert correct, values
    # both sides are float32 on the CPU: agreement to round-off
    assert values["loss_gap"] < 1e-5 and values["grad_gap"] < 1e-4


def test_control_in_lower_precision_is_not_correct():
    """The reference in bfloat16 put in the program's place fails the
    comparison that the float32 program passes, under each cell's own
    limits."""
    spec = small_spec("train.resident", batch=8)
    from bench.sources.resident import control_batches

    batches = control_batches(spec, 8, SEED, ROOT, 3)
    import jax

    dev = jax.devices()[:1]
    truth = harness.reference_readings(spec, SEED, batches, dev)
    control = harness.reference_readings(spec, SEED, batches, dev,
                                         dtype=jnp.bfloat16)
    values = harness.gaps(control, truth)
    for w in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]:
        limits = json.loads((ROOT / "bench" / "workloads"
                             / f"{w['name']}.json").read_text())["limits"]
        values_w = {k: v for k, v in values.items() if k in limits}
        assert not harness.is_correct(harness.checks(values_w, limits)), (
            w["name"], values)


def test_grad_gap_sees_the_gradient_scale_under_clipping():
    """Clipping leaves the first moment alike whatever the gradient's
    scale; the norm the program clipped by brings the scale back, so a
    gradient twice too large reads a gap of 1."""
    import jax
    import numpy as np

    o = {"b1": 0.9, "grad_clip": 1.0}
    g = {"a": np.full((3, 4), 2.0, np.float32),
         "b": np.arange(5, dtype=np.float32)}
    norm = float(np.sqrt(sum(np.sum(x * x) for x in g.values())))
    truth = [float(np.linalg.norm(x)) for x in jax.tree_util.tree_leaves(g)]

    def readings(k):   # a program whose gradient is k times the truth
        mu = jax.tree_util.tree_map(
            lambda x: (1 - o["b1"]) * k * x * min(1.0, 1.0 / (k * norm)), g)
        return harness.first_grad_norms(o, mu, k * norm)

    for k, gap in ((1.0, 0.0), (2.0, 1.0)):
        values = harness.gaps(
            {"losses": [1.0], "grad_norms": readings(k), "change_norms": truth},
            {"losses": [1.0], "grad_norms": truth, "change_norms": truth})
        assert values["grad_gap"] == pytest.approx(gap, abs=1e-6)
