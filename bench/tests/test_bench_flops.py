"""The operation counts against a brute count of the matmuls in a jaxpr."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops
from bench.reference import bert_mlm

from .conftest import small_config


def matmul_flops(jaxpr, times: int = 1) -> int:
    """2 x every multiply-add of every dot_general, sub-jaxprs included
    (a scan's body counts once per iteration)."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (lc, _), _ = eqn.params["dimension_numbers"]
            k = math.prod(eqn.invars[0].aval.shape[d] for d in lc)
            total += 2 * k * math.prod(eqn.outvars[0].aval.shape)
        n = eqn.params.get("length", 1) if eqn.primitive.name == "scan" \
            else 1
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    total += matmul_flops(inner, n)
    return times * total


def small_batch(c, B):
    S = c["seq_len"]
    z = jnp.zeros((B, S), jnp.int32)
    return {"tokens": z, "labels": z, "loss_mask": jnp.ones((B, S))}


def test_forward_parts_equal_the_reference_forward():
    c, B = small_config(), 3
    params = jax.eval_shape(lambda: bert_mlm.init_params(c, bert_mlm.seed_key(0)))
    jaxpr = jax.make_jaxpr(lambda p, b: bert_mlm.nll_sum(p, b, c))(
        params, small_batch(c, B)).jaxpr
    assert matmul_flops(jaxpr) == sum(flops.forward(c, B, c["seq_len"])
                                      .values())


def test_step_matmuls_equal_the_programs_train_step():
    """The program's step runs each matmul forward twice (remat of the
    layers, the checkpointed loss chunks) and backward once (2x)."""
    from repro.configs import default_run_config
    from repro.configs.base import ShapeConfig
    from repro.models import build_model
    from repro.train.train_step import abstract_state, make_train_step

    from bench import harness

    c, B = small_config(), 3
    cfg = harness.program_config(c)
    model = build_model(cfg)
    run = default_run_config(cfg, ShapeConfig("t", c["seq_len"], B,
                                              "train"))
    step = make_train_step(model, run, harness.adamw(c))
    jaxpr = jax.make_jaxpr(step)(abstract_state(model, run),
                                 small_batch(c, B)).jaxpr
    f, b = flops.step_matmuls(c, B, c["seq_len"])
    assert matmul_flops(jaxpr) == f
    assert b > 0


@pytest.mark.parametrize("name,per_token", [("bert-mlm-120m", 0.7208),
                                            ("bert-mlm-350m", 2.1706)])
def test_model_flops_per_token(name, per_token):
    """6 x the matmul parameters plus 12 L S d for attention."""
    import json
    from pathlib import Path

    c = json.loads((Path(__file__).resolve().parents[1] / "configs"
                    / f"{name}.json").read_text())
    got = flops.model_flops_per_step(c, 1, 512) / 512 / 1e9
    assert got == pytest.approx(per_token, abs=1e-4)


def test_cross_entropy_counts_every_logit():
    c = small_config()
    f, b = flops.cross_entropy(c, 2, 8)
    assert b == 4 * 2 * 8 * c["vocab_size"] and f > 0
