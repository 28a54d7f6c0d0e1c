"""Ahead-of-time compiles for a described TPU v5e (no chip needed).

The TPU compiler refuses what the Pallas interpreter accepts: blocks that
break the (8, 128) tiling rule, layouts Mosaic and XLA disagree on,
broadcasts the vector unit cannot do.  Each kernel compiles here with
``interpret=False`` at the real width ``chip_smoke.py`` runs it at, and
the bert-mlm-120m one-chip train step compiles at seq 512 / batch 32.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library, and every
test worker imports every test file.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.fused_xent import fused_xent
from repro.kernels.paged_attention import paged_attention_fwd
from repro.kernels.ssd_scan import ssd_scan


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = jax.sharding.SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)


KERNELS = {
    # bert-mlm-120m: 12 heads of 64 at seq 512, not causal
    "flash_attention": lambda s: (
        lambda q, k, v: flash_attention_fwd(q, k, v, causal=False,
                                            interpret=False),
        [s((8, 512, 12, 64))] * 3),
    # bert-mlm-120m's 32768-token vocabulary
    "fused_xent": lambda s: (
        lambda logits, labels: fused_xent(logits, labels, interpret=False),
        [s((4096, 32768)), s((4096,), jnp.int32)]),
    # mamba2-130m: 24 heads of 64, state 128, one group, chunk 256
    "ssd_scan": lambda s: (
        lambda x, dt, A, B, C: ssd_scan(x, dt, A, B, C, 256,
                                        interpret=False),
        [s((2, 2048, 24, 64)), s((2, 2048, 24), jnp.float32),
         s((24,), jnp.float32), s((2, 2048, 1, 128)),
         s((2, 2048, 1, 128))]),
    # starcoder2-3b: 24 heads, 2 kv heads of 128, 16-token pages
    "paged_attention": lambda s: (
        lambda q, kp, vp, tbl, lens: paged_attention_fwd(
            q, kp, vp, tbl, lens, interpret=False),
        [s((8, 24, 128)), s((513, 16, 2, 128)), s((513, 16, 2, 128)),
         s((8, 64), jnp.int32), s((8,), jnp.int32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(spec, name):
    fn, args = KERNELS[name](spec)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_bert_train_step_compiles_for_one_v5e(topo):
    from repro.configs import default_run_config, get_config
    from repro.configs.base import ShapeConfig
    from repro.models import build_model
    from repro.train.optimizer import AdamWConfig
    from repro.train.runner import StepRunner

    cfg = get_config("bert-mlm-120m")
    cfg = dataclasses.replace(cfg, max_position=max(cfg.max_position, 512))
    run = default_run_config(cfg, ShapeConfig("cli", 512, 32, "train"))
    mesh = jax.sharding.Mesh(
        np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2)
    runner = StepRunner(build_model(cfg), run, AdamWConfig(total_steps=20),
                        mesh)
    mem = runner.lower().compile().memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < 16e9, f"{used / 1e9:.2f} GB does not fit a 16 GB v5e"
