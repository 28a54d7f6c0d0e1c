"""Reproduces the paper's scaling analysis (Fig. 1 + R4/R5) analytically,
then measures the async training loop's telemetry on this host.

Prints samples/s vs worker count for the 120M and 350M MLM models on the
paper's hardware (H100-NVL, 25 GbE) and on the TPU v5e target, plus the
R5 max-batch table, and finally a measured run through the sharding-aware
StepRunner/TrainLoop (step-time EMA, tokens/s, hlocost-MFU, host-stall
fraction).

  PYTHONPATH=src python examples/scaling_study.py
"""
import dataclasses
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.configs import get_config, reduced
from repro.core import (DPScalingModel, H100_NVL, MemoryModel, TPU_V5E,
                        dp_scaling_curve)

print("== Fig. 1: DP scaling (samples/s) ==")
for arch, b in (("bert-mlm-120m", 184), ("bert-mlm-350m", 20)):
    cfg = get_config(arch)
    for chip, name in ((H100_NVL, "H100-NVL/25GbE"), (TPU_V5E, "TPUv5e/ICI")):
        curve = dp_scaling_curve(cfg, per_dev_batch=b, chip=chip, seq=512)
        xs = sorted(curve)
        line = " ".join(f"{n}:{curve[n]['samples_per_s']:.0f}" for n in xs)
        print(f"{arch:16s} b={b:3d} {name:16s} {line}")
        print(f"{'':16s}      efficiency@256 = "
              f"{curve[256]['efficiency']:.2f}")

print()
print("== R5: memory-limited max per-device batch (seq 512) ==")
for arch in ("bert-mlm-120m", "bert-mlm-350m"):
    mm = MemoryModel(get_config(arch), act_factor=150.0)
    print(f"{arch:16s} H100-NVL(94GB): {mm.max_batch(512, H100_NVL.hbm_bytes):4d}"
          f"   TPUv5e(16GB): {mm.max_batch(512, TPU_V5E.hbm_bytes):4d}")
print("paper observed: 184 (120M) vs 20 (350M) per H100")
print()
print("== R5 -> beyond-paper: state sharding recovers the batch ==")
cfg = get_config("gemma3-4b")
for shards in (1, 16, 256):
    mm = MemoryModel(cfg, state_shards=shards)
    print(f"gemma3-4b seq=4096, state sharded {shards:3d}x: "
          f"max batch/device = {mm.max_batch(4096, TPU_V5E.hbm_bytes)}")

print()
print("== measured: async loop telemetry over the deterministic pipeline ==")
import tempfile

from repro.configs.base import RunConfig, ShapeConfig
from repro.data import DataPipeline
from repro.launch.mesh import make_host_mesh
from repro.models import build_model
from repro.train.optimizer import AdamWConfig
from repro.train.runner import StepRunner, TrainLoop

B, S, STEPS = 8, 64, 12
mcfg = dataclasses.replace(reduced(get_config("bert-mlm-120m"), d_model=128),
                           vocab_size=512, max_position=S)
model = build_model(mcfg)
run = RunConfig(model=mcfg, shape=ShapeConfig("s", S, B, "train"),
                sharding="ddp", param_dtype="float32",
                activation_dtype="float32")


def lm_work(batch, rng):
    toks = batch["tokens"]
    return {"tokens": toks, "labels": np.roll(toks, -1, axis=1),
            "loss_mask": batch["attn_mask"]}


with tempfile.TemporaryDirectory() as tmp:
    pipeline = DataPipeline.build(tmp, n_functions=300, seq_len=S,
                                  batch_size=B, vocab_size=mcfg.vocab_size,
                                  max_merges=60, n_workers=2, seed=0,
                                  work_fn=lm_work)
    runner = StepRunner(model, run, AdamWConfig(total_steps=STEPS),
                        make_host_mesh())
    _, mlog = TrainLoop(runner, log_every=4).run(pipeline, STEPS)
    pipeline.close()
t = mlog.telemetry
print(f"bert-mlm-120m(reduced) b={B} seq={S}: "
      f"step_ema={t['step_time_ema']*1e3:.1f}ms "
      f"tokens/s={t['tokens_per_s']:.0f} "
      f"host_stall={t['stall_fraction']*100:.1f}% "
      f"mfu={mlog.mfu[-1]:.2e} compiles={t['n_traces']:.0f}")
