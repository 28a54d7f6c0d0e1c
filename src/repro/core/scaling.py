"""Analytic scaling models — the paper's recommendations as equations.

* ``param_count``        — exact parameter count from the spec tree.
* ``MemoryModel``        — HBM footprint of a training step; solves the
                           paper's R5 "max per-device batch" limit.
* ``dp_scaling_curve``   — R4: samples/s vs #workers under a
                           compute/communication overlap model.
* ``model_flops``        — 6·N·D (dense) / 6·N_active·D (MoE) for the
                           roofline "useful FLOPs" ratio.

Hardware constants default to the TPU v5e target (see DESIGN.md §2); the
paper's H100-NVL numbers are provided for reproducing Fig. 1 / R5 as
published.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, Optional

import jax
import numpy as np

from repro.configs.base import ModelConfig


# ---------------------------------------------------------------------------
# Hardware descriptions
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Chip:
    name: str
    peak_flops: float          # bf16 FLOP/s
    hbm_bytes: float
    hbm_bw: float              # bytes/s
    link_bw: float             # bytes/s per ICI/NVLink-class link
    net_bw: float              # bytes/s inter-node (DCN / 25GbE)


# Peak dense bf16 FLOP/s of one chip, keyed by ``jax.Device.device_kind``.
# "TPU v5 lite": 197 TFLOP/s, Google Cloud documentation, "TPU v5e".
PEAK_FLOPS: Dict[str, float] = {"TPU v5 lite": 197e12}


def device_peak_flops(device) -> float:
    """Peak bf16 FLOP/s of ``device`` for MFU: NaN on the CPU, which has
    no meaningful MFU; an accelerator whose ``device_kind`` is not in
    :data:`PEAK_FLOPS` raises rather than borrowing another chip's peak."""
    if device.platform == "cpu":
        return float("nan")
    try:
        return PEAK_FLOPS[device.device_kind]
    except KeyError:
        raise KeyError(f"no peak FLOP/s on record for device kind "
                       f"{device.device_kind!r}; add it to PEAK_FLOPS "
                       "with its published source") from None


TPU_V5E = Chip("tpu-v5e", PEAK_FLOPS["TPU v5 lite"], 16e9, 819e9, 50e9,
               25e9)
H100_NVL = Chip("h100-nvl", 835e12, 94e9, 3.9e12, 300e9, 25e9 / 8)  # 25 GbE


# ---------------------------------------------------------------------------
# Parameter counting (exact, from the spec tree)
# ---------------------------------------------------------------------------


def param_count(cfg: ModelConfig, active_only: bool = False) -> int:
    from repro.models.transformer import model_specs

    specs = model_specs(cfg)
    leaves = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: hasattr(x, "shape") and hasattr(x, "axes")
    )[0]
    total = 0
    for path, leaf in leaves:
        n = int(np.prod(leaf.shape))
        keys = [getattr(p, "key", getattr(p, "idx", None)) for p in path]
        if active_only and cfg.moe is not None and "moe" in keys \
                and any(k in ("wi", "wg", "wo") for k in keys):
            n = int(n * (cfg.moe.top_k / cfg.moe.n_experts))
        total += n
    return total


def model_flops(cfg: ModelConfig, tokens: int) -> float:
    """MODEL_FLOPS = 6·N·D with N = active params (fwd+bwd); for inference
    callers scale by 1/3 (2·N·D)."""
    return 6.0 * param_count(cfg, active_only=True) * tokens


# ---------------------------------------------------------------------------
# Memory model (R5)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MemoryModel:
    """HBM bytes for one training step.

    state: params(pb) + grads(pb) + adam mu,nu (2×4B), sharded over
    ``state_shards`` (1 = pure DDP, the paper's setting).
    activations: with remat-at-block-boundaries, ~``act_factor`` × d_model
    bytes per token per layer survive the forward pass.
    """

    cfg: ModelConfig
    param_bytes: int = 2           # bf16
    opt_bytes: int = 8             # two f32 moments
    act_factor: float = 14.0       # boundary + attention workspace, bf16
    state_shards: int = 1

    def state_bytes(self) -> float:
        n = param_count(self.cfg)
        return n * (2 * self.param_bytes + self.opt_bytes) / self.state_shards

    def act_bytes(self, batch: int, seq: int) -> float:
        return (self.act_factor * self.cfg.d_model * self.cfg.n_layers
                * batch * seq)

    def step_bytes(self, batch: int, seq: int) -> float:
        return self.state_bytes() + self.act_bytes(batch, seq)

    def max_batch(self, seq: int, hbm: float, reserve: float = 0.10) -> int:
        """R5: largest per-device batch that fits (0 => doesn't fit at all)."""
        budget = hbm * (1 - reserve) - self.state_bytes()
        if budget <= 0:
            return 0
        per_sample = self.act_factor * self.cfg.d_model * self.cfg.n_layers * seq
        return int(budget // per_sample)


# ---------------------------------------------------------------------------
# DP scaling model (R4 / Fig. 1)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class DPScalingModel:
    """samples/s vs worker count for synchronous data parallelism.

    compute:  per-device step time = flops_per_sample·b / (peak·mfu)
    comm:     ring all-reduce of gradients, 2·P·(n-1)/n bytes per device,
              overlapped with the backward pass by ``overlap``.
    input:    per-device data-loading time; 0 once R1-R3 are applied, the
              pre-optimization pipeline is modeled with loader_s > 0.
    """

    cfg: ModelConfig
    chip: Chip = TPU_V5E
    seq: int = 512
    mfu: float = 0.45
    overlap: float = 0.9
    grad_bytes: int = 2
    loader_s: float = 0.0

    def step_time(self, per_dev_batch: int, n_devices: int,
                  intra: int = 2) -> float:
        P = param_count(self.cfg)
        tokens = per_dev_batch * self.seq
        t_compute = model_flops(self.cfg, tokens) / (self.chip.peak_flops * self.mfu)
        if n_devices > 1:
            vol = 2 * P * self.grad_bytes * (n_devices - 1) / n_devices
            # slowest hop: intra-node link for n<=intra, network beyond
            bw = self.chip.link_bw if n_devices <= intra else self.chip.net_bw
            t_comm = vol / bw
        else:
            t_comm = 0.0
        t_exposed = max(0.0, t_comm - self.overlap * t_compute)
        return t_compute + t_exposed + self.loader_s

    def samples_per_s(self, per_dev_batch: int, n_devices: int) -> float:
        return per_dev_batch * n_devices / self.step_time(per_dev_batch, n_devices)

    def efficiency(self, per_dev_batch: int, n_devices: int) -> float:
        ideal = self.samples_per_s(per_dev_batch, 1) * n_devices
        return self.samples_per_s(per_dev_batch, n_devices) / ideal


def dp_scaling_curve(cfg: ModelConfig, per_dev_batch: int,
                     device_counts=(1, 2, 4, 8, 16, 32, 64, 128, 256),
                     **kw) -> Dict[int, Dict[str, float]]:
    m = DPScalingModel(cfg, **kw)
    return {
        n: {
            "samples_per_s": m.samples_per_s(per_dev_batch, n),
            "efficiency": m.efficiency(per_dev_batch, n),
        }
        for n in device_counts
    }
