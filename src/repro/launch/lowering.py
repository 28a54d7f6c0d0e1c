"""Builders that lower train / prefill / decode steps for a mesh.

Used by launch/dryrun.py (production meshes), the hillclimb harness and
the multi-device tests (small host meshes).  No jax device-state side
effects at import.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import DEFAULT_MICROBATCH, DEFAULT_SHARDING, get_config
from repro.configs.base import INPUT_SHAPES, ModelConfig, RunConfig, ShapeConfig
from repro.core.scaling import model_flops, param_count
from repro.distributed import sharding as shd
from repro.models.model import Model, build_model
from repro.models.transformer import cache_shapes
from repro.train.optimizer import AdamWConfig
from repro.train.train_step import (batch_shardings, make_decode_step,
                                    make_prefill_step, param_shardings)

# archs that skip long_500k (full attention, no windowed variant) — DESIGN.md
LONG_OK = {"mamba2-130m", "zamba2-2.7b", "gemma2-27b", "gemma3-4b"}


def skip_reason(arch: str, shape_name: str) -> Optional[str]:
    if shape_name == "long_500k" and arch not in LONG_OK:
        return ("full-attention architecture without a sliding-window "
                "variant: 500k decode cache/attention is out of scope "
                "(DESIGN.md §Shape skips)")
    return None


@dataclass
class LoweredCase:
    arch: str
    shape: ShapeConfig
    sharding: str
    lowered: Any
    model_flops_global: float
    pallas_cost: Any = None  # analytic per-call kernel Cost (use_pallas)


def make_run(arch: str, shape: ShapeConfig, *, sharding: Optional[str] = None,
             mode_kind: str = "train", **overrides) -> RunConfig:
    cfg = get_config(arch)
    if sharding is None:
        sharding = DEFAULT_SHARDING[arch]
        if mode_kind != "train" and sharding in ("fsdp", "fsdp_tp"):
            sharding = "tp"  # serving: no per-step param gathers
    if mode_kind == "train" and "microbatch" not in overrides:
        overrides["microbatch"] = DEFAULT_MICROBATCH.get(arch, 0)
    return RunConfig(model=cfg, shape=shape, sharding=sharding, **overrides)


def _seq_axis(run: RunConfig, mesh) -> Optional[str]:
    """Sequence parallelism: on for fsdp_tp training (activation memory)."""
    if run.sharding == "fsdp_tp" and run.shape.mode == "train" \
            and run.shape.seq_len % mesh.shape["model"] == 0:
        return "model"
    return None


def _pallas_costs(run, mesh, shape, *, causal: bool):
    """Marker -> analytic per-call Cost for every kernel the lowering may
    contain (hlocost substitutes them for the interpret-mode loops)."""
    if not run.use_pallas:
        return None
    out = {}
    fc = shd.flash_analytic_cost(run.model, mesh, run.sharding,
                                 shape.global_batch, shape.seq_len,
                                 causal=causal)
    if fc is not None:
        out["pallas_flash"] = fc
    sc = shd.ssd_analytic_cost(run.model, mesh, run.sharding,
                               shape.global_batch, shape.seq_len)
    if sc is not None:
        out["pallas_ssd"] = sc
    if shape.mode == "train":
        # fused xent: one (B_loc * chunk, V) logits block read + (T,) write
        from repro.analysis.hlocost import Cost
        from repro.train.train_step import loss_blocks, loss_chunk_len

        bax = shd.batch_axes(mesh, shape.global_batch, run.sharding)
        n_sh = 1
        for a in bax:
            n_sh *= mesh.shape[a]
        b_loc = max(1, shape.global_batch // n_sh)
        # the loss head's block length (its segments tile with it where S
        # splits evenly); the cost model counts every segment, each the
        # costlier branch of its cond
        _, c = loss_blocks(shape.seq_len, loss_chunk_len(
            shape.global_batch, shape.seq_len, run.model.vocab_size, n_sh))
        V = run.model.vocab_size
        Vl = V // mesh.shape.get("model", 1) if V % mesh.shape.get(
            "model", 1) == 0 and run.sharding in ("tp", "fsdp_tp") else V
        toks = b_loc * c
        out["pallas_xent"] = Cost(flops=4.0 * toks * Vl,
                                  bytes=float(toks * Vl * 4 + toks * 8))
    return out or None


def lower_train(arch: str, shape: ShapeConfig, mesh, *,
                sharding: Optional[str] = None, seq_parallel=None,
                **overrides) -> LoweredCase:
    """Lowers the SAME execution path the trainer runs: the train step is
    built by ``train.runner.StepRunner`` (explicit in/out shardings from
    ``state_shardings``/``batch_shardings``, donated state buffers), so
    dry-run roofline numbers describe exactly what ``TrainLoop`` executes.
    """
    from repro.train.runner import StepRunner

    run = make_run(arch, shape, sharding=sharding, mode_kind="train",
                   **overrides)
    model = build_model(run.model)
    sp = _seq_axis(run, mesh) if seq_parallel is None else (
        "model" if seq_parallel else None)
    runner = StepRunner(model, run, AdamWConfig(), mesh, seq_axis=sp)
    lowered = runner.lower()
    mf = model_flops(run.model, shape.global_batch * shape.seq_len)
    pc = _pallas_costs(run, mesh, shape,
                       causal=run.model.family != "encoder")
    return LoweredCase(arch, shape, run.sharding, lowered, mf, pc)


def lower_prefill(arch: str, shape: ShapeConfig, mesh, *,
                  sharding: Optional[str] = None,
                  shard_cache_out: bool = False, **overrides) -> LoweredCase:
    run = make_run(arch, shape, sharding=sharding, mode_kind="serve",
                   **overrides)
    model = build_model(run.model)
    fn = make_prefill_step(model, run, mesh)
    p_sh = param_shardings(model, mesh, run)
    b_sh = batch_shardings(model, mesh, run, shape)
    inputs = model.input_specs(shape, act_dtype=jnp.dtype(run.activation_dtype))
    out_sh = None
    if shard_cache_out:
        # §Perf: shard the returned KV cache like the decode step consumes
        # it (batch over data, sequence over model) instead of letting XLA
        # choose — the baseline replicates large cache slices.
        B = shape.global_batch
        _, c_axes = cache_shapes(model.cfg, B, shape.seq_len,
                                 jnp.dtype(run.activation_dtype))
        crules = shd.cache_rules(mesh, B, run.sharding)
        c_abs, _ = cache_shapes(model.cfg, B, shape.seq_len,
                                jnp.dtype(run.activation_dtype))
        c_sh = jax.tree_util.tree_map(
            lambda axes, leaf: NamedSharding(
                mesh, shd.spec_for(axes, leaf.shape, crules, mesh)),
            c_axes, c_abs,
            is_leaf=lambda x: isinstance(x, tuple) or x is None)
        logits_sh = NamedSharding(mesh, shd.batch_spec(mesh, B, "fsdp", 3))
        out_sh = (logits_sh, c_sh)
    lowered = jax.jit(fn, in_shardings=(p_sh, b_sh),
                      out_shardings=out_sh).lower(
        model.abstract(jnp.dtype(run.param_dtype)), inputs)
    # prefill = forward only: 2·N·D
    mf = model_flops(run.model, shape.global_batch * shape.seq_len) / 3.0
    pc = _pallas_costs(run, mesh, shape, causal=True)
    return LoweredCase(arch, shape, run.sharding, lowered, mf, pc)


def lower_decode(arch: str, shape: ShapeConfig, mesh, *,
                 sharding: Optional[str] = None, **overrides) -> LoweredCase:
    run = make_run(arch, shape, sharding=sharding, mode_kind="serve",
                   **overrides)
    model = build_model(run.model)
    B, S = shape.global_batch, shape.seq_len
    fn = make_decode_step(model, run, mesh, dist_cache=True, global_batch=B)
    p_abs = model.abstract(jnp.dtype(run.param_dtype))
    p_sh = shd.tree_shardings(model.param_axes(), p_abs, mesh, run.sharding)
    c_abs, c_axes = cache_shapes(model.cfg, B, S,
                                 jnp.dtype(run.activation_dtype))
    crules = shd.cache_rules(mesh, B, run.sharding)
    c_sh = jax.tree_util.tree_map(
        lambda axes, leaf: NamedSharding(
            mesh, shd.spec_for(axes, leaf.shape, crules, mesh)),
        c_axes, c_abs,
        is_leaf=lambda x: isinstance(x, tuple) or x is None,
    )
    tok_sh = NamedSharding(
        mesh, shd.batch_spec(mesh, B, "fsdp", ndim=2))
    lowered = jax.jit(
        fn,
        in_shardings=(p_sh, c_sh, tok_sh, NamedSharding(mesh, P())),
        donate_argnums=(1,),
    ).lower(
        p_abs, c_abs,
        jax.ShapeDtypeStruct((B, 1), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32),
    )
    # one token per sequence: 2·N·B flops-ish
    mf = 2.0 * param_count(run.model, active_only=True) * B
    return LoweredCase(arch, shape, run.sharding, lowered, mf)


def lower_case(arch: str, shape_name: str, mesh, **overrides) -> LoweredCase:
    shape = INPUT_SHAPES[shape_name]
    if shape.mode == "train":
        return lower_train(arch, shape, mesh, **overrides)
    if shape.mode == "prefill":
        return lower_prefill(arch, shape, mesh, **overrides)
    return lower_decode(arch, shape, mesh, **overrides)
