"""Gradient-sync subsystem: bucket partitioning, the ParallelPlan's
strategy dispatch, and — on a real multi-device (virtual CPU) mesh —
equivalence of the bucketed/backward-overlapped ddp step with the seed
fused path: allclose gradients (rtol 1e-6 at leaf scale, 1e-8 absolute
floor for f32 reduction-order noise) and an identical loss trajectory,
for microbatches 1 and 4.

Param-trajectory comparison after several Adam steps is intentionally NOT
asserted element-wise: Adam normalizes by sqrt(nu), so an element whose
gradient is structurally ~0 (e.g. attention k-bias, softmax shift
invariance) turns 1e-8 reduction-order noise into an O(lr) update
difference.  The loss trajectory is the functional equivalence check.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _subproc import run_py
from repro.distributed import gradsync
from repro.distributed.sharding import (GRAD_SYNC_BUCKETED, GRAD_SYNC_EP,
                                        GRAD_SYNC_NONE, GRAD_SYNC_SCATTER,
                                        GRAD_SYNC_TP, GRAD_SYNC_XLA,
                                        ParallelPlan)


# ---------------------------------------------------------------------------
# Bucket partitioning (pure)
# ---------------------------------------------------------------------------


def _leaves(*shapes, dtype=jnp.float32):
    return [jax.ShapeDtypeStruct(s, dtype) for s in shapes]


def test_buckets_cover_every_leaf_exactly_once():
    leaves = _leaves((128, 128), (128,), (64, 64), (32,), (256, 8))
    buckets = gradsync.partition_buckets(leaves, bucket_mb=0.02)
    seen = [i for b in buckets for i in b.indices]
    assert sorted(seen) == list(range(len(leaves)))
    assert len(seen) == len(set(seen))


def test_buckets_walk_in_reverse_layer_order():
    leaves = _leaves((8, 8), (8, 8), (8, 8), (8, 8))
    buckets = gradsync.partition_buckets(leaves, bucket_mb=0.0005)
    # flat order reversed: last leaf (deepest in backward == first ready)
    # leads the first bucket
    order = [i for b in buckets for i in b.indices]
    assert order == [3, 2, 1, 0]


def test_bucket_size_targeting_and_oversized_leaf():
    # 64KB leaves against a 100KB target: two per bucket
    leaves = _leaves(*([(128, 128)] * 5))  # 65536 B each
    buckets = gradsync.partition_buckets(leaves, bucket_mb=0.14)
    assert [len(b.indices) for b in buckets] == [2, 2, 1]
    assert all(b.nbytes <= 0.14e6 for b in buckets)
    # a leaf bigger than the target gets its own bucket, never split
    big = gradsync.partition_buckets(_leaves((1024, 1024), (8,)),
                                     bucket_mb=0.01)
    assert [len(b.indices) for b in big] == [1, 1]
    assert big[1].nbytes == 1024 * 1024 * 4


def test_buckets_are_dtype_homogeneous():
    leaves = [jax.ShapeDtypeStruct((64,), jnp.float32),
              jax.ShapeDtypeStruct((64,), jnp.bfloat16),
              jax.ShapeDtypeStruct((64,), jnp.bfloat16)]
    buckets = gradsync.partition_buckets(leaves, bucket_mb=1.0)
    assert len(buckets) == 2
    for b in buckets:
        assert len({jnp.dtype(leaves[i].dtype) for i in b.indices}) == 1


def test_bucket_mb_must_be_positive():
    with pytest.raises(ValueError):
        gradsync.partition_buckets(_leaves((8,)), bucket_mb=0)


def test_bucketed_psum_roundtrip_preserves_structure():
    # 1x1 mesh: psum over size-1 axes is the identity, which exercises the
    # concat/slice/reshape round-trip without needing multiple devices
    from repro.launch.mesh import make_host_mesh
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    mesh = make_host_mesh(1, 1)
    tree = {"a": jnp.arange(12.0).reshape(3, 4),
            "b": [jnp.ones((5,)), jnp.full((2, 2, 2), 3.0)]}
    buckets = gradsync.partition_buckets(
        jax.tree_util.tree_leaves(tree), bucket_mb=4e-5)
    assert len(buckets) > 1
    out = shard_map(
        lambda t: gradsync.bucketed_psum(t, ("data", "model"), buckets),
        mesh=mesh, in_specs=(P(),), out_specs=P(), check_vma=False)(tree)
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x),
                                                   np.asarray(y)),
        tree, out)


def test_fused_psum_is_single_bucket_and_matches_bucketed():
    from repro.launch.mesh import make_host_mesh
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    mesh = make_host_mesh(1, 1)
    tree = {"a": jnp.arange(6.0).reshape(2, 3), "b": jnp.ones((4,))}
    buckets = gradsync.partition_buckets(
        jax.tree_util.tree_leaves(tree), bucket_mb=1e-5)
    run = lambda f: shard_map(f, mesh=mesh, in_specs=(P(),), out_specs=P(),
                              check_vma=False)(tree)
    fused = run(lambda t: gradsync.fused_psum(t, ("data", "model")))
    bucketed = run(
        lambda t: gradsync.bucketed_psum(t, ("data", "model"), buckets))
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x),
                                                   np.asarray(y)),
        fused, bucketed)


def test_bucket_stats_and_wire_bytes():
    buckets = gradsync.partition_buckets(_leaves((128, 128), (64,)),
                                         bucket_mb=0.01)
    stats = gradsync.bucket_plan_stats(buckets)
    assert stats["n_buckets"] == len(buckets)
    assert stats["comm_bytes"] == 128 * 128 * 4 + 64 * 4
    assert gradsync.ring_allreduce_bytes(1000, 1) == 0.0
    assert gradsync.ring_allreduce_bytes(1000, 4) == pytest.approx(1500.0)


# ---------------------------------------------------------------------------
# ParallelPlan strategy dispatch (pure, duck-typed mesh)
# ---------------------------------------------------------------------------


class FakeMesh:
    def __init__(self, **axes):
        self.shape = dict(axes)
        self.axis_names = tuple(axes)


def test_plan_ddp_multi_shard_buckets():
    plan = ParallelPlan.make(FakeMesh(data=4, model=2), "ddp", 16)
    assert plan.dp_axes == ("data", "model")
    assert plan.dp_size == 8
    assert plan.local_batch == 2
    assert plan.grad_sync == GRAD_SYNC_BUCKETED


def test_plan_overlap_off_is_fused_baseline():
    plan = ParallelPlan.make(FakeMesh(data=4), "ddp", 16, overlap=False)
    assert plan.grad_sync == GRAD_SYNC_XLA
    plan = ParallelPlan.make(FakeMesh(data=4), "fsdp", 16, overlap=False)
    assert plan.grad_sync == GRAD_SYNC_XLA


def test_plan_single_shard_and_meshless_skip_sync():
    assert ParallelPlan.make(FakeMesh(data=1, model=1), "ddp",
                             8).grad_sync == GRAD_SYNC_NONE
    assert ParallelPlan.make(None, "ddp", 8).grad_sync == GRAD_SYNC_NONE


def test_plan_fsdp_modes_scatter_and_tp_engages():
    # fsdp on any multi-shard dp mesh scatters (the model axis carries
    # no tp specs under mode fsdp); a real model axis under the tp
    # modes now engages the explicitly-scheduled tp step (the old
    # tp_sharded -> xla_fused fallback row is gone)
    assert ParallelPlan.make(FakeMesh(data=2, model=2), "fsdp",
                             8).grad_sync == GRAD_SYNC_SCATTER
    assert ParallelPlan.make(FakeMesh(data=4, model=1), "fsdp_tp",
                             8).grad_sync == GRAD_SYNC_SCATTER
    for mode in ("tp", "fsdp_tp"):
        plan = ParallelPlan.make(FakeMesh(data=2, model=2), mode, 8)
        assert plan.tp_engaged and plan.tp_axis == "model"
        assert plan.grad_sync == GRAD_SYNC_TP, mode
        assert plan.grad_buckets({}) is None
        assert plan.scatter_plan({}) is None


def test_plan_indivisible_microbatch_falls_back_to_fused():
    # local batch 2 can't split into 4 microbatches: bucketing would
    # change semantics, so the plan routes to the pjit path instead
    plan = ParallelPlan.make(FakeMesh(data=4), "ddp", 8, microbatch=4)
    assert plan.local_batch == 2
    assert plan.grad_sync == GRAD_SYNC_XLA
    ok = ParallelPlan.make(FakeMesh(data=4), "ddp", 16, microbatch=4)
    assert ok.grad_sync == GRAD_SYNC_BUCKETED


def test_plan_moe_rides_overlap_paths():
    # the Switch aux loss is nonlinear in batch-mean router statistics,
    # which used to force every MoE config onto the pjit path.  The
    # router now pmean's its me/ce statistics inside the shard_map'd
    # step (tests/test_moe_router_stats.py proves the aux then equals
    # the global value), so MoE composes with the bucketed/scatter
    # overlap strategies like any dense model
    plan = ParallelPlan.make(FakeMesh(data=4), "ddp", 16, has_moe=True)
    assert plan.grad_sync == GRAD_SYNC_BUCKETED
    assert plan.fallback_reason is None
    assert ParallelPlan.make(FakeMesh(data=4), "fsdp", 16,
                             has_moe=True).grad_sync == GRAD_SYNC_SCATTER
    from repro.configs import get_config, reduced
    from repro.configs.base import RunConfig, ShapeConfig

    moe_cfg = reduced(get_config("mixtral-8x7b"))
    run = RunConfig(model=moe_cfg, shape=ShapeConfig("t", 32, 16, "train"),
                    sharding="ddp")
    plan = ParallelPlan.for_run(run, FakeMesh(data=4))
    assert plan.has_moe and plan.n_experts == moe_cfg.moe.n_experts
    assert plan.grad_sync == GRAD_SYNC_BUCKETED


def test_plan_buckets_sized_at_f32_under_accumulation():
    # with microbatch>1 the synced grads are the f32 accumulators, not
    # param-dtype arrays: buckets (and comm telemetry) must size at f32
    abstract = [jax.ShapeDtypeStruct((64, 64), jnp.bfloat16)]
    one = ParallelPlan.make(FakeMesh(data=4), "ddp", 16, microbatch=1)
    four = ParallelPlan.make(FakeMesh(data=4), "ddp", 16, microbatch=4)
    assert one.grad_buckets(abstract)[0].nbytes == 64 * 64 * 2
    assert four.grad_buckets(abstract)[0].nbytes == 64 * 64 * 4


# ---------------------------------------------------------------------------
# Strategy-dispatch table — mirrors the table in docs/parallelism.md
# ("ParallelPlan fallback behavior").  A row here and a row there must
# stay in lockstep: the doc's table cites this test by name.
# ---------------------------------------------------------------------------

STRATEGY_TABLE = [
    # mode, mesh axes, global_batch, microbatch, has_moe -> strategy
    ("ddp", dict(data=4), 16, 1, False, GRAD_SYNC_BUCKETED),
    ("ddp", dict(data=4, model=2), 16, 1, False, GRAD_SYNC_BUCKETED),
    ("ddp", dict(data=4), 16, 4, False, GRAD_SYNC_BUCKETED),
    ("ddp", dict(data=4), 8, 4, False, GRAD_SYNC_XLA),    # 2 % 4 != 0
    # MoE rides the bucketed path: router stats are psum'd per-shard
    ("ddp", dict(data=4), 16, 1, True, GRAD_SYNC_BUCKETED),
    ("ddp", dict(data=1, model=1), 8, 1, False, GRAD_SYNC_NONE),
    ("fsdp", dict(data=4), 16, 1, False, GRAD_SYNC_SCATTER),
    ("fsdp", dict(data=4), 16, 4, False, GRAD_SYNC_SCATTER),
    ("fsdp", dict(data=4), 8, 4, False, GRAD_SYNC_XLA),   # 2 % 4 != 0
    ("fsdp", dict(data=4), 16, 1, True, GRAD_SYNC_SCATTER),  # MoE ok
    ("fsdp", dict(data=1), 8, 1, False, GRAD_SYNC_NONE),
    ("fsdp_tp", dict(data=4, model=1), 16, 1, False, GRAD_SYNC_SCATTER),
    ("fsdp_tp", dict(data=2, model=2), 16, 1, False, GRAD_SYNC_TP),
    ("fsdp_tp", dict(data=2, model=2), 16, 1, True, GRAD_SYNC_XLA),
    ("tp", dict(data=2, model=2), 16, 1, False, GRAD_SYNC_TP),
]


@pytest.mark.parametrize("mode,axes,gb,micro,moe,expect", STRATEGY_TABLE)
def test_plan_strategy_table(mode, axes, gb, micro, moe, expect):
    plan = ParallelPlan.make(FakeMesh(**axes), mode, gb,
                             microbatch=micro, has_moe=moe)
    assert plan.grad_sync == expect, plan.describe()


# the pp/pp_dp half of the fallback spec (docs/parallelism.md table):
# pipelining engages only when the pipe axis is real, the depth divides
# into equal stages, the model is stageable and MoE-free, and the
# microbatch count divides the per-shard batch; every other combination
# demotes 'pipe' to a plain data axis and dispatches like ddp.
PP_STRATEGY_TABLE = [
    # mode, axes, gb, micro, moe, n_layers, stageable -> strategy
    ("pp", dict(pipe=2, data=1), 8, 2, False, 4, True, "pipe_overlap"),
    ("pp_dp", dict(pipe=2, data=4), 16, 2, False, 4, True,
     "pipe_overlap"),
    ("pp_dp", dict(pipe=2, data=2), 16, 8, False, 4, True,
     "pipe_overlap"),      # M == the full per-shard batch (local 8)
    # M exceeds the per-shard batch (local 4 < 8): pipelining declines,
    # and so does the demoted-ddp path (2 % 8 != 0) -> fused
    ("pp_dp", dict(pipe=2, data=4), 16, 8, False, 4, True,
     GRAD_SYNC_XLA),
    # MoE: pipelining declines (stage_compatible says no), but the
    # demoted-ddp path now buckets — router stats are psum'd per-shard
    ("pp_dp", dict(pipe=2, data=4), 16, 2, True, 4, True,
     GRAD_SYNC_BUCKETED),
    # stage-indivisible depth: pipe demoted to a data axis -> ddp
    # dispatch over ('pipe','data')
    ("pp_dp", dict(pipe=2, data=4), 16, 2, False, 5, True,
     GRAD_SYNC_BUCKETED),
    # structurally un-stageable model (multi-group / shared weights)
    ("pp_dp", dict(pipe=2, data=4), 16, 2, False, 4, False,
     GRAD_SYNC_BUCKETED),
    # pipe axis of size 1: nothing to pipeline -> ddp dispatch
    ("pp_dp", dict(pipe=1, data=4), 16, 1, False, 4, True,
     GRAD_SYNC_BUCKETED),
    # microbatch does not divide the per-shard batch: pipelining AND the
    # bucketed fallback both decline -> fused
    ("pp_dp", dict(pipe=2, data=4), 8, 3, False, 4, True,
     GRAD_SYNC_XLA),
    # single shard every way
    ("pp", dict(pipe=1, data=1), 8, 1, False, 4, True, GRAD_SYNC_NONE),
]


@pytest.mark.parametrize("mode,axes,gb,micro,moe,nl,stg,expect",
                         PP_STRATEGY_TABLE)
def test_plan_strategy_table_pp(mode, axes, gb, micro, moe, nl, stg,
                                expect):
    plan = ParallelPlan.make(FakeMesh(**axes), mode, gb,
                             microbatch=micro, has_moe=moe,
                             n_layers=nl, stageable=stg)
    assert plan.grad_sync == expect, plan.describe()


# the expert-axis half of the fallback spec (docs/parallelism.md
# table): ep_overlap engages only for ddp with overlap on, a real
# expert axis carrying part of the batch, and an expert count divisible
# by the axis width; every other combination keeps 'expert' as a plain
# data axis with dense MoE dispatch under the mode's normal strategy.
EP_STRATEGY_TABLE = [
    # mode, axes, gb, micro, has_moe, n_experts -> strategy, reason
    ("ddp", dict(data=2, expert=2), 16, 1, True, 4, GRAD_SYNC_EP, None),
    ("ddp", dict(data=2, expert=2), 16, 2, True, 8, GRAD_SYNC_EP, None),
    # expert count does not divide the axis: dense dispatch, bucketed
    ("ddp", dict(data=2, expert=2), 16, 1, True, 3, GRAD_SYNC_BUCKETED,
     "ep-indivisible experts"),
    # no MoE at all: the expert axis is just more data parallelism
    ("ddp", dict(data=2, expert=2), 16, 1, False, 0, GRAD_SYNC_BUCKETED,
     None),
    # fsdp has no ep path: MoE runs dense under scatter_overlap
    ("fsdp", dict(data=2, expert=2), 16, 1, True, 4, GRAD_SYNC_SCATTER,
     "no ep path"),
    # batch can't shard over the expert axis (2 % (2*2) != 0): expert
    # drops out of the dp axes, ep declines, bucketed over data only
    ("ddp", dict(data=2, expert=2), 2, 1, True, 4, GRAD_SYNC_BUCKETED,
     "batch-indivisible expert axis"),
    # microbatch does not divide the per-shard batch: ep AND bucketed
    # both decline -> fused
    ("ddp", dict(data=2, expert=2), 16, 3, True, 4, GRAD_SYNC_XLA,
     "indivisible microbatch"),
]


@pytest.mark.parametrize("mode,axes,gb,micro,moe,ne,expect,reason",
                         EP_STRATEGY_TABLE)
def test_plan_strategy_table_ep(mode, axes, gb, micro, moe, ne, expect,
                                reason):
    plan = ParallelPlan.make(FakeMesh(**axes), mode, gb,
                             microbatch=micro, has_moe=moe, n_experts=ne)
    assert plan.grad_sync == expect, plan.describe()
    if reason is None:
        assert plan.fallback_reason is None, plan.fallback_reason
    else:
        assert reason in (plan.fallback_reason or ""), plan.describe()
    assert plan.ep_engaged == (expect == GRAD_SYNC_EP)


# the tensor-parallel half of the fallback spec (docs/parallelism.md
# table): tp_overlap engages only for the tp modes on a mesh with a
# real model axis, overlap on, no MoE (the ep dispatch owns the model
# axis there), and head/ff/seq dims the model axis divides; fsdp_tp on
# a model-axis-1 mesh degrades gracefully to plain ZeRO-3.
TP_STRATEGY_TABLE = [
    # mode, axes, gb, micro, moe, heads, kv, dff, seq
    #   -> strategy, fallback_reason
    ("tp", dict(data=2, model=2), 16, 1, False, 4, 2, 256, 64,
     GRAD_SYNC_TP, None),
    ("fsdp_tp", dict(data=2, model=2), 16, 1, False, 4, 2, 256, 64,
     GRAD_SYNC_TP, None),
    ("fsdp_tp", dict(data=2, model=2), 16, 4, False, 4, 2, 256, 64,
     GRAD_SYNC_TP, None),
    # pure tp on a data=1 mesh has no data parallelism but still needs
    # the explicitly-scheduled step
    ("tp", dict(data=1, model=2), 8, 1, False, 4, 2, 256, 64,
     GRAD_SYNC_TP, None),
    # dims the model axis can't divide: honest fallback, not a crash
    ("fsdp_tp", dict(data=2, model=2), 16, 1, False, 3, 3, 256, 64,
     GRAD_SYNC_XLA, "tp-indivisible heads"),
    ("fsdp_tp", dict(data=2, model=2), 16, 1, False, 4, 2, 255, 64,
     GRAD_SYNC_XLA, "tp-indivisible d_ff"),
    ("fsdp_tp", dict(data=2, model=2), 16, 1, False, 4, 2, 256, 63,
     GRAD_SYNC_XLA, "tp-indivisible seq_len"),
    # MoE x tp has no composition yet: the fused partitioner carries it
    ("fsdp_tp", dict(data=2, model=2), 16, 1, True, 4, 2, 256, 64,
     GRAD_SYNC_XLA, "moe"),
    # model axis of width 1: fsdp_tp is just ZeRO-3 over data
    ("fsdp_tp", dict(data=4, model=1), 16, 1, False, 4, 2, 256, 64,
     GRAD_SYNC_SCATTER, None),
]


@pytest.mark.parametrize("mode,axes,gb,micro,moe,nh,nkv,dff,seq,"
                         "expect,reason", TP_STRATEGY_TABLE)
def test_plan_strategy_table_tp(mode, axes, gb, micro, moe, nh, nkv,
                                dff, seq, expect, reason):
    plan = ParallelPlan.make(FakeMesh(**axes), mode, gb,
                             microbatch=micro, has_moe=moe, n_heads=nh,
                             n_kv_heads=nkv, d_ff=dff, seq_len=seq)
    assert plan.grad_sync == expect, plan.describe()
    if reason is None:
        assert plan.fallback_reason is None, plan.fallback_reason
    else:
        assert reason in (plan.fallback_reason or ""), plan.describe()
    assert plan.tp_engaged == (expect == GRAD_SYNC_TP)


def test_plan_ep_describe_and_param_specs():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    plan = ParallelPlan.make(FakeMesh(data=2, expert=2), "ddp", 16,
                             has_moe=True, n_experts=4)
    d = plan.describe()
    assert d["grad_sync"] == GRAD_SYNC_EP
    assert d["ep_engaged"] and d["ep_size"] == 2 and d["n_experts"] == 4
    assert d["fallback_reason"] is None
    # expert-dim leaves shard over 'expert' at their experts position;
    # everything else replicates
    axes_tree = {"wi": ("experts", "embed", "ff"),
                 "stacked": ("layers", "experts", "embed", "ff"),
                 "router": ("embed", None)}
    abstract = {"wi": jax.ShapeDtypeStruct((4, 8, 16), jnp.float32),
                "stacked": jax.ShapeDtypeStruct((2, 4, 8, 16),
                                                jnp.float32),
                "router": jax.ShapeDtypeStruct((8, 4), jnp.float32)}
    specs = plan.ep_param_specs(axes_tree, abstract)
    assert specs["wi"] == P("expert")
    assert specs["stacked"] == P(None, "expert")
    assert specs["router"] == P()
    sp = plan.ep_sync_plan(axes_tree, abstract)
    # dict flatten order: router(0), stacked(1), wi(2); the two
    # expert-dim leaves bucket separately, sized at their LOCAL E/ep
    # slices, the router rides the replicated buckets at full size
    assert sorted(sp.stage_indices) == [1, 2]
    assert sp.stage_bytes == (2 * 2 * 8 * 16 + 2 * 8 * 16) * 4
    assert sp.replicated_bytes == 8 * 4 * 4


def test_pp_fallback_demotes_pipe_to_data_axis():
    # engaged: batch over ('data',) only, replicated across stages
    p = ParallelPlan.make(FakeMesh(pipe=2, data=4), "pp_dp", 16,
                          microbatch=2, n_layers=4)
    assert p.pipe_engaged and p.dp_axes == ("data",) and p.pp_size == 2
    # indivisible depth: pipe joins the dp axes
    f = ParallelPlan.make(FakeMesh(pipe=2, data=4), "pp_dp", 16,
                          microbatch=2, n_layers=5)
    assert not f.pipe_engaged and f.dp_axes == ("pipe", "data")
    assert f.pp_size == 1 and f.dp_size == 8


# ---------------------------------------------------------------------------
# fsdp bucket partitioning (pure)
# ---------------------------------------------------------------------------


def test_shard_dim_picks_first_divisible_dim():
    mk = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    assert gradsync.shard_dim(mk(16, 3), 8) == 0
    # scan-stacked block params: leading repeats dim is tiny, so the
    # divisible d_model dim is chosen instead of replicating the leaf
    assert gradsync.shard_dim(mk(1, 128, 256), 8) == 1
    assert gradsync.shard_dim(mk(3, 5), 8) is None       # replicated
    assert gradsync.shard_dim(mk(), 8) is None           # scalar
    assert gradsync.shard_dim(mk(16), 1) is None         # 1 shard: no-op


def test_fsdp_buckets_split_scatter_vs_psum_and_cover_all():
    mk = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    leaves = [mk(16, 4), mk(3,), mk(1, 8, 8), mk(5, 5), mk(32,)]
    sp = gradsync.partition_fsdp_buckets(leaves, 4, bucket_mb=1e-4)
    assert sp.n_shards == 4
    assert sp.shard_dims == (0, None, 1, None, 0)
    seen = sorted(i for b in sp.buckets for i in b.indices)
    assert seen == list(range(len(leaves)))
    assert sorted(sp.scatter_indices) == [0, 2, 4]
    for b in sp.scatter:                 # every member size splits by n
        for i in b.indices:
            assert int(np.prod(leaves[i].shape)) % 4 == 0
    assert sp.scatter_bytes == (16 * 4 + 8 * 8 + 32) * 4
    assert sp.psum_bytes == (3 + 25) * 4


def test_fsdp_scatter_buckets_walk_reverse_and_gather_walks_forward():
    mk = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    leaves = [mk(8, 8) for _ in range(4)]
    sp = gradsync.partition_fsdp_buckets(leaves, 4, bucket_mb=1e-4)
    order = [i for b in sp.scatter for i in b.indices]
    assert order == [3, 2, 1, 0]         # backward (scatter) order


def test_fsdp_gather_scatter_roundtrip_on_one_device_mesh():
    # size-1 dp axis: gather/scatter are identities, which exercises the
    # blocks<->leaf reshape round-trip for dim0 AND non-dim0 shard dims
    from repro.launch.mesh import make_host_mesh
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    mesh = make_host_mesh(1, 1)
    tree = {"a": jnp.arange(24.0).reshape(6, 4),
            "b": jnp.arange(16.0).reshape(1, 4, 4), "c": jnp.ones((3,))}
    leaves = jax.tree_util.tree_leaves(tree)
    sp = gradsync.partition_fsdp_buckets(leaves, 1, bucket_mb=1e-4)
    assert sp.scatter == ()              # n=1: nothing shardable

    sp2 = gradsync.partition_fsdp_buckets(leaves, 2, bucket_mb=1e-4)
    assert sorted(sp2.scatter_indices) == [0, 1]
    # on a (1,1) mesh run with a size-1 FsdpBucketPlan: identity
    out = shard_map(
        lambda t: gradsync.bucketed_psum_scatter(
            gradsync.gather_fsdp_params(t, ("data", "model"), sp),
            ("data", "model"), sp),
        mesh=mesh, in_specs=(P(),), out_specs=P(), check_vma=False)(tree)
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x),
                                                   np.asarray(y)),
        tree, out)


def test_plan_scatter_param_specs_match_shard_dims():
    mk = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    tree = {"w": mk(16, 4), "stacked": mk(1, 8, 8), "odd": mk(3,)}
    plan = ParallelPlan.make(FakeMesh(data=4), "fsdp", 16)
    specs = plan.scatter_param_specs(tree)
    from jax.sharding import PartitionSpec as P
    assert specs["w"] == P("data")
    assert specs["stacked"] == P(None, "data")
    assert specs["odd"] == P()
    sp = plan.scatter_plan(tree)
    assert sp.shard_dims == tuple(
        {"odd": None, "stacked": 1, "w": 0}[k]
        for k in sorted(tree))           # flat order is key-sorted


def test_plan_unknown_mode_raises():
    with pytest.raises(KeyError):
        ParallelPlan.make(None, "zzz", 8)


def test_plan_describe_is_flat_and_complete():
    d = ParallelPlan.make(FakeMesh(data=2, model=2), "fsdp_tp", 8).describe()
    assert d["mode"] == "fsdp_tp" and d["model_axis"] == "model"
    for k in ("dp_axes", "dp_size", "grad_sync", "grad_bucket_mb",
              "local_batch", "microbatch"):
        assert k in d


def test_runner_reports_grad_sync_telemetry():
    import dataclasses

    from repro.configs import get_config, reduced
    from repro.configs.base import RunConfig, ShapeConfig
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model
    from repro.train.optimizer import AdamWConfig
    from repro.train.runner import StepRunner

    cfg = dataclasses.replace(reduced(get_config("bert-mlm-120m"),
                                      d_model=64),
                              vocab_size=256, max_position=32)
    run = RunConfig(model=cfg, shape=ShapeConfig("t", 32, 4, "train"),
                    sharding="ddp", param_dtype="float32",
                    activation_dtype="float32")
    runner = StepRunner(build_model(cfg), run, AdamWConfig(),
                        make_host_mesh(1, 1))
    info = runner.grad_sync_info()
    assert info["grad_sync"] == GRAD_SYNC_NONE  # 1 dp shard: nothing to do
    assert info["n_buckets"] == 0 and info["comm_bytes"] == 0


# ---------------------------------------------------------------------------
# Multi-device equivalence (subprocess, like test_multidevice)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_bucketed_ddp_matches_fused_on_two_device_mesh():
    print(run_py("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config, reduced
        from repro.configs.base import RunConfig, ShapeConfig
        from repro.distributed.sharding import ParallelPlan
        from repro.launch.mesh import make_host_mesh
        from repro.models import build_model
        from repro.train.optimizer import AdamWConfig
        from repro.train.train_step import (init_state, make_grad_fn,
                                            make_train_step)

        def close(ref, got, rtol=1e-6, floor=1e-8):
            for a, b in zip(jax.tree_util.tree_leaves(ref),
                            jax.tree_util.tree_leaves(got)):
                a, b = np.asarray(a), np.asarray(b)
                np.testing.assert_allclose(
                    b, a, rtol=rtol,
                    atol=rtol * float(np.abs(a).max()) + floor)

        B, S = 8, 32
        cfg = dataclasses.replace(reduced(get_config('bert-mlm-120m'),
                                          d_model=64),
                                  vocab_size=256, max_position=S)
        model = build_model(cfg)
        mesh = make_host_mesh(2, 1)
        opt = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=10)
        toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 4,
                                  cfg.vocab_size)
        for n_micro in (1, 4):
            # microbatch>1 partitions the batch differently per strategy
            # (global chunks vs per-shard slices); with a uniform mask the
            # two are mathematically identical, so micro=1 carries the
            # ragged-mask case and micro=4 the uniform one
            if n_micro == 1:
                mask = (jax.random.uniform(jax.random.PRNGKey(2),
                                           (B, S)) > 0.3).astype(
                                               jnp.float32)
            else:
                mask = jnp.ones((B, S), jnp.float32)
            batch = {'tokens': toks, 'labels': jnp.roll(toks, -1, 1),
                     'loss_mask': mask}
            run = RunConfig(model=cfg,
                            shape=ShapeConfig('t', S, B, 'train'),
                            sharding='ddp', param_dtype='float32',
                            activation_dtype='float32',
                            microbatch=n_micro)
            params = init_state(model, jax.random.PRNGKey(0),
                                run)['params']
            _, gref, mref = jax.jit(make_grad_fn(model, run))(params,
                                                              batch)
            plan = ParallelPlan.for_run(run, mesh, grad_bucket_mb=0.05)
            assert plan.grad_sync == 'bucketed_overlap', plan.describe()
            nb = len(plan.grad_buckets(model.abstract(jnp.float32)))
            assert nb > 1, 'tiny bucket target must yield several buckets'
            _, gb, mb = jax.jit(make_grad_fn(model, run, mesh, plan))(
                params, batch)
            close(gref, gb)                                   # rtol 1e-6
            np.testing.assert_allclose(float(mref['loss']),
                                       float(mb['loss']), rtol=1e-6)

            # identical loss trajectory over 4 full optimizer steps
            step_b = jax.jit(make_train_step(model, run, opt, mesh,
                                             plan=plan))
            step_f = jax.jit(make_train_step(model, run, opt))
            sb = init_state(model, jax.random.PRNGKey(0), run)
            sf = init_state(model, jax.random.PRNGKey(0), run)
            for _ in range(4):
                sb, m_b = step_b(sb, batch)
                sf, m_f = step_f(sf, batch)
                np.testing.assert_allclose(float(m_f['loss']),
                                           float(m_b['loss']), rtol=1e-6)
                np.testing.assert_allclose(float(m_f['grad_norm']),
                                           float(m_b['grad_norm']),
                                           rtol=1e-5)
            print(f'micro={n_micro} OK ({nb} buckets)')
        print('equivalence OK')
    """, n_devices=2))


@pytest.mark.slow
def test_scatter_fsdp_matches_fused_on_two_device_mesh():
    # vocab 511 is deliberately odd: mlm/out_bias (511,) has no
    # 2-divisible dim, so the replicated-remainder (plain psum) bucket
    # path is exercised alongside the scatter buckets
    print(run_py("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config, reduced
        from repro.configs.base import RunConfig, ShapeConfig
        from repro.distributed.sharding import ParallelPlan
        from repro.launch.mesh import make_host_mesh
        from repro.models import build_model
        from repro.train.optimizer import AdamWConfig
        from repro.train.train_step import (init_state, make_grad_fn,
                                            make_train_step)

        def close(ref, got, rtol=1e-6, floor=1e-8):
            for a, b in zip(jax.tree_util.tree_leaves(ref),
                            jax.tree_util.tree_leaves(got)):
                a, b = np.asarray(a), np.asarray(b)
                np.testing.assert_allclose(
                    b, a, rtol=rtol,
                    atol=rtol * float(np.abs(a).max()) + floor)

        B, S = 8, 32
        cfg = dataclasses.replace(reduced(get_config('bert-mlm-120m'),
                                          d_model=64),
                                  vocab_size=511, max_position=S)
        model = build_model(cfg)
        mesh = make_host_mesh(2, 1)
        opt = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=10)
        toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 4,
                                  cfg.vocab_size)
        for mode in ('fsdp', 'fsdp_tp'):
            for n_micro in (1, 4):
                # micro=1 carries the ragged-mask case, micro=4 the
                # uniform one (same reasoning as the ddp test above)
                if n_micro == 1:
                    mask = (jax.random.uniform(jax.random.PRNGKey(2),
                                               (B, S)) > 0.3).astype(
                                                   jnp.float32)
                else:
                    mask = jnp.ones((B, S), jnp.float32)
                batch = {'tokens': toks, 'labels': jnp.roll(toks, -1, 1),
                         'loss_mask': mask}
                run = RunConfig(model=cfg,
                                shape=ShapeConfig('t', S, B, 'train'),
                                sharding=mode, param_dtype='float32',
                                activation_dtype='float32',
                                microbatch=n_micro)
                params = init_state(model, jax.random.PRNGKey(0),
                                    run)['params']
                _, gref, mref = jax.jit(make_grad_fn(model, run))(params,
                                                                  batch)
                plan = ParallelPlan.for_run(run, mesh,
                                            grad_bucket_mb=0.05)
                assert plan.grad_sync == 'scatter_overlap', \\
                    plan.describe()
                sp = plan.scatter_plan(model.abstract(jnp.float32))
                assert len(sp.scatter) > 1, 'several scatter buckets'
                assert len(sp.psum) >= 1, 'odd vocab: psum remainder'
                _, gs, ms = jax.jit(make_grad_fn(model, run, mesh,
                                                 plan))(params, batch)
                # fused and scatter sum the B*S per-token terms in
                # different orders (and XLA:CPU's threaded reductions
                # vary from run to run): f32 agreement is bounded near
                # sqrt(B*S)*eps ~ 2e-6 at leaf scale, so 1e-5 as for the
                # grad norm below
                close(gref, gs, rtol=1e-5)
                np.testing.assert_allclose(float(mref['loss']),
                                           float(ms['loss']), rtol=1e-6)

                # identical loss + grad-norm trajectory over 4 steps
                step_s = jax.jit(make_train_step(model, run, opt, mesh,
                                                 plan=plan))
                step_f = jax.jit(make_train_step(model, run, opt))
                ss = init_state(model, jax.random.PRNGKey(0), run)
                sf = init_state(model, jax.random.PRNGKey(0), run)
                for _ in range(4):
                    ss, m_s = step_s(ss, batch)
                    sf, m_f = step_f(sf, batch)
                    np.testing.assert_allclose(float(m_f['loss']),
                                               float(m_s['loss']),
                                               rtol=1e-6)
                    np.testing.assert_allclose(float(m_f['grad_norm']),
                                               float(m_s['grad_norm']),
                                               rtol=1e-5)
                print(f'{mode} micro={n_micro} OK '
                      f'({len(sp.scatter)}sc+{len(sp.psum)}ps buckets)')
        print('scatter equivalence OK')
    """, n_devices=2))


@pytest.mark.slow
def test_scatter_runner_trains_on_eight_device_mesh():
    print(run_py("""
        import dataclasses, jax, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.configs import get_config, reduced
        from repro.configs.base import RunConfig, ShapeConfig
        from repro.launch.mesh import make_host_mesh
        from repro.models import build_model
        from repro.train.optimizer import AdamWConfig
        from repro.train.runner import StepRunner, TrainLoop

        B, S = 16, 32
        cfg = dataclasses.replace(reduced(get_config('bert-mlm-120m'),
                                          d_model=64),
                                  vocab_size=256, max_position=S)
        model = build_model(cfg)
        run = RunConfig(model=cfg, shape=ShapeConfig('t', S, B, 'train'),
                        sharding='fsdp', param_dtype='float32',
                        activation_dtype='float32')
        runner = StepRunner(model, run, AdamWConfig(total_steps=8),
                            make_host_mesh(8, 1), grad_bucket_mb=0.05)
        info = runner.grad_sync_info()
        assert info['grad_sync'] == 'scatter_overlap', info
        assert info['n_buckets'] > 1
        assert info['comm_bytes'] == sum(info['bucket_bytes'])
        assert info['param_gather_bytes'] > 0
        # reduce-scatter wire volume: (n-1)/n of the scatter payload —
        # half of what the ddp ring all-reduce would move
        assert info['wire_bytes_per_device'] < info['comm_bytes']

        rng = np.random.default_rng(0)
        def batches():
            while True:
                t = rng.integers(4, 256, (B, S)).astype(np.int32)
                yield {'tokens': t, 'labels': t,
                       'loss_mask': np.ones((B, S), np.float32)}

        state, log = TrainLoop(runner, log_every=2).run(batches(), 8)
        assert log.telemetry['n_traces'] == 1         # jit-once preserved
        assert log.telemetry['grad_sync'] == 'scatter_overlap'
        assert log.telemetry['param_gather_bytes'] > 0
        losses = [m['loss'] for m in log.metrics]
        assert all(np.isfinite(l) for l in losses), losses

        # ZeRO-3: params AND optimizer moments are stored sharded —
        # every dp-divisible leaf's per-device shard is 1/8 of the leaf
        embed = state['params']['embed']['tokens']
        assert embed.sharding.spec == P('data')
        shard = embed.addressable_shards[0].data
        assert shard.shape[0] == embed.shape[0] // 8
        mu = state['opt']['mu']['embed']['tokens']
        assert mu.sharding.spec == P('data')
        print('scatter runner-on-mesh OK')
    """, n_devices=8))


@pytest.mark.slow
def test_bucketed_runner_trains_on_eight_device_mesh():
    print(run_py("""
        import dataclasses, jax, numpy as np
        from repro.configs import get_config, reduced
        from repro.configs.base import RunConfig, ShapeConfig
        from repro.launch.mesh import make_host_mesh
        from repro.models import build_model
        from repro.train.optimizer import AdamWConfig
        from repro.train.runner import StepRunner, TrainLoop

        B, S = 16, 32
        cfg = dataclasses.replace(reduced(get_config('bert-mlm-120m'),
                                          d_model=64),
                                  vocab_size=256, max_position=S)
        model = build_model(cfg)
        run = RunConfig(model=cfg, shape=ShapeConfig('t', S, B, 'train'),
                        sharding='ddp', param_dtype='float32',
                        activation_dtype='float32')
        runner = StepRunner(model, run, AdamWConfig(total_steps=8),
                            make_host_mesh(4, 2), grad_bucket_mb=0.05)
        info = runner.grad_sync_info()
        assert info['grad_sync'] == 'bucketed_overlap', info
        assert info['n_buckets'] > 1
        assert info['comm_bytes'] == sum(info['bucket_bytes'])

        rng = np.random.default_rng(0)
        def batches():
            while True:
                t = rng.integers(4, 256, (B, S)).astype(np.int32)
                yield {'tokens': t, 'labels': t,
                       'loss_mask': np.ones((B, S), np.float32)}

        state, log = TrainLoop(runner, log_every=2).run(batches(), 8)
        assert log.telemetry['n_traces'] == 1         # jit-once preserved
        assert log.telemetry['grad_sync'] == 'bucketed_overlap'
        assert log.telemetry['grad_buckets'] == info['n_buckets']
        losses = [m['loss'] for m in log.metrics]
        assert all(np.isfinite(l) for l in losses), losses
        print('runner-on-mesh OK')
    """, n_devices=8))


@pytest.mark.slow
def test_tp_overlap_matches_fused_on_two_device_mesh():
    # pure tp on a (data=1, model=2) mesh: the explicit sequence-
    # parallel schedule (one all_gather into each block's parallel
    # region, one psum_scatter out) must reproduce the single-device
    # fused gradients and loss trajectory exactly
    print(run_py("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config, reduced
        from repro.configs.base import RunConfig, ShapeConfig
        from repro.distributed.sharding import ParallelPlan
        from repro.launch.mesh import make_host_mesh
        from repro.models import build_model
        from repro.train.optimizer import AdamWConfig
        from repro.train.train_step import (init_state, make_grad_fn,
                                            make_train_step)

        def close(ref, got, rtol=1e-6, floor=1e-8):
            # leaf scale clamped at 1.0: the tp schedule reorders the
            # seq-dim reductions (slice + collective transpose), so
            # tiny-scale leaves see noise marginally above a bare
            # rtol*max floor — same convention as the tp_overlap bench
            for a, b in zip(jax.tree_util.tree_leaves(ref),
                            jax.tree_util.tree_leaves(got)):
                a, b = np.asarray(a), np.asarray(b)
                scale = max(float(np.abs(a).max()), 1.0)
                np.testing.assert_allclose(b, a, rtol=rtol,
                                           atol=rtol * scale + floor)

        B, S = 8, 32
        cfg = dataclasses.replace(reduced(get_config('bert-mlm-120m'),
                                          d_model=64),
                                  vocab_size=256, max_position=S)
        model = build_model(cfg)
        mesh = make_host_mesh(data=1, model=2)
        opt = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=10)
        toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 4,
                                  cfg.vocab_size)
        for n_micro in (1, 4):
            # micro=1 carries the ragged-mask case (seq-sliced rows see
            # different masked counts per model rank), micro=4 the
            # uniform one
            if n_micro == 1:
                mask = (jax.random.uniform(jax.random.PRNGKey(2),
                                           (B, S)) > 0.3).astype(
                                               jnp.float32)
            else:
                mask = jnp.ones((B, S), jnp.float32)
            batch = {'tokens': toks, 'labels': jnp.roll(toks, -1, 1),
                     'loss_mask': mask}
            run = RunConfig(model=cfg,
                            shape=ShapeConfig('t', S, B, 'train'),
                            sharding='tp', param_dtype='float32',
                            activation_dtype='float32',
                            microbatch=n_micro)
            params = init_state(model, jax.random.PRNGKey(0),
                                run)['params']
            _, gref, mref = jax.jit(make_grad_fn(model, run))(params,
                                                              batch)
            plan = ParallelPlan.for_run(run, mesh, grad_bucket_mb=0.05)
            assert plan.grad_sync == 'tp_overlap', plan.describe()
            _, gt, mt = jax.jit(make_grad_fn(model, run, mesh, plan))(
                params, batch)
            close(gref, gt)                                   # rtol 1e-6
            np.testing.assert_allclose(float(mref['loss']),
                                       float(mt['loss']), rtol=1e-6)

            # identical loss + grad-norm trajectory over 4 full steps
            step_t = jax.jit(make_train_step(model, run, opt, mesh,
                                             plan=plan))
            step_f = jax.jit(make_train_step(model, run, opt))
            st = init_state(model, jax.random.PRNGKey(0), run)
            sf = init_state(model, jax.random.PRNGKey(0), run)
            for _ in range(4):
                st, m_t = step_t(st, batch)
                sf, m_f = step_f(sf, batch)
                np.testing.assert_allclose(float(m_f['loss']),
                                           float(m_t['loss']),
                                           rtol=1e-6)
                np.testing.assert_allclose(float(m_f['grad_norm']),
                                           float(m_t['grad_norm']),
                                           rtol=1e-5)
            print(f'tp micro={n_micro} OK')
        print('tp equivalence OK')
    """, n_devices=2))


@pytest.mark.slow
def test_fsdp_tp_runner_trains_on_four_device_mesh():
    # fsdp_tp on a 2x2 (data x model) mesh: dense leaves ZeRO-3 over
    # 'data', tp leaves sharded over 'model', optimizer moments
    # following params — with the tp telemetry surfaced
    print(run_py("""
        import dataclasses, jax, numpy as np
        from repro.configs import get_config, reduced
        from repro.configs.base import RunConfig, ShapeConfig
        from repro.launch.mesh import make_host_mesh
        from repro.models import build_model
        from repro.train.optimizer import AdamWConfig
        from repro.train.runner import StepRunner, TrainLoop

        B, S = 8, 32
        cfg = dataclasses.replace(reduced(get_config('bert-mlm-120m'),
                                          d_model=64),
                                  vocab_size=256, max_position=S)
        model = build_model(cfg)
        run = RunConfig(model=cfg, shape=ShapeConfig('t', S, B, 'train'),
                        sharding='fsdp_tp', param_dtype='float32',
                        activation_dtype='float32')
        runner = StepRunner(model, run, AdamWConfig(total_steps=6),
                            make_host_mesh(data=2, model=2),
                            grad_bucket_mb=0.05)
        info = runner.grad_sync_info()
        assert info['grad_sync'] == 'tp_overlap', info
        assert info['tp_engaged'] and info['tp_size'] == 2
        assert info['n_tp_buckets'] >= 1
        assert info['tp_wire_bytes_per_device'] > 0
        assert info['param_gather_bytes'] > 0

        rng = np.random.default_rng(0)
        def batches():
            while True:
                t = rng.integers(4, 256, (B, S)).astype(np.int32)
                yield {'tokens': t, 'labels': t,
                       'loss_mask': np.ones((B, S), np.float32)}

        state, log = TrainLoop(runner, log_every=2).run(batches(), 6)
        assert log.telemetry['n_traces'] == 1         # jit-once preserved
        assert log.telemetry['grad_sync'] == 'tp_overlap'
        losses = [m['loss'] for m in log.metrics]
        assert all(np.isfinite(l) for l in losses), losses

        # state layout: tp leaves live sharded over 'model' (local
        # shard = 1/2 along the sharded dim), dense ZeRO-3 leaves over
        # 'data', and every optimizer moment follows its param
        leaves = jax.tree_util.tree_leaves(state['params'])
        specs = [tuple(l.sharding.spec) for l in leaves]
        assert any('model' in s for s in specs), specs
        assert any('data' in s for s in specs), specs
        tp_leaf = next(l for l, s in zip(leaves, specs) if 'model' in s)
        ax = tuple(tp_leaf.sharding.spec).index('model')
        shard = tp_leaf.addressable_shards[0].data
        assert shard.shape[ax] == tp_leaf.shape[ax] // 2
        zl = next(l for l, s in zip(leaves, specs) if 'data' in s)
        zax = tuple(zl.sharding.spec).index('data')
        assert zl.addressable_shards[0].data.shape[zax] \\
            == zl.shape[zax] // 2
        for part in ('mu', 'nu'):
            for p, m in zip(leaves,
                            jax.tree_util.tree_leaves(
                                state['opt'][part])):
                assert p.sharding.spec == m.sharding.spec, (part,
                                                            p.shape)
        print('fsdp_tp runner-on-mesh OK')
    """, n_devices=4))
