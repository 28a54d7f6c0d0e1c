"""The loss head runs on the masked positions only, and stays exact.

``chunked_xent`` gathers each row's masked positions to its front and
runs the MLM transform / unembedding and log-softmax on the segments up
to the smallest capacity of ``loss_capacities(S)`` that holds every
row's count.  Each case compares its sums and their gradients (params
and hidden) with the plain computation over every position, and checks
the capacity taken.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _subproc import run_py
from repro.configs import get_config, reduced
from repro.configs.base import RunConfig, ShapeConfig
from repro.models import build_model
from repro.models.transformer import head_apply
from repro.train import train_step as ts

B, S, V = 4, 64, 256
ARCHS = {"encoder": "bert-mlm-120m", "causal": "starcoder2-3b"}


def _model(family, seq=S):
    cfg = dataclasses.replace(reduced(get_config(ARCHS[family]), d_model=32),
                              vocab_size=V, max_position=seq)
    model = build_model(cfg)
    return cfg, model.init(jax.random.PRNGKey(0))


def _rows_with(counts, seq=S, seed=0):
    """A 0/1 mask whose rows hold ``counts`` masked positions each, at
    random places."""
    rng = np.random.default_rng(seed)
    m = np.zeros((len(counts), seq), np.float32)
    for r, n in enumerate(counts):
        m[r, rng.choice(seq, n, replace=False)] = 1.0
    return m


def _bernoulli(seed=1, seq=S, rate=0.15):
    return (np.random.default_rng(seed).random((B, seq)) < rate).astype(
        np.float32)


MASKS = {
    # name: (mask, capacity taken over S)
    "none": (np.zeros((B, S), np.float32), 0.125),
    "bernoulli": (_bernoulli(), 0.25),
    "quarter": (_rows_with([S // 4, 3, 9, 0]), 0.25),
    "quarter_plus_one": (_rows_with([S // 4 + 1, 3, 9, 0]), 0.5),
    "all_ones": (np.ones((B, S), np.float32), 1.0),
}


def _all_positions(params, h, labels, mask, cfg):
    """The plain head: logits at every position, then the mask."""
    logits = head_apply(params, h, cfg)
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(lp, labels[..., None], axis=-1)[..., 0]
    acc = (logits.argmax(-1) == labels) * mask
    return (nll * mask).sum(), acc.sum(), mask.sum()


def _compare(cfg, params, mask, *, chunk, use_pallas=False, seq=S,
             rtol=2e-5):
    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.normal(size=(B, seq, cfg.d_model)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, V, (B, seq)), jnp.int32)
    mask = jnp.asarray(mask)

    def compacted(p, h):
        s = ts.chunked_xent(p, h, labels, mask, cfg, chunk=chunk,
                            use_pallas=use_pallas)
        return s[0], s

    def plain(p, h):
        s = _all_positions(p, h, labels, mask, cfg)
        return s[0], s

    grad = lambda f: jax.jit(jax.value_and_grad(f, argnums=(0, 1),
                                                has_aux=True))
    (_, got), g_got = grad(compacted)(params, h)
    (_, want), g_want = grad(plain)(params, h)
    for a, b in zip(got[:3], want):
        np.testing.assert_allclose(float(a), float(b), rtol=rtol, atol=1e-4)
    for a, b in zip(jax.tree_util.tree_leaves(g_got),
                    jax.tree_util.tree_leaves(g_want)):
        a, b = np.asarray(a), np.asarray(b)
        np.testing.assert_allclose(a, b, rtol=rtol,
                                   atol=rtol * float(np.abs(b).max()) + 1e-7)
    return float(got[3])


@pytest.mark.parametrize("mask_name", sorted(MASKS))
@pytest.mark.parametrize("family", sorted(ARCHS))
def test_compacted_head_matches_every_position(family, mask_name):
    cfg, params = _model(family)
    mask, rows = MASKS[mask_name]
    assert _compare(cfg, params, mask, chunk=24) == rows


@pytest.mark.parametrize("mask_name", ["bernoulli", "all_ones"])
def test_blocks_that_do_not_divide_the_rows(mask_name):
    # S = 58 = 2 x 29: blocks of at most 7 cannot tile it, so the all-
    # positions case pads its last block; the 16-row case tiles by 4
    seq = 58
    assert ts.loss_blocks(seq, 7) == (9, 7)
    cfg, params = _model("encoder", seq)
    mask = _bernoulli(seq=seq) if mask_name == "bernoulli" \
        else np.ones((B, seq), np.float32)
    rows = _compare(cfg, params, mask, chunk=7, seq=seq)
    want = 16 / seq if mask_name == "bernoulli" else 1.0
    assert rows == pytest.approx(want)


def test_pallas_xent_gets_the_compacted_rows():
    cfg, params = _model("encoder")
    assert _compare(cfg, params, MASKS["bernoulli"][0], chunk=16,
                    use_pallas=True, rtol=1e-4) == 0.25


def test_capacity_ladder():
    assert ts.loss_capacities(512) == (64, 128, 256, 512)
    assert ts.loss_capacities(64) == (8, 16, 32, 64)
    assert ts.loss_capacities(100) == (16, 32, 56, 100)
    assert ts.loss_capacities(12) == (8, 12)
    assert ts.loss_capacities(8) == (8,)


@pytest.mark.parametrize("rows, chunk, blocks", [
    (512, 81, (8, 64)),     # 350m at batch 48: 8 x 64, no padding
    (128, 81, (2, 64)),
    (512, 21, (32, 16)),    # 120m at batch 184
    (128, 21, (8, 16)),
    (58, 7, (9, 7)),        # no exact tiling within twice the least
    (64, 512, (1, 64)),
])
def test_loss_blocks(rows, chunk, blocks):
    n, c = ts.loss_blocks(rows, chunk)
    assert (n, c) == blocks
    assert c <= chunk and 0 <= n * c - rows < n


@pytest.mark.parametrize("counts, rows", [
    ([0, 0, 0, 0], 8), ([8, 0, 1, 2], 8), ([9, 0, 0, 0], 16),
    ([16, 16, 16, 16], 16), ([17, 0, 0, 0], 32), ([33, 1, 1, 1], 64),
    ([64, 0, 0, 0], 64),
])
def test_case_taken_for_row_counts(counts, rows):
    cfg, params = _model("encoder")
    mask = jnp.asarray(_rows_with(counts))
    h = jnp.zeros((B, S, cfg.d_model), jnp.float32)
    labels = jnp.zeros((B, S), jnp.int32)
    got = jax.jit(lambda m: ts.chunked_xent(params, h, labels, m, cfg,
                                            chunk=16))(mask)
    assert float(got[3]) == pytest.approx(rows / S)
    assert float(got[2]) == sum(counts)


@pytest.mark.parametrize("mask_name, rows", [("bernoulli", 0.25),
                                             ("all_ones", 1.0)])
def test_loss_rows_reaches_the_loops_registry(mask_name, rows):
    from repro.launch.mesh import make_host_mesh
    from repro.observability import MetricsRegistry
    from repro.train.optimizer import AdamWConfig
    from repro.train.runner import StepRunner, TrainLoop

    cfg, _ = _model("encoder")
    run = RunConfig(model=cfg, shape=ShapeConfig("t", S, B, "train"),
                    sharding="ddp", param_dtype="float32",
                    activation_dtype="float32")
    runner = StepRunner(build_model(cfg), run, AdamWConfig(),
                        make_host_mesh(1, 1))
    mask = MASKS[mask_name][0]

    def batches():
        rng = np.random.default_rng(0)
        while True:
            toks = rng.integers(4, V, (B, S)).astype(np.int32)
            yield {"tokens": toks, "labels": toks, "loss_mask": mask}

    reg = MetricsRegistry()
    _, log = TrainLoop(runner, log_every=1, metrics=reg,
                       device_prefetch=False).run(batches(), 2)
    assert [m["loss_rows"] for m in log.metrics] == [rows, rows]
    assert reg["train_loss_rows"].value == rows
    assert reg["train_tokens"].value == float(mask.sum())


def test_per_shard_steps_take_their_own_capacity():
    """Under the bucketed ddp ``shard_map`` step each shard compacts its
    own rows (here to 32, 16, 8 and 64 of 64): the gradient equals the
    single-device step's, and ``loss_rows`` is the shards' mean."""
    print(run_py("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config, reduced
        from repro.configs.base import RunConfig, ShapeConfig
        from repro.distributed.sharding import ParallelPlan
        from repro.launch.mesh import make_host_mesh
        from repro.models import build_model
        from repro.train.train_step import init_state, make_grad_fn

        B, S, V = 8, 64, 256
        cfg = dataclasses.replace(reduced(get_config('bert-mlm-120m'),
                                          d_model=32),
                                  vocab_size=V, max_position=S)
        model = build_model(cfg)
        rng = np.random.default_rng(0)
        counts = [20, 3, 16, 2, 5, 0, 64, 1]   # two rows a shard
        mask = np.zeros((B, S), np.float32)
        for r, n in enumerate(counts):
            mask[r, rng.choice(S, n, replace=False)] = 1.0
        toks = rng.integers(4, V, (B, S)).astype(np.int32)
        batch = {'tokens': toks, 'labels': toks, 'loss_mask': mask}
        run = RunConfig(model=cfg, shape=ShapeConfig('t', S, B, 'train'),
                        sharding='ddp', param_dtype='float32',
                        activation_dtype='float32')
        params = init_state(model, jax.random.PRNGKey(0), run)['params']
        _, g1, m1 = jax.jit(make_grad_fn(model, run))(params, batch)
        plan = ParallelPlan.for_run(run, make_host_mesh(4, 1))
        assert plan.grad_sync == 'bucketed_overlap', plan.describe()
        _, g4, m4 = jax.jit(make_grad_fn(model, run, plan.mesh, plan))(
            params, batch)
        for a, b in zip(jax.tree_util.tree_leaves(g1),
                        jax.tree_util.tree_leaves(g4)):
            a, b = np.asarray(a), np.asarray(b)
            np.testing.assert_allclose(
                b, a, rtol=1e-5, atol=1e-5 * float(np.abs(a).max()) + 1e-8)
        np.testing.assert_allclose(float(m4['loss']), float(m1['loss']),
                                   rtol=1e-6)
        assert float(m1['loss_rows']) == 1.0
        assert float(m4['loss_rows']) == (0.5 + 0.25 + 0.125 + 1.0) / 4
        assert float(m4['tokens']) == sum(counts)
        print('per-shard OK')
    """, n_devices=4))
