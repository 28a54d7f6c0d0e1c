"""Multi-device semantics (8 virtual CPU devices via subprocess, since the
device count is locked at jax init): sharded train step == single-device,
expert-parallel MoE == dense, distributed decode == local decode."""
import pytest

from _subproc import run_py


def _run(body: str):
    return run_py(body, n_devices=8)


@pytest.mark.slow
def test_fsdp_tp_train_step_matches_single_device():
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config, reduced
        from repro.configs.base import RunConfig, ShapeConfig
        from repro.models import build_model
        from repro.train.optimizer import AdamWConfig
        from repro.train.train_step import (init_state, make_train_step,
                                            state_shardings, batch_shardings)
        from repro.launch.mesh import make_host_mesh

        cfg = reduced(get_config('llava-next-mistral-7b'), d_model=128)
        model = build_model(cfg)
        shape = ShapeConfig('t', 64, 4, 'train')
        opt = AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10)
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 64), 0,
                                  cfg.vocab_size)
        batch = {'tokens': toks, 'labels': jnp.roll(toks, -1, 1),
                 'loss_mask': jnp.ones((4, 64), jnp.float32),
                 'image_embeds': 0.1*jax.random.normal(
                     jax.random.PRNGKey(2),
                     (4, cfg.n_image_tokens, cfg.d_model))}

        # single device
        run1 = RunConfig(model=cfg, shape=shape, sharding='ddp',
                         param_dtype='float32', activation_dtype='float32')
        state = init_state(model, jax.random.PRNGKey(0), run1)
        s1, m1 = jax.jit(make_train_step(model, run1, opt))(state, batch)

        # 2x4 mesh fsdp_tp
        mesh = make_host_mesh(2, 4)
        run2 = run1.with_(sharding='fsdp_tp')
        st_sh = state_shardings(model, mesh, run2)
        state2 = init_state(model, jax.random.PRNGKey(0), run2)
        state2 = jax.device_put(state2, st_sh)
        step2 = jax.jit(make_train_step(model, run2, opt, mesh),
                        in_shardings=(st_sh, None),
                        out_shardings=(st_sh, None))
        s2, m2 = step2(state2, batch)
        np.testing.assert_allclose(float(m1['loss']), float(m2['loss']),
                                   rtol=2e-4)
        for a, b in zip(jax.tree_util.tree_leaves(s1['params']),
                        jax.tree_util.tree_leaves(s2['params'])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=2e-3)
        print('fsdp_tp == single-device OK')
    """))


def test_context_parallel_attention_matches_single_device():
    # starcoder2's 2 kv heads do not divide a 4-wide model axis, so tp
    # falls back and attention shards q and the scores over the sequence
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config, reduced
        from repro.configs.base import RunConfig, ShapeConfig
        from repro.models import build_model
        from repro.train.optimizer import AdamWConfig
        from repro.train.train_step import (build_attn_ctx, init_state,
                                            make_train_step,
                                            state_shardings)
        from repro.launch.mesh import make_host_mesh

        cfg = reduced(get_config('starcoder2-3b'), d_model=128)
        model = build_model(cfg)
        shape = ShapeConfig('t', 64, 4, 'train')
        opt = AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10)
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 64), 0,
                                  cfg.vocab_size)
        batch = {'tokens': toks, 'labels': jnp.roll(toks, -1, 1),
                 'loss_mask': jnp.ones((4, 64), jnp.float32)}
        run1 = RunConfig(model=cfg, shape=shape, sharding='ddp',
                         param_dtype='float32', activation_dtype='float32')
        state = init_state(model, jax.random.PRNGKey(0), run1)
        s1, m1 = jax.jit(make_train_step(model, run1, opt))(state, batch)

        mesh = make_host_mesh(2, 4)
        run2 = run1.with_(sharding='fsdp_tp')
        assert sorted(build_attn_ctx(cfg, mesh, run2, 4, 64)) == \
            ['kv', 'q']
        st_sh = state_shardings(model, mesh, run2)
        state2 = jax.device_put(
            init_state(model, jax.random.PRNGKey(0), run2), st_sh)
        step2 = jax.jit(make_train_step(model, run2, opt, mesh),
                        in_shardings=(st_sh, None),
                        out_shardings=(st_sh, None))
        s2, m2 = step2(state2, batch)
        np.testing.assert_allclose(float(m1['loss']), float(m2['loss']),
                                   rtol=2e-4)
        for a, b in zip(jax.tree_util.tree_leaves(s1['params']),
                        jax.tree_util.tree_leaves(s2['params'])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=2e-3)
        print('context-parallel == single-device OK')
    """))


@pytest.mark.slow
def test_moe_ep_matches_dense_on_mesh():
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config, reduced
        from repro.models import build_model
        from repro.models.moe import apply_moe_dense, apply_moe_ep
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(2, 4)
        cfg = reduced(get_config('deepseek-v2-lite-16b'))
        m = build_model(cfg)
        p = m.init(jax.random.PRNGKey(0))
        moe_p = jax.tree_util.tree_map(lambda x: x[0],
                                       p['groups'][0][1]['moe'])
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, cfg.d_model))
        yd, _ = apply_moe_dense(moe_p, x, cfg)
        ye, _ = jax.jit(lambda p_, x_: apply_moe_ep(
            p_, x_, cfg, mesh, batch_axes=('data',),
            expert_axis='model'))(moe_p, x)
        np.testing.assert_allclose(np.asarray(yd), np.asarray(ye),
                                   atol=1e-5, rtol=1e-4)
        print('moe ep == dense OK')
    """))


@pytest.mark.slow
def test_distributed_decode_matches_local():
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config, reduced
        from repro.models import build_model
        from repro.models.attention import DistDecode
        from repro.serve.cache import pad_cache
        from repro.launch.mesh import make_host_mesh

        cfg = reduced(get_config('qwen2-72b'))
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        S0 = 31
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, S0+1), 0,
                                  cfg.vocab_size)
        _, cache = model.prefill(params, {'tokens': toks[:, :S0]})
        cache = pad_cache(cache, cfg, 40)  # divisible by the model axis (4)

        local, _ = model.decode_step(params, cache, toks[:, S0:S0+1], S0)

        mesh = make_host_mesh(2, 4)
        dist = DistDecode(axes=('model',), batch_axes=('data',), mesh=mesh)
        fn = jax.jit(lambda p, c, t: model.apply(
            p, {'tokens': t, 'pos': jnp.int32(S0)}, mode='decode',
            cache=c, dist=dist)[0])
        distl = fn(params, cache, toks[:, S0:S0+1])
        np.testing.assert_allclose(np.asarray(local), np.asarray(distl),
                                   atol=2e-4, rtol=2e-3)
        print('distributed decode == local OK')
    """))


@pytest.mark.slow
def test_dist_decode_cache_write_lands_in_right_shard():
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.serve.dist_attn import dist_decode_attend
        from repro.models.attention import DistDecode
        from repro.configs import get_config, reduced
        from repro.launch.mesh import make_host_mesh

        cfg = reduced(get_config('qwen2-72b'))
        mesh = make_host_mesh(2, 4)
        B, S, Hkv, D = 2, 32, 2, 16
        H = 4
        ks = jax.random.split(jax.random.PRNGKey(0), 5)
        q = jax.random.normal(ks[0], (B, 1, H, D))
        kn = jax.random.normal(ks[1], (B, 1, Hkv, D))
        vn = jax.random.normal(ks[2], (B, 1, Hkv, D))
        cache = {'k': jax.random.normal(ks[3], (B, S, Hkv, D)),
                 'v': jax.random.normal(ks[4], (B, S, Hkv, D))}
        pos = 17
        dist = DistDecode(axes=('model',), batch_axes=('data',), mesh=mesh)
        o, newc = jax.jit(lambda q, kn, vn, c: dist_decode_attend(
            q, kn, vn, c, pos, cfg, dist))(q, kn, vn, cache)
        np.testing.assert_allclose(np.asarray(newc['k'][:, pos]),
                                   np.asarray(kn[:, 0]), atol=1e-6)
        # untouched positions preserved
        np.testing.assert_allclose(np.asarray(newc['k'][:, :pos]),
                                   np.asarray(cache['k'][:, :pos]), atol=1e-6)
        print('dist cache write OK')
    """))
