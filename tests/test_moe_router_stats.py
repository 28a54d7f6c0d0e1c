"""The correctness argument behind MoE grad-sync composability.

The Switch load-balance aux ``E * sum(me * ce) * coef`` is NONLINEAR in
the batch-mean router statistics ``me`` (mean softmax probs) and ``ce``
(mean top-k assignment counts): the mean of per-shard auxes is not the
aux of the global batch.  That nonlinearity is what used to force every
MoE config onto the ``xla_fused`` path (see the old strategy table).

``models.moe.route(..., stat_axes=...)`` fixes the root cause by
pmean-ing me/ce over the data axes inside the shard_map'd step, making
every shard's aux the *global* value — and since pmean is linear (its
transpose is a scaled psum), the per-shard loss contract of
``train_step.loss_for`` (``aux / dp_size`` per shard, gradients summed
across shards) then reproduces the global gradient exactly.  These
tests lock in both directions on a real 2-device mesh:

* psum'd statistics -> per-shard aux == the single-device global aux;
* raw per-shard statistics -> the averaged aux does NOT match (if it
  did, the fallback this PR removed would never have been needed).
"""
import pytest

from _subproc import run_py


@pytest.mark.parametrize("n_experts,top_k", [(4, 1), (4, 2), (8, 2)])
def test_psum_router_stats_reproduce_global_aux(n_experts, top_k):
    print(run_py(f"""
        import dataclasses, functools
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.configs import get_config, reduced
        from jax import shard_map
        from repro.launch.mesh import make_host_mesh
        from repro.models.moe import route

        cfg = reduced(get_config('mixtral-8x7b'), d_model=32)
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, n_experts={n_experts}, top_k={top_k}))
        p = {{'router': 0.5 * jax.random.normal(
            jax.random.PRNGKey(0), (cfg.d_model, {n_experts}))}}
        x = jax.random.normal(jax.random.PRNGKey(1), (64, cfg.d_model))
        _, _, aux_ref = route(p, x, cfg)

        mesh = make_host_mesh(2)

        @functools.partial(
            shard_map, mesh=mesh, in_specs=(P(), P('data')),
            out_specs=(P(), P()), check_vma=False)
        def shard_aux(p_, x_):
            # global statistics: every shard computes the global aux
            _, _, a_glob = route(p_, x_, cfg, stat_axes='data')
            # raw per-shard statistics, averaged afterwards — the
            # WRONG order for a nonlinear function of the stats
            _, _, a_loc = route(p_, x_, cfg)
            return a_glob, jax.lax.pmean(a_loc, 'data')

        a_glob, a_loc = shard_aux(p, x)
        ref = float(aux_ref)
        np.testing.assert_allclose(float(a_glob), ref, rtol=1e-6)
        # mean-of-per-shard-aux must NOT equal the global aux (this is
        # exactly why the old plan forced MoE onto xla_fused)
        rel = abs(float(a_loc) - ref) / abs(ref)
        assert rel > 1e-4, (float(a_loc), ref, rel)
        print('router stats psum OK', ref, float(a_loc))
    """, n_devices=2))


def test_psum_router_stats_grads_sum_to_global():
    # the gradient half of the argument: d(aux)/d(router) computed from
    # per-shard losses aux/dp with pmean'd stats, SUMMED across shards,
    # equals the single-device gradient — pmean's transpose lands the
    # 1/dp exactly where the per-shard loss contract expects it
    print(run_py("""
        import dataclasses, functools
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.configs import get_config, reduced
        from jax import shard_map
        from repro.launch.mesh import make_host_mesh
        from repro.models.moe import route

        cfg = reduced(get_config('mixtral-8x7b'), d_model=32)
        p = {'router': 0.5 * jax.random.normal(
            jax.random.PRNGKey(0), (cfg.d_model, cfg.moe.n_experts))}
        x = jax.random.normal(jax.random.PRNGKey(1), (64, cfg.d_model))
        gref = jax.grad(lambda p_: route(p_, x, cfg)[2])(p)

        mesh = make_host_mesh(2)

        @functools.partial(
            shard_map, mesh=mesh, in_specs=(P(), P('data')),
            out_specs=P(), check_vma=False)
        def summed_shard_grad(p_, x_):
            g = jax.grad(
                lambda q: route(q, x_, cfg, stat_axes='data')[2] / 2.0
            )(p_)
            return jax.tree_util.tree_map(
                lambda l: jax.lax.psum(l, 'data'), g)

        g = summed_shard_grad(p, x)
        np.testing.assert_allclose(
            np.asarray(g['router']), np.asarray(gref['router']),
            rtol=1e-6, atol=1e-8)
        print('router stats grad OK')
    """, n_devices=2))
