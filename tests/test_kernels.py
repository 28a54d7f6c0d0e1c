"""Pallas kernels vs pure-jnp oracles (interpret mode), incl. hypothesis
shape/dtype sweeps as required per kernel."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.fused_xent import fused_xent
from repro.kernels.ssd_scan import ssd_scan


def _qkv(key, B, S, H, Hkv, D, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    return (jax.random.normal(k1, (B, S, H, D), dtype),
            jax.random.normal(k2, (B, S, Hkv, D), dtype),
            jax.random.normal(k3, (B, S, Hkv, D), dtype))


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kw", [
    dict(causal=True),
    dict(causal=False),
    dict(causal=True, window=64),
    dict(causal=True, softcap=50.0),
    dict(causal=True, window=32, softcap=30.0),
])
def test_flash_matches_ref(kw):
    q, k, v = _qkv(jax.random.PRNGKey(0), 2, 256, 4, 2, 64)
    out = flash_attention_fwd(q, k, v, **kw)
    want = ref.flash_attention_ref(q, k, v, **kw)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


@settings(max_examples=12, deadline=None)
@given(
    bq=st.sampled_from([64, 128]),
    s_mult=st.integers(1, 4),
    rep=st.sampled_from([1, 2, 4]),
    d=st.sampled_from([32, 64, 128]),
    dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
)
def test_flash_shape_dtype_sweep(bq, s_mult, rep, d, dtype):
    S = bq * s_mult
    Hkv = 2
    q, k, v = _qkv(jax.random.PRNGKey(s_mult), 1, S, Hkv * rep, Hkv, d, dtype)
    out = flash_attention_fwd(q, k, v, causal=True, block_q=bq, block_k=bq)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    tol = 3e-2 if dtype == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(np.float32(out), np.float32(want),
                               atol=tol, rtol=tol)
    assert out.dtype == dtype


def test_flash_custom_vjp_close_to_ref_grad():
    q, k, v = _qkv(jax.random.PRNGKey(3), 1, 128, 2, 1, 32)
    g1 = jax.grad(lambda q: ops.flash_attention(q, k, v).sum())(q)
    g2 = jax.grad(lambda q: ref.flash_attention_ref(q, k, v).sum())(q)
    np.testing.assert_allclose(g1, g2, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# SSD chunk scan
# ---------------------------------------------------------------------------


def _ssd_inputs(key, B, S, H, P, G, N):
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, S, G, N))
    Cm = jax.random.normal(ks[4], (B, S, G, N))
    return x, dt, A, Bm, Cm


@pytest.mark.parametrize("S,chunk", [(64, 32), (96, 32), (100, 32), (256, 64)])
def test_ssd_matches_ref(S, chunk):
    x, dt, A, Bm, Cm = _ssd_inputs(jax.random.PRNGKey(0), 2, S, 4, 16, 2, 8)
    y1, s1 = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk)
    y2, s2 = ref.ssd_ref(x, dt, A, Bm, Cm, chunk=chunk)
    np.testing.assert_allclose(y1, y2, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(s1, s2, atol=1e-4, rtol=1e-4)


@settings(max_examples=10, deadline=None)
@given(
    B=st.integers(1, 2),
    nc=st.integers(1, 4),
    H=st.sampled_from([2, 4]),
    P=st.sampled_from([8, 16]),
    N=st.sampled_from([8, 16]),
)
def test_ssd_shape_sweep(B, nc, H, P, N):
    S = 32 * nc
    x, dt, A, Bm, Cm = _ssd_inputs(jax.random.PRNGKey(nc), B, S, H, P, 1, N)
    y1, s1 = ssd_scan(x, dt, A, Bm, Cm, chunk=32)
    y2, s2 = ref.ssd_ref(x, dt, A, Bm, Cm, chunk=32)
    np.testing.assert_allclose(y1, y2, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(s1, s2, atol=1e-4, rtol=1e-4)


def test_ssd_chunk_invariance_of_ref():
    """SSD is exact: the chunk size must not change the result."""
    x, dt, A, Bm, Cm = _ssd_inputs(jax.random.PRNGKey(1), 1, 128, 2, 8, 1, 8)
    y1, s1 = ref.ssd_ref(x, dt, A, Bm, Cm, chunk=16)
    y2, s2 = ref.ssd_ref(x, dt, A, Bm, Cm, chunk=64)
    np.testing.assert_allclose(y1, y2, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(s1, s2, atol=1e-4, rtol=1e-4)


def test_ssd_ref_matches_naive_recurrence():
    """Chunked dual form == step-by-step recurrence (ssd_step)."""
    from repro.models.ssm import ssd_step

    x, dt, A, Bm, Cm = _ssd_inputs(jax.random.PRNGKey(2), 1, 40, 2, 8, 1, 8)
    y_ref, s_ref = ref.ssd_ref(x, dt, A, Bm, Cm, chunk=16)
    state = jnp.zeros((1, 2, 8, 8))
    ys = []
    for t in range(40):
        y, state = ssd_step(state, x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t])
        ys.append(y)
    y_naive = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(y_ref, y_naive, atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(s_ref, state, atol=1e-3, rtol=1e-3)


# ---------------------------------------------------------------------------
# fused cross-entropy
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("T,V,bt,bv", [
    (64, 1000, 32, 256), (100, 1000, 32, 512), (128, 517, 64, 128),
])
def test_xent_matches_ref(T, V, bt, bv):
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    logits = jax.random.normal(k1, (T, V)) * 3
    labels = jax.random.randint(k2, (T,), 0, V)
    out = fused_xent(logits, labels, block_t=bt, block_v=bv)
    want = ref.xent_ref(logits, labels)
    np.testing.assert_allclose(out, want, atol=1e-4, rtol=1e-4)


@settings(max_examples=10, deadline=None)
@given(T=st.integers(1, 200), V=st.integers(2, 2000))
def test_xent_property_sweep(T, V):
    k1, k2 = jax.random.split(jax.random.PRNGKey(T * 1000 + V))
    logits = jax.random.normal(k1, (T, V))
    labels = jax.random.randint(k2, (T,), 0, V)
    out = fused_xent(logits, labels)
    want = ref.xent_ref(logits, labels)
    np.testing.assert_allclose(out, want, atol=1e-4, rtol=1e-4)
    assert bool((out >= -1e-5).all())  # nll is non-negative


def test_xent_grad_matches_softmax_identity():
    """d nll/d logits = softmax - onehot (via the custom vjp)."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(5))
    logits = jax.random.normal(k1, (16, 64))
    labels = jax.random.randint(k2, (16,), 0, 64)
    g = jax.grad(lambda l: ops.xent(l, labels).sum())(logits)
    want = jax.nn.softmax(logits, -1) - jax.nn.one_hot(labels, 64)
    np.testing.assert_allclose(g, want, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("backend,explicit,want", [
    ("cpu", None, True), ("tpu", None, False), ("gpu", None, RuntimeError),
    ("tpu", True, True), ("cpu", False, False)])
def test_interpret_mode_follows_backend(monkeypatch, backend, explicit,
                                        want):
    from repro.kernels import resolve_interpret

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if want is RuntimeError:
        with pytest.raises(RuntimeError):
            resolve_interpret(explicit)
    else:
        assert resolve_interpret(explicit) is want
