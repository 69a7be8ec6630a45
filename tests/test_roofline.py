"""Roofline tooling tests: the HLO cost walker must be exact on known
workloads (scan trip counts, nested scans, dus windows, collectives)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline.analysis import Roofline, parse_collectives
from repro.roofline.hlo_cost import analyze


def test_walker_scan_trip_counts():
    def f(x, w):
        def body(c, _):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, None, length=12)
        return y.sum()

    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    res = analyze(jax.jit(f).lower(x, w).compile().as_text())
    expect = 12 * 2 * 64 * 128 * 128
    assert abs(res["flops"] - expect) / expect < 0.01


def test_walker_nested_scans():
    def g(x, w):
        def outer(c, _):
            def inner(c2, _):
                return c2 @ w, None
            c2, _ = jax.lax.scan(inner, c, None, length=3)
            return c2, None
        y, _ = jax.lax.scan(outer, x, None, length=5)
        return y.sum()

    x = jax.ShapeDtypeStruct((32, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    res = analyze(jax.jit(g).lower(x, w).compile().as_text())
    expect = 15 * 2 * 32 * 64 * 64
    assert abs(res["flops"] - expect) / expect < 0.01


def test_walker_dus_window_not_full_buffer():
    """Writing a small window into a big stacked buffer per scan step must
    be charged at window size, not buffer size."""
    def f(big, upd):
        def body(buf, i):
            return jax.lax.dynamic_update_index_in_dim(buf, upd, i, 0), None
        out, _ = jax.lax.scan(body, big, jnp.arange(64))
        return out.sum()

    big = jax.ShapeDtypeStruct((64, 1024), jnp.float32)
    upd = jax.ShapeDtypeStruct((1024,), jnp.float32)
    res = analyze(jax.jit(f).lower(big, upd).compile().as_text())
    full_buffer_cost = 64 * 64 * 1024 * 4  # what naive counting charges
    assert res["bytes"] < full_buffer_cost


def test_roofline_terms_and_bottleneck():
    r = Roofline(flops_per_chip=197e12, hbm_bytes_per_chip=819e9 / 2,
                 collective_bytes_per_chip=50e9 * 2, n_chips=4,
                 device_kind="TPU v5 lite", model_flops=4 * 197e12 / 2)
    assert abs(r.t_compute - 1.0) < 1e-9
    assert abs(r.t_memory - 0.5) < 1e-9
    assert abs(r.t_collective - 2.0) < 1e-9
    assert r.bottleneck == "collective"
    assert abs(r.useful_flops_ratio - 0.5) < 1e-9
    assert abs(r.roofline_fraction - 0.25) < 1e-9


def test_roofline_unknown_device_kind_raises():
    with pytest.raises(ValueError, match="no published peaks"):
        Roofline(flops_per_chip=1.0, hbm_bytes_per_chip=1.0,
                 collective_bytes_per_chip=0.0, n_chips=1,
                 device_kind="cpu")


def test_parse_collectives_from_text():
    txt = """
  %ar = f32[16,128]{1,0} all-reduce(%x), replica_groups={}
  %ag.1 = bf16[4,256]{1,0} all-gather(%y), dimensions={0}
  ROOT %cp = (f32[8]{0}, f32[8]{0}) collective-permute(%z)
"""
    got = parse_collectives(txt)
    assert got["all-reduce"]["bytes"] == 16 * 128 * 4
    assert got["all-gather"]["bytes"] == 4 * 256 * 2
    assert got["collective-permute"]["bytes"] == 2 * 8 * 4


def test_walker_counts_collectives_inside_scans():
    """Collectives inside a scanned body must multiply by trip count."""
    mesh = jax.make_mesh((1,), ("d",))

    def f(x):
        def body(c, _):
            return jax.lax.psum(c, "d") * 0.5, None
        y, _ = jax.lax.scan(body, x, None, length=7)
        return y

    from jax.sharding import PartitionSpec as P

    fn = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P(), out_specs=P(),
                               check_vma=False))
    x = jax.ShapeDtypeStruct((256,), jnp.float32)
    res = analyze(fn.lower(x).compile().as_text())
    # 7 trips x 1KB all-reduce (may be optimized away on 1 device; accept
    # either exact multiple or zero-after-folding)
    assert res["collective_bytes"] in (0.0, 7 * 256 * 4) or \
        res["collective_bytes"] % (256 * 4) == 0
