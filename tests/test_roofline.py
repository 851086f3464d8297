"""Validate the trip-count-aware HLO cost model and collective parser."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline.hlo_cost import hlo_cost, parse_computations, xla_cost_dict
from repro.roofline.analysis import parse_collectives, shape_bytes


def _compiled(f, *args):
    return jax.jit(f).lower(*args).compile()


def test_dot_flops_match_xla():
    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((256, 512), jnp.float32)
    comp = _compiled(lambda a, b: a @ b, x, w)
    c = hlo_cost(comp.as_text())
    want = 2 * 128 * 256 * 512
    assert abs(c.flops - want) / want < 0.01
    xla = xla_cost_dict(comp.cost_analysis())["flops"]
    assert abs(c.flops - xla) / xla < 0.05


def test_scan_trip_multiplication():
    """The whole point: scan x N must cost ~N x the unrolled-once body."""
    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)

    def f_scan(a, b):
        def body(c, _):
            return jnp.tanh(c @ b), None
        c, _ = jax.lax.scan(body, a, None, length=10)
        return c.sum()

    def f_unroll(a, b):
        c = a
        for _ in range(10):
            c = jnp.tanh(c @ b)
        return c.sum()

    cs = hlo_cost(_compiled(f_scan, x, w).as_text())
    comp_u = _compiled(f_unroll, x, w)
    cu = hlo_cost(comp_u.as_text())
    assert abs(cs.flops - cu.flops) / cu.flops < 0.05
    # and both match XLA's count of the unrolled program
    xla_u = xla_cost_dict(comp_u.cost_analysis())["flops"]
    assert abs(cs.flops - xla_u) / xla_u < 0.05
    assert cs.dynamic_loops == 0


def test_nested_scan_trips():
    x = jax.ShapeDtypeStruct((32, 32), jnp.float32)

    def f(a):
        def outer(c, _):
            def inner(d, _):
                return d @ d, None
            d, _ = jax.lax.scan(inner, c, None, length=4)
            return d, None
        c, _ = jax.lax.scan(outer, a, None, length=3)
        return c.sum()

    c = hlo_cost(_compiled(f, x).as_text())
    want = 3 * 4 * 2 * 32 * 32 * 32
    assert abs(c.flops - want) / want < 0.1


def test_bytes_scale_with_trips():
    x = jax.ShapeDtypeStruct((256, 256), jnp.float32)

    def f_scan(a):
        def body(c, _):
            return c @ c, None
        c, _ = jax.lax.scan(body, a, None, length=8)
        return c

    c1 = hlo_cost(_compiled(f_scan, x).as_text())
    # one iteration reads/writes >= 3 x 256KB; 8 trips >= 6MB
    assert c1.bytes > 8 * 3 * 256 * 256 * 4 * 0.8


def test_shape_bytes_parser():
    assert shape_bytes("f32[16,128]") == 16 * 128 * 4
    assert shape_bytes("bf16[2,3,4]") == 48
    assert shape_bytes("pred[8]") == 8
    assert shape_bytes("f32") == 4
    assert shape_bytes("s64[]") == 8


def test_collectives_counted_inside_loops(tmp_path):
    """psum inside a scan: hlo_cost multiplies by trips."""
    import subprocess
    import sys
    import textwrap
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        import sys
        sys.path.insert(0, "src")
        from repro.roofline.hlo_cost import hlo_cost
        mesh = jax.make_mesh((4,), ("data",))

        def f(x):
            def body(c, _):
                return jax.lax.psum(c, "data") * 0.5, None
            c, _ = jax.lax.scan(body, x, None, length=6)
            return c

        fn = jax.shard_map(f, mesh=mesh, in_specs=P(None, "data"),
                           out_specs=P(None, "data"), check_vma=False)
        comp = jax.jit(fn).lower(
            jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile()
        c = hlo_cost(comp.as_text())
        per = 64 * 16 * 4  # per-device shard bytes
        assert c.coll_bytes >= 6 * per, (c.coll_bytes, per)
        print("OK", c.coll_bytes)
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd="/root/repo", timeout=240)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout
