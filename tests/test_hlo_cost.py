"""Trip-count-aware HLO cost analyzer vs known-cost programs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hlo_cost import analyze_hlo, parse_hlo


def _cost(f, *args):
    c = jax.jit(f).lower(*args).compile()
    return analyze_hlo(c.as_text())


def test_plain_matmul_flops():
    a = jax.ShapeDtypeStruct((512, 1024), jnp.float32)
    b = jax.ShapeDtypeStruct((1024, 256), jnp.float32)
    hc = _cost(lambda a, b: a @ b, a, b)
    assert hc.flops == pytest.approx(2 * 512 * 1024 * 256, rel=0.01)


@pytest.mark.parametrize("n", [1, 4, 16])
def test_scan_trip_count_scaling(n):
    """THE defect this module exists for: XLA counts while bodies once."""
    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=n)
        return y
    m = 128
    hc = _cost(f, jax.ShapeDtypeStruct((m, m), jnp.float32),
               jax.ShapeDtypeStruct((m, m), jnp.float32))
    assert hc.flops == pytest.approx(2 * m ** 3 * n, rel=0.02)


def test_nested_scan():
    def g(x, w):
        def outer(c, _):
            def inner(ci, _):
                return ci @ w, None
            y, _ = jax.lax.scan(inner, c, None, length=3)
            return y, None
        y, _ = jax.lax.scan(outer, x, None, length=5)
        return y
    hc = _cost(g, jax.ShapeDtypeStruct((64, 64), jnp.float32),
               jax.ShapeDtypeStruct((64, 64), jnp.float32))
    assert hc.flops == pytest.approx(2 * 64 ** 3 * 15, rel=0.02)


def test_dynamic_slice_bytes_not_overcounted():
    """Slicing a big stacked array per scan step counts slice bytes only."""
    big = jax.ShapeDtypeStruct((64, 128, 128), jnp.float32)

    def f(xs):
        def body(c, i):
            return c + jax.lax.dynamic_index_in_dim(xs, i, 0,
                                                    keepdims=False), None
        out, _ = jax.lax.scan(body, jnp.zeros((128, 128)), jnp.arange(64))
        return out
    hc = _cost(f, big)
    full = 64 * 128 * 128 * 4
    # must be O(n_steps * slice) ~ full array once-ish, NOT steps * full
    assert hc.bytes_accessed < 20 * full


def test_collective_bytes_from_sharded_program():
    import subprocess
    import sys
    import textwrap
    from test_distributed import SRC, child_env
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core.hlo_cost import analyze_hlo
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("d",))
        def f(x):
            return jax.lax.with_sharding_constraint(
                x.sum(0, keepdims=True), NamedSharding(mesh, P()))
        xs = jax.ShapeDtypeStruct((64, 1024), jnp.float32)
        with mesh:
            c = jax.jit(f, in_shardings=NamedSharding(mesh, P("d", None))
                        ).lower(xs).compile()
        hc = analyze_hlo(c.as_text())
        assert hc.collective_bytes > 0, "expected an all-reduce"
        print("COLL", hc.collective_bytes)
    """)
    res = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, env=child_env(SRC),
                         timeout=600)  # 8 fake-device startup is slow on CI
    assert res.returncode == 0, res.stderr[-2000:]
    assert "COLL" in res.stdout


def test_parse_hlo_structure():
    c = jax.jit(lambda a, b: jnp.tanh(a @ b)).lower(
        jax.ShapeDtypeStruct((8, 8), jnp.float32),
        jax.ShapeDtypeStruct((8, 8), jnp.float32)).compile()
    comps, entry = parse_hlo(c.as_text())
    assert entry is not None
    assert entry in comps
    assert len(comps[entry].instructions) > 0
