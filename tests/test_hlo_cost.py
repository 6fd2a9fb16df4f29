"""The HLO cost walker is the roofline's measurement backbone — pin it down
against hand-countable programs."""
import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_cost import module_costs, parse_module


def _costs(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return module_costs(compiled.as_text())


class TestFlops:
    def test_single_matmul(self):
        a = jax.ShapeDtypeStruct((128, 256), jnp.float32)
        b = jax.ShapeDtypeStruct((256, 64), jnp.float32)
        r = _costs(lambda x, y: x @ y, a, b)
        assert r["flops_per_device"] == pytest.approx(2 * 128 * 256 * 64,
                                                      rel=0.01)

    def test_scan_multiplies_trip_count(self):
        w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        x = jax.ShapeDtypeStruct((64, 64), jnp.float32)

        def scanned(w, x):
            def body(c, _):
                return c @ w, None
            y, _ = jax.lax.scan(body, x, None, length=13)
            return y

        r = _costs(scanned, w, x)
        assert r["flops_per_device"] == pytest.approx(13 * 2 * 64 ** 3,
                                                      rel=0.01)

    def test_nested_scans_multiply(self):
        w = jax.ShapeDtypeStruct((32, 32), jnp.float32)
        x = jax.ShapeDtypeStruct((32, 32), jnp.float32)

        def nested(w, x):
            def outer(c, _):
                def inner(ci, _):
                    return ci @ w, None
                c, _ = jax.lax.scan(inner, c, None, length=5)
                return c, None
            y, _ = jax.lax.scan(outer, x, None, length=4)
            return y

        r = _costs(nested, w, x)
        assert r["flops_per_device"] == pytest.approx(20 * 2 * 32 ** 3,
                                                      rel=0.01)

    def test_batched_dot(self):
        a = jax.ShapeDtypeStruct((4, 16, 32), jnp.float32)
        b = jax.ShapeDtypeStruct((4, 32, 8), jnp.float32)
        r = _costs(lambda x, y: jnp.einsum("bij,bjk->bik", x, y), a, b)
        assert r["flops_per_device"] == pytest.approx(2 * 4 * 16 * 32 * 8,
                                                      rel=0.01)


class TestCollectives:
    def test_psum_counted_with_ring_factor(self):
        n = len(jax.devices())
        if n < 2:
            pytest.skip("needs > 1 device")
        from jax.sharding import PartitionSpec as P
        from repro.dist.sharding import make_mesh
        mesh = make_mesh((n,), ("d",))
        fn = jax.shard_map(lambda x: jax.lax.psum(x, "d"), mesh=mesh,
                           in_specs=P("d"), out_specs=P())
        x = jax.ShapeDtypeStruct((n * 128,), jnp.float32)
        compiled = jax.jit(fn).lower(x).compile()
        r = module_costs(compiled.as_text())
        ar = r["collectives"]["all-reduce"]
        assert ar["count"] >= 1
        # 2 * bytes * (n-1)/n ring model on the 128-elem shard
        assert ar["bytes"] == pytest.approx(2 * 128 * 4 * (n - 1) / n,
                                            rel=0.05)


class TestParser:
    def test_parses_computations(self):
        a = jax.ShapeDtypeStruct((8, 8), jnp.float32)
        compiled = jax.jit(lambda x: jnp.tanh(x @ x)).lower(a).compile()
        comps, entry = parse_module(compiled.as_text())
        assert entry is not None
        assert entry in comps
        assert comps[entry].instrs
