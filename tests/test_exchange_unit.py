"""Unit tests for exchange collectives + the HLO traffic parser + profile-level
partitioner behaviour (single device; multi-device in test_multidevice)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.comm import hlo as hlo_parser
from repro.compat import shard_map
from repro.core import exchange
from repro.core.partition import auto_replication
from repro.sparse.io import DATASET_PROFILES


def _mesh1():
    return Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("group", "sub"))


def test_ring_all_gather_single_device_identity():
    mesh = _mesh1()
    x = jnp.arange(12.0).reshape(4, 3)

    def f(x):
        return exchange.ring_all_gather(x, ("group", "sub"))

    out = jax.jit(shard_map(f, mesh=mesh, in_specs=P(("group", "sub")),
                                out_specs=P(None)))(x)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


def test_merge_partials_identity_r1():
    mesh = _mesh1()
    x = jnp.ones((8, 4))

    def f(x):
        return exchange.merge_partials(x, "sub")  # r=1 → identity

    out = jax.jit(shard_map(f, mesh=mesh, in_specs=P(None, None),
                                out_specs=P(None, None)))(x)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


def test_auto_replication_on_paper_profiles():
    """Patents mode-0 (46 indices) on 256 devices NEEDS the beyond-paper
    hierarchical replication — the paper's scheme (r=1) cannot occupy the
    mesh; uniform big modes keep the paper scheme."""
    m = 256
    patents = DATASET_PROFILES["patents"]
    hist = np.full(patents.shape[0], patents.nnz // patents.shape[0])
    r = auto_replication(hist, m)
    assert m // r <= patents.shape[0] and r >= 8
    amazon = DATASET_PROFILES["amazon"]
    hist = np.full(10_000, amazon.nnz // amazon.shape[0])  # flat sample
    assert auto_replication(hist, m) == 1
    # single hot index (Twitch streamers effect): r must split it
    hist = np.ones(100_000, np.int64)
    hist[0] = 10_000_000
    assert auto_replication(hist, m) > 1


def test_traffic_factors():
    hlo = """
  %x1 = f32[1024]{0} all-reduce(f32[1024] %a)
  %x2 = f32[1024]{0} reduce-scatter(f32[4096] %b), dimensions={0}
"""
    coll = hlo_parser.collective_bytes(hlo)
    assert coll["all-reduce"] == 1024 * 4 * 2.0     # ring: 2× bytes
    assert coll["reduce-scatter"] == 1024 * 4 * 1.0


def test_parse_hlo_nested_loops():
    """Nested while loops multiply trip counts."""
    import jax.numpy as jnp

    def f(xs, w):
        def outer(c, x):
            def inner(c2, y):
                return c2 + y @ w, None
            c2, _ = jax.lax.scan(inner, c, x)
            return c2, None
        out, _ = jax.lax.scan(outer, jnp.zeros((2, 4)), xs)
        return out

    compiled = jax.jit(f).lower(
        jax.ShapeDtypeStruct((3, 5, 2, 6), jnp.float32),
        jax.ShapeDtypeStruct((6, 4), jnp.float32)).compile()
    r = hlo_parser.parse_hlo(compiled.as_text())
    # 2·2·4·6 = 96 flops per inner step × 5 inner × 3 outer = 1440
    assert r["dot_flops"] == 3 * 5 * 96.0, r
