"""The dry run's pieces at CPU scale: the loop-weighted HLO parser, the
roofline terms and the production mesh. The real 256/512-device run is
`python -m repro.launch.dryrun` (see EXPERIMENTS.md)."""
import pytest
import jax

from repro.comm import hlo as hlo_parser
from repro.launch import roofline as rf


def test_collective_parser():
    hlo = """
  %ar = f32[128,256]{1,0} all-reduce(f32[128,256] %x), replica_groups={}
  %ag.1 = bf16[64,32]{1,0} all-gather(bf16[32,32] %y), dimensions={0}
  %rs = f32[16]{0} reduce-scatter(f32[128] %z), dimensions={0}
  %cp = f32[8,8]{1,0} collective-permute(f32[8,8] %w)
  %other = f32[9] add(f32[9] %a, f32[9] %b)
"""
    coll = hlo_parser.collective_bytes(hlo)
    assert coll["all-reduce"] == 128 * 256 * 4 * 2.0
    assert coll["all-gather"] == 64 * 32 * 2 * 1.0
    assert coll["reduce-scatter"] == 16 * 4
    assert coll["collective-permute"] == 8 * 8 * 4
    assert coll["total"] == sum(v for k, v in coll.items() if k != "total")


def test_parser_weights_loops():
    """Collectives/dots inside scan bodies count × known_trip_count."""
    import jax.numpy as jnp

    def f(xs, w):
        def body(c, x):
            return c + x @ w, None
        out, _ = jax.lax.scan(body, jnp.zeros((3, 5)), xs)
        return out

    compiled = jax.jit(f).lower(
        jax.ShapeDtypeStruct((7, 3, 4), jnp.float32),
        jax.ShapeDtypeStruct((4, 5), jnp.float32)).compile()
    r = hlo_parser.parse_hlo(compiled.as_text())
    # 2·M·N·K = 2·3·5·4 = 120 per step × 7 trips
    assert r["dot_flops"] == 7 * 120.0, r


def test_roofline_terms_bottleneck():
    terms = rf.roofline_terms({"flops": 197e12, "bytes accessed": 819e9 / 2},
                              {"total": 0.0})
    # exactly 1s compute, 0.5s memory → compute-bound, fraction 1.0
    assert terms["bottleneck"] == "t_compute"
    assert terms["roofline_fraction"] == pytest.approx(1.0)
    terms2 = rf.roofline_terms({"flops": 1.0, "bytes accessed": 819e9},
                               {"total": 0.0})
    assert terms2["bottleneck"] == "t_memory"


def test_production_mesh_shapes():
    """Mesh functions build the assigned shapes (needs 512 devices → check
    construction logic only via devices reshape math on the small host)."""
    from repro.launch.mesh import make_cp_production_mesh
    if jax.device_count() >= 512:
        m = make_cp_production_mesh(multi_pod=True)
        assert m.devices.shape == (32, 16)
        assert m.axis_names == ("group", "sub")
    else:
        with pytest.raises(Exception):
            make_cp_production_mesh(multi_pod=False)  # 256 > available
