"""The reduction from a profiler trace to metrics, and the roofline count."""
import json
import os

import jax
import pytest

import peaks
import roofline
import tracing

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

# Two TPU planes and a host thread; times in ns from the line's start
# (text-proto offsets are in ps). Device 0 runs an ec_local op [0, 2000)
# and an unscoped op [3000, 4000); device 1 one ec_local op [500, 4500)
# and one that starts before the window ends and is clipped to it.
_SYNTHETIC = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000
             stats { metadata_id: 1 str_value: "jit(update)/ec_local/mul" } }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "dot.2" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } } }
planes { id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 500000 duration_ps: 4000000
             stats { metadata_id: 1 str_value: "jit(update)/ec_local/mul" } }
    events { metadata_id: 2 offset_ps: 4800000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "dot.2" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } } }
planes { id: 3 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 2500000 }
    events { metadata_id: 3 offset_ps: 2500000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 3500000 duration_ps: 1500000 } }
  event_metadata { key: 1 value { id: 1 name: "window" } }
  event_metadata { key: 2 value { id: 2 name: "sweep" } }
  event_metadata { key: 3 value { id: 3 name: "fit_read" } }
  event_metadata { key: 4 value { id: 4 name: "window_wait" } } }
"""


def test_reduction_of_a_synthetic_two_chip_trace():
    s = tracing.reduce(jax.profiler.ProfileData.from_text_proto(_SYNTHETIC))
    assert s.devices == 2
    assert s.window_s == pytest.approx(5e-6)
    # device 0 busy 3000 ns, device 1 busy 4000 + 200 (clipped) ns
    assert s.busy_s == pytest.approx((3000 + 4200) / 2 * 1e-9)
    assert s.scope_s["ec_local"] == pytest.approx((2000 + 4000) / 2 * 1e-9)
    assert s.scope_s["other"] == pytest.approx((1000 + 200) / 2 * 1e-9)
    assert s.scope_s["merge"] == 0.0 and s.scope_s["factor_exchange"] == 0.0
    assert s.scope_s["unattributed"] == 0.0
    # device 0 idles [2000, 3000) in fit_read and [4000, 5000) in
    # window_wait; device 1 idles [0, 500) in sweep and [4500, 4800)
    gaps = {(name, round(sec * 1e9)) for name, sec in s.gaps}
    assert gaps == {("fit_read", 1000), ("window_wait", 1000),
                    ("sweep", 500), ("window_wait", 300)}
    b = s.breakdown()
    assert b["device_ops"][0] == ["fusion.1 [ec_local]", pytest.approx(3e-6)]
    assert [g[0] for g in b["idle_gaps"]][:2] == ["fit_read", "window_wait"]


def test_reduction_of_the_recorded_cpu_trace():
    """``data/cpu_tiny.xplane.pb``: a CPU run of the program (an
    amazon-shaped 240 x 90 x 90 tensor from ``gen.generate(1, ..., 4000,
    1.1)``, rank 8, preset ``paper``) tracing two sweeps inside the
    benchmark's annotations; ``cpu_tiny.op_names.json`` is the
    ``(module, op) -> op_name`` map of its compiled mode updates."""
    with open(os.path.join(DATA, "cpu_tiny.op_names.json")) as f:
        names = {tuple(k.split("|", 1)): v for k, v in json.load(f).items()}
    pd = jax.profiler.ProfileData.from_file(
        os.path.join(DATA, "cpu_tiny.xplane.pb"))
    s = tracing.reduce(pd, names)
    assert s.devices == 1
    assert 0 < s.busy_s <= s.window_s
    total = sum(s.scope_s.values())
    assert s.scope_s["ec_local"] > 0 and s.scope_s["other"] > 0
    assert total == pytest.approx(sum(s.op_s.values()))
    assert total >= s.busy_s * (1 - 1e-9)
    assert all(name in tracing.HOST_NAMES + ("host",) for name, _ in s.gaps)
    assert s.busy_s + sum(sec for _, sec in s.gaps) == \
        pytest.approx(s.window_s)
    b = s.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    # without the op-name map the CPU trace names no scope
    assert tracing.reduce(pd).scope_s["ec_local"] == 0.0


def test_op_scopes_from_hlo_text():
    text = (
        'HloModule jit_update, entry_computation_layout={}\n'
        '  %fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, '
        'calls=%fc, metadata={op_name="jit(update)/jit(main)/ec_local/mul" '
        'source_file="x.py"}\n'
        '  ROOT %dot.1 = f32[2]{0} dot(%a, %b), '
        'metadata={op_name="jit(update)/jit(main)/dot_general"}\n')
    names = tracing.op_scopes_from_hlo([text])
    assert names[("jit_update", "fusion.3", "f32[8]{0}")] == \
        names[("jit_update", "fusion.3")] == \
        "jit(update)/jit(main)/ec_local/mul"
    assert names[("jit_update", "dot.1")] == \
        "jit(update)/jit(main)/dot_general"
    assert len(names) == 4


def test_op_names_whose_scopes_clash_between_programs_are_dropped():
    """Each mode's ``jit_update`` shares instruction names; where the
    result types differ, they still tell the programs apart. A key whose
    scopes clash is marked, not left to count as ``other``."""
    a = ('HloModule jit_update, x\n'
         '  %fusion.1 = f32[8]{0} fusion(%p), metadata={op_name="u/ec_local/m"}\n'
         '  %fusion.2 = f32[8]{0} fusion(%p), metadata={op_name="u/ec_local/m"}\n')
    b = ('HloModule jit_update, x\n'
         '  %fusion.1 = f32[8]{0} fusion(%p), metadata={op_name="u/dot"}\n'
         '  %fusion.2 = f32[8]{0} fusion(%p), metadata={op_name="u/ec_local/a"}\n')
    c = a.replace("f32[8]", "f32[9]")
    names = tracing.op_scopes_from_hlo([a, b, c])
    assert names[("jit_update", "fusion.1")] == tracing.CLASH
    assert names[("jit_update", "fusion.1", "f32[8]{0}")] == tracing.CLASH
    # a result type of its own keeps the instruction's scope
    assert names[("jit_update", "fusion.1", "f32[9]{0}")] == "u/ec_local/m"
    assert "/ec_local/" in names[("jit_update", "fusion.2")]


@pytest.mark.parametrize("clashing_op,fails", [("dot.2", True),
                                               ("fusion.1", True),
                                               ("nothing.9", False)])
def test_ops_whose_scopes_clash_never_count_as_the_solve(clashing_op, fails):
    """The synthetic trace's ops as the HLO of two clashing programs would
    name them: they go to ``unattributed``, and where they hold more than
    a negligible share of the device time the reduction fails."""
    pd = jax.profiler.ProfileData.from_text_proto(
        _SYNTHETIC.replace('str_value: "jit(update)/ec_local/mul"',
                           'str_value: ""'))
    names = {("", "fusion.1"): "jit(update)/ec_local/mul",
             ("", "dot.2"): "jit(update)/dot_general",
             ("", clashing_op): tracing.CLASH}
    if fails:
        with pytest.raises(ValueError, match=clashing_op):
            tracing.reduce(pd, names)
    else:
        s = tracing.reduce(pd, names)
        assert s.scope_s["unattributed"] == 0.0
        assert s.scope_s["other"] == pytest.approx((1000 + 200) / 2 * 1e-9)


@pytest.mark.parametrize("nnz,nmodes,rank,rows,nbytes,flops", [
    (1000, 3, 32, 10, 1000 * (4 + 12 + 256) + 10 * 128, 1000 * 32 * 3),
    (500, 5, 32, 7, 500 * (4 + 20 + 512) + 7 * 128, 500 * 32 * 5),
    (44_034_229, 3, 32, 192_848,
     44_034_229 * 272 + 192_848 * 128, 44_034_229 * 96),
])
def test_ec_cost_on_known_shapes(nnz, nmodes, rank, rows, nbytes, flops):
    assert roofline.ec_cost(nnz, nmodes, rank, rows) == (nbytes, flops)


def test_least_time_is_bytes_bound_on_v5e():
    pk = peaks.peaks_for("TPU v5 lite")
    nbytes, flops = roofline.ec_cost(44_034_229, 3, 32, 192_848)
    t, bound = roofline.least_time(nbytes, flops, pk)
    assert bound == "bytes"
    assert t == pytest.approx(nbytes / 819e9)
    sweep = roofline.sweep_least_time(44_034_229, (192848, 70971, 72207),
                                      32, pk, solve=False)
    assert sweep == pytest.approx(sum(
        roofline.ec_cost(44_034_229, 3, 32, r)[0]
        for r in (192848, 70971, 72207)) / 819e9)
    assert roofline.solve_cost(100, 32) == (3 * 100 * 32 * 4,
                                            4 * 100 * 32 * 32)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")
