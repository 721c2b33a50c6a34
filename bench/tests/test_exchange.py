"""The factor exchange's byte count, its interconnect peaks, and its device
time in a trace."""
import jax
import pytest

import exchange
import ici_peaks
import tracing


@pytest.mark.parametrize("shape,rank,chips,nbytes", [
    # (10 + 6 + 4) rows of 2 float32, three quarters received per chip
    ((10, 6, 4), 2, 4, 120.0),
    ((10, 6, 4), 2, 1, 0.0),
    ((7, 5), 4, 2, 96.0),
    # amazon-4chip: 336,026 true rows of rank 32 over 4 chips
    ((192848, 70971, 72207), 32, 4, 32_258_496.0),
])
def test_exchange_bytes_on_known_shapes(shape, rank, chips, nbytes):
    assert exchange.exchange_bytes(shape, rank, chips) == nbytes


def test_exchange_least_time_at_the_v5e_ici_peak():
    ici = ici_peaks.ici_peaks_for("TPU v5 lite")
    assert ici["ici_bytes_per_s"] == 200e9      # 1,600 Gbps
    assert exchange.exchange_least_time((10, 6, 4), 2, 4, ici) == \
        pytest.approx(120 / 200e9)


def test_unknown_device_kind_has_no_ici_peak():
    with pytest.raises(ValueError, match="no published interconnect"):
        ici_peaks.ici_peaks_for("TPU v99")


# One TPU plane and a host thread; times in ns from the line's start. The
# ring's loop op [0, 3000) holds its body's collective-permute [500, 2500);
# an ec_local op follows at [3000, 4000).
_LOOP = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 3000000
             stats { metadata_id: 1
                     str_value: "jit(update)/factor_exchange/while" } }
    events { metadata_id: 2 offset_ps: 500000 duration_ps: 2000000
             stats { metadata_id: 1
                     str_value: "jit(update)/factor_exchange/ppermute" } }
    events { metadata_id: 3 offset_ps: 3000000 duration_ps: 1000000
             stats { metadata_id: 1 str_value: "jit(update)/ec_local/mul" } }
  }
  event_metadata { key: 1 value { id: 1 name: "while.7" } }
  event_metadata { key: 2 value { id: 2 name: "collective-permute-done" } }
  event_metadata { key: 3 value { id: 3 name: "fusion.1" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 } }
  event_metadata { key: 1 value { id: 1 name: "window" } } }
"""


def test_exchange_seconds_count_a_loop_and_its_body_once():
    s = tracing.reduce(jax.profiler.ProfileData.from_text_proto(_LOOP))
    # the scope total holds the loop and its body: the interval twice
    assert s.scope_s["factor_exchange"] == pytest.approx(5000e-9)
    assert exchange.exchange_seconds(s) == pytest.approx(2000e-9)


@pytest.mark.parametrize("name", ["exchange_ms", "exchange_roofline"])
def test_exchange_metrics_read_nothing_without_an_exchange(name):
    import types
    from cell import metric_reader
    read = metric_reader(name)
    one_chip = tracing.Summary(window_s=1.0, busy_s=1.0, devices=1,
                               scope_s={"ec_local": 0.5}, op_s={
                                   "jit_update/fusion.1 [ec_local]": 0.5},
                               gaps=[])
    for trace in (None, one_chip):
        ctx = types.SimpleNamespace(trace=trace, sweeps=2, peaks={},
                                    shape=(10, 6, 4), rank=2,
                                    cell=types.SimpleNamespace(chips=1))
        assert read(ctx) is None
