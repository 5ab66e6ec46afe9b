"""The trace-to-metrics reduction, on hand-made planes and on a small
trace in the profiler's own file format (an XSpace written as text and
serialized the way the profiler writes ``.xplane.pb``), read back through
``jax.profiler.ProfileData`` as a recorded trace is."""
from types import SimpleNamespace as NS

import pytest

import chipbench_tiny as T  # noqa: F401  (puts chipbench on sys.path)
import trace_reduce as R

XSPACE = """
planes { id: 1 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 100000000 duration_ps: 1000000000 } }
  event_metadata { key: 1 value { id: 1 name: "chipbench.window" } } }
planes { id: 2 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 2000000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 50000000 duration_ps: 350000000 }
    events { metadata_id: 2 offset_ps: 600000000 duration_ps: 100000000 }
    events { metadata_id: 1 offset_ps: 1000000000 duration_ps: 400000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "custom-call.2" } }
  event_metadata { key: 3 value { id: 3 name: "jit_train_step" } } }
"""


def _ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur,
              stats=list(stats.items()))


def _planes(ops, window=(100, 1100)):
    host = NS(name="/host:CPU", lines=[NS(name="main", events=[
        _ev(R.WINDOW, window[0], window[1] - window[0])])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[_ev("jit_step", 0, 5000)]),
        NS(name=R.OPS_LINE, events=[_ev(*o[:3], **o[3]) for o in ops])])
    return [host, dev]


def test_busy_gaps_and_kernels_inside_the_window():
    ops = [("fusion.1", 0, 200, {}),               # clipped to 100..200
           ("custom-call.7", 300, 100, {"long_name": "sketch_update_k"}),
           ("fusion.1", 350, 60, {}),              # overlaps: union
           ("fusion.2", 900, 400, {})]             # clipped to 900..1100
    r = R.reduce_planes(_planes(ops))
    assert r.window_s == pytest.approx(1000e-9)
    assert r.busy_s == pytest.approx((100 + 110 + 200) * 1e-9)
    assert [x for g in r.gaps for x in g] == pytest.approx(
        [100e-9, 100e-9, 310e-9, 490e-9])
    assert r.kernel_seconds("sketch_update") == pytest.approx((100e-9, 1))
    assert r.kernel_seconds("fused_precond") == (0.0, 0)
    r.label_gaps([("train_step", 0.0, 1e-6), ("data_wait", 3e-7, 8e-7)])
    assert r.gap_labels == ["train_step", "data_wait"]
    b = r.breakdown()
    assert b["device_ops"][0][0] == "fusion.2"
    assert b["idle_gaps"][0] == ["data_wait", pytest.approx(490e-9)]


def test_breakdown_keeps_ten_of_each():
    ops = [(f"op.{i}", 100 + 20 * i, 10, {}) for i in range(30)]
    b = R.reduce_planes(_planes(ops)).breakdown()
    assert len(b["device_ops"]) == len(b["idle_gaps"]) == 10


def test_missing_window_or_device_is_an_error():
    with pytest.raises(ValueError, match="annotation"):
        R.reduce_planes([NS(name="/device:TPU:0", lines=[])])
    with pytest.raises(ValueError, match="plane"):
        R.reduce_planes(_planes([])[:1])


def test_profiler_file_format(tmp_path):
    from jax.profiler import ProfileData
    run_dir = tmp_path / "plugins" / "profile" / "2026_01_01_00_00_00"
    run_dir.mkdir(parents=True)
    (run_dir / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(XSPACE))
    r = R.reduce_dir(tmp_path)
    # window 100..1100 us: ops clipped to 100..400, 600..700, 1000..1100
    assert r.n_devices == 1
    assert r.window_s == pytest.approx(1e-3)
    assert r.busy_s == pytest.approx(500e-6)
    assert r.ops["fusion.1"].count == 2
    assert r.ops["fusion.1"].seconds == pytest.approx(400e-6)
    assert [x for g in r.gaps for x in g] == pytest.approx(
        [300e-6, 200e-6, 600e-6, 300e-6])
