"""The reduction from the profiler's trace to device time: on a hand-made
event list, and on a small trace recorded on a TPU v5e (a served window
over a resident float store, cut to a few thousand events)."""

import json
import os

import _bench_path  # noqa: F401
import pytest

from mbench import layerlib, tracing

SAMPLE = os.path.join(os.path.dirname(__file__), "data", "tpu_trace_sample.json")
D0, D1 = "/device:TPU:0", "/device:TPU:1"


def _ev(plane, line, name, start_us, dur_us):
    return (plane, line, name, start_us * 1e3, dur_us * 1e3)


def test_busy_is_the_union_of_op_intervals():
    events = [
        _ev(D0, "XLA Ops", "fusion.1", 0, 10),
        _ev(D0, "XLA Ops", "fusion.2", 5, 10),       # overlaps the first
        _ev(D0, "XLA Ops", "cp_count_multi", 30, 20),
        _ev(D0, "XLA Modules", "jit__device_cp_bounds(12)", 0, 15),
        _ev(D0, "XLA Modules", "jit__device_multi_counts(7)", 30, 20),
        _ev("/host:CPU", "python", "PjitFunction(_device_multi_counts)",
            16, 12),
    ]
    r = tracing.reduce(events)
    assert r["busy_s"] == pytest.approx(35e-6)
    assert r["modules"]["_device_cp_bounds"] == pytest.approx(15e-6)
    assert tracing.step_seconds(r, ("_device_multi_counts",)) == \
        pytest.approx(20e-6)
    assert r["ops"]["fusion.2"] == pytest.approx(10e-6)
    assert r["gaps"] == [["PjitFunction(_device_multi_counts)",
                          pytest.approx(15e-6)]]


def test_busy_is_averaged_over_chips():
    events = [_ev(D0, "XLA Ops", "a", 0, 10), _ev(D1, "XLA Ops", "a", 0, 30)]
    assert tracing.reduce(events)["busy_s"] == pytest.approx(20e-6)


def test_no_device_op_reads_nothing():
    r = tracing.reduce([_ev("/host:CPU", "python", "x", 0, 5)])
    assert r["busy_s"] is None
    ctx = type("C", (), {"trace": r, "window_s": 1.0,
                         "hbm_bytes_per_s": 819e9})()
    assert layerlib.idle_share(ctx) is None
    assert layerlib.hbm_share(ctx, 1e6, ("_device_cp_bounds",)) is None


def test_module_names_drop_jit_and_id():
    assert tracing.module_name("jit__device_fused_verify(3)") == \
        "_device_fused_verify"
    assert tracing.module_name("jit_pair_counts") == "pair_counts"


def test_recorded_tpu_trace():
    with open(SAMPLE) as f:
        rec = json.load(f)
    r = tracing.reduce([tuple(e) for e in rec["events"]])
    want = rec["expected"]
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    for name, secs in want["modules"].items():
        assert r["modules"][name] == pytest.approx(secs, rel=1e-9)
    assert 0.0 < r["busy_s"] <= want["span_s"]
