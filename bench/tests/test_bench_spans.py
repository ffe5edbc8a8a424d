"""The program's spans as the benchmark reads them: the idle attribution
and the split on hand-made inputs, the spans in a CPU profiler trace read
through ``mbench.tracing.collect``, and a tiny traced window of
``bench/trace_spans.py`` (no chip, so no device time: the split and the
coverage only)."""

import _bench_path
import pytest

from mbench import cell, deploy, spans, tracing

D0 = "/device:TPU:0"
HOST = "/host:CPU"
TOPK_SQL = ("SELECT mask_id FROM MasksDatabaseView ORDER BY "
            "CP(mask, full_img, (0.2, 0.6)) DESC LIMIT 5;")


def _ev(plane, line, name, start_us, dur_us):
    return (plane, line, name, start_us * 1e3, dur_us * 1e3)


def test_idle_goes_to_the_innermost_open_span():
    events = [
        _ev(D0, "XLA Ops", "fusion", 0, 10),
        _ev(D0, "XLA Ops", "fusion", 50, 10),
        _ev(HOST, "worker", "service.execute", 0, 90),
        _ev(HOST, "worker", "device.wait", 20, 35),     # 20..55
        _ev(HOST, "loop", "tier.respond", 92, 4),
        _ev(HOST, "worker", "PjitFunction(x)", 60, 5),  # not a program span
        _ev(HOST, "loop", "other", 99, 1),              # the window's end
    ]
    idle = spans.idle_by_span(events)
    # idle: 10..50 and 60..100
    assert idle["service.execute"] == pytest.approx((10 + 30) * 1e-6)
    assert idle["device.wait"] == pytest.approx(30e-6)
    assert idle["tier.respond"] == pytest.approx(4e-6)
    assert idle[spans.NONE] == pytest.approx(6e-6)       # 90..92, 96..100
    assert sum(idle.values()) == pytest.approx(80e-6)
    assert spans.line(idle).startswith(
        "idle_by_span service.execute=0.000040 device.wait=0.000030")
    assert spans.idle_by_span([_ev(HOST, "t", "tier.read", 0, 5)]) == {}


def test_split_reads_the_totals_and_the_counters():
    def t(seconds, own=None):
        return {"count": 1, "seconds": seconds,
                "self_seconds": seconds if own is None else own}

    before = {"tier.read": t(1.0)}
    after = {"tier.read": t(1.5), "tier.respond": t(0.5),
             "tier.queue": t(0.2), "tier.resume": t(0.2),
             "service.execute": t(9.0, 0.3), "service.item": t(4.0, 0.1),
             "parse": t(0.1), "bounds": t(0.4, 0.2),
             "scheduler.drive": t(5.0, 0.1), "scheduler.round": t(4.9, 1.0),
             "device.call": t(1.0), "device.wait": t(2.0),
             "device.fetch": t(0.5)}
    totals = spans.delta(before, after)
    assert totals["tier.read"] == {"count": 0, "seconds": 0.5,
                                   "self_seconds": 0.5}
    out = spans.split(totals, {"h2d_bytes": 3072, "d2h_bytes": 1024},
                      requests=10, queries=4)
    assert out["tier_ms_per_request"] == pytest.approx(100.0)
    assert out["queue_wait_ms_per_request"] == pytest.approx(40.0)
    assert out["service_ms_per_request"] == pytest.approx(50.0)
    assert out["bounds_host_ms_per_query"] == pytest.approx(50.0)
    assert out["verify_host_ms_per_query"] == pytest.approx(275.0)
    assert out["dispatch_ms_per_query"] == pytest.approx(250.0)
    assert out["device_wait_ms_per_query"] == pytest.approx(625.0)
    assert out["transfer_kib_per_query"] == pytest.approx(1.0)
    assert spans.split(totals, {}, requests=0, queries=0) == {}
    assert spans.coverage(totals, 0.0) is None


def test_program_spans_are_host_events_of_a_profile(tmp_path):
    import jax
    from repro.service import MaskSearchService
    from repro.service.server import _synthetic_store

    store, rois = _synthetic_store(24, 32)
    svc = MaskSearchService(store, provided_rois=rois, backend="device",
                            trace=True, verify_batch=8)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        (status, _), = svc.execute_many([{"op": "query", "sql": TOPK_SQL,
                                          "rid": 1}])
    finally:
        jax.profiler.stop_trace()
        svc.close()
    assert status == "ok"
    names = {e[2] for e in tracing.collect(str(tmp_path))
             if e[1] != tracing.OPS_LINE}
    assert {"service.execute", "service.item", "parse", "plan.compile",
            "bounds", "scheduler.drive", "scheduler.round",
            "scheduler.fused_pass", "service.finish", "device.call",
            "device.wait", "device.fetch"} <= names & spans.PROGRAM_SPANS


def test_tiny_traced_window_gives_every_figure():
    import trace_spans

    tiny = {"n_masks": 96, "height": 48, "width": 48,
            "tier_settings": {"tenant_rate": 1e6, "tenant_burst": 1e6,
                              "queue_depth": 256, "batch_max": 32}}
    paths = cell.Paths(_bench_path.ROOT)
    _, _, cfg, mix = cell.load_cell(paths, "packed448-gui-serial", tiny,
                                    {"per_client": 16})
    cell.import_program(paths)
    params = deploy.params_for(cfg, 2**33 + 9)
    store = deploy.build_store(cfg, params, {})
    cell.warm(cfg, store, params["boxes"], mix,
              cell.window_requests(mix, 2**33 + 9, 2.0))
    row = trace_spans.one_window(paths, cfg, mix, store, params["boxes"],
                                 2**33 + 9, 2.0, "profile")
    assert row["failed"] == 0 and row["requests"] > 0 and row["queries"] > 0
    for name in ("tier_ms_per_request", "queue_wait_ms_per_request",
                 "service_ms_per_request", "verify_host_ms_per_query",
                 "dispatch_ms_per_query", "device_wait_ms_per_query",
                 "transfer_kib_per_query", "bounds_host_ms_per_query"):
        assert row[name] > 0, name
    assert 0 < row["coverage"] <= 1.0
    assert row["spans"]["tier.read"]["count"] == row["requests"]
    assert row["busy_s"] is None and row["idle_by_span"] == {}  # no chip
