"""Spans of the served path (DESIGN.md §10): a request through the async
tier, traced from its HTTP read to the device fetch, and the tracer's
window totals.

Key invariants:
  * one request's spans share the tier's request id ``rid``: the tier's
    ``tier.*`` fragments and the service's ``service.execute`` tree
    (``service.item`` → parse / plan.compile / bounds, the scheduler's
    drive, ``service.finish``), with ``device.*`` spans under them on the
    device backend and none on the host backend;
  * the tree's structure, ``device.*`` left out, is the same on both;
  * with the tracer off a served request starts no span, and the device
    backend's transfer counters still count;
  * totals: self time is a span's duration minus its children's, and
    ``Tracer.record`` intervals feed the totals and the current tree.
"""

import json
import time
import urllib.request

import pytest

from repro.core import queries
from repro.obs import GLOBAL_TRACER, Tracer
from repro.obs import trace as trace_mod
from repro.service import MaskSearchService
from repro.service.asyncserver import serve_in_thread
from repro.service.server import _synthetic_store

TOPK_SQL = ("SELECT mask_id FROM MasksDatabaseView ORDER BY "
            "CP(mask, full_img, (0.2, 0.6)) DESC LIMIT 5;")
AGG_SQL = queries.SCENARIO3_IOU
TIER_SPANS = {"tier.read", "tier.queue", "tier.resume", "tier.respond"}


@pytest.fixture(scope="module")
def db():
    return _synthetic_store(40, 32)


class _Served:
    """A traced service behind the async tier that keeps every span it
    finishes (the tier's fragments never enter the trace ring)."""

    def __init__(self, db, backend, trace=True):
        store, rois = db
        self.service = MaskSearchService(store, provided_rois=rois,
                                         backend=backend, trace=trace,
                                         verify_batch=8)
        self.finished = []
        finish = self.service.tracer._finish

        def keep(sp, *, root):
            self.finished.append(sp)
            finish(sp, root=root)

        self.service.tracer._finish = keep
        self.handle = serve_in_thread(self.service)

    def query(self, sql) -> dict:
        req = urllib.request.Request(
            self.handle.base_url + "/v1/query",
            data=json.dumps({"sql": sql}).encode(), method="POST")
        with urllib.request.urlopen(req, timeout=120) as resp:
            return json.loads(resp.read())

    def close(self):
        self.handle.stop()
        self.service.close()

    def request(self, rid) -> tuple:
        """→ (the tier's spans of request ``rid`` by name, the service's
        ``service.execute`` tree that served it).  The client can hold the
        reply before the tier leaves its ``tier.respond`` span: wait for
        it."""
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline and not any(
                sp.name == "tier.respond" and sp.attrs.get("rid") == rid
                for sp in list(self.finished)):
            time.sleep(0.01)
        tier = {}
        for sp in self.finished:
            if sp.name in TIER_SPANS and sp.attrs.get("rid") == rid:
                assert sp.name not in tier, sp.name     # once each
                tier[sp.name] = sp
        (top,) = [sp for sp in self.finished if sp.name == "service.execute"
                  and sp.attrs.get("rids") == [rid]]
        return tier, top

    def last_rid(self):
        return max(sp.attrs["rid"] for sp in self.finished
                   if sp.name == "tier.read")


def _names(sp) -> set:
    return {s.name for s in sp.walk()}


@pytest.mark.parametrize("backend", ["host", "device"])
@pytest.mark.parametrize("case", ["executed", "cache_hit", "mask_agg"])
def test_served_request_span_tree(db, backend, case):
    served = _Served(db, backend)
    try:
        sql = AGG_SQL if case == "mask_agg" else TOPK_SQL
        served.query(sql)
        if case == "cache_hit":
            assert served.query(sql)["cache_hit"] is True
        rid = served.last_rid()
        tier, top = served.request(rid)
    finally:
        served.close()
    assert set(tier) == TIER_SPANS
    read, queue, resume, respond = (tier[n] for n in (
        "tier.read", "tier.queue", "tier.resume", "tier.respond"))
    # the request's life in order: read, queue, the service, resume, reply
    assert read.t0 <= queue.t0 <= top.t0
    assert top.t0 + top.dur_s <= resume.t0 + resume.dur_s <= respond.t0
    kids = [c.name for c in top.children]
    item = top.children[0]
    assert item.name == "service.item" and item.attrs["rid"] == rid
    assert item.children[0].name == "parse"
    if case == "cache_hit":
        assert kids == ["service.item"]
        assert item.attrs["cache_hit"] is True
        assert _names(top) == {"service.execute", "service.item", "parse"}
        return
    assert kids == ["service.item", "scheduler.drive", "service.finish"]
    assert item.attrs["cache_hit"] is False
    assert top.children[2].attrs["rid"] == rid
    assert {"plan.compile", "bounds"} <= _names(item)
    drive = top.children[1]
    assert drive.children and all(r.name == "scheduler.round"
                                  for r in drive.children)
    verify = "verify.round" if case == "mask_agg" else "scheduler.fused_pass"
    assert verify in _names(drive)
    device = {n for n in _names(top) if n.startswith("device.")}
    if backend == "host":
        assert device == set()
    else:
        assert device == {"device.call", "device.wait", "device.fetch"}
        fetch = next(s for s in drive.walk() if s.name == "device.fetch")
        assert fetch.attrs["step"].startswith("_device_")


def test_served_structure_is_backend_invariant(db):
    shapes = {}
    for backend in ("host", "device"):
        served = _Served(db, backend)
        try:
            served.query(TOPK_SQL)
            _, top = served.request(served.last_rid())
        finally:
            served.close()
        shapes[backend] = top.structure()
        if backend == "device":
            assert any(s.name.startswith("device.") for s in top.walk())
    assert shapes["device"] == shapes["host"]


def test_untraced_request_starts_no_span_and_counts_transfers(db):
    served = _Served(db, "device", trace=False)
    try:
        stats = served.service.backend.stats
        before = (stats.device_calls, stats.h2d_bytes, stats.d2h_bytes,
                  stats.fetches)
        g0 = GLOBAL_TRACER.spans_started
        served.query(AGG_SQL)
        served.query("SELECT mask_id FROM MasksDatabaseView WHERE "
                     "CP(mask, full_img, (0.3, 0.7)) > 100;")
    finally:
        served.close()
    assert served.service.tracer.spans_started == 0
    assert GLOBAL_TRACER.spans_started == g0
    assert served.finished == []
    after = (stats.device_calls, stats.h2d_bytes, stats.d2h_bytes,
             stats.fetches)
    assert all(a > b for a, b in zip(after, before)), (before, after)


def test_served_metrics_carry_spans_compiles_phases_and_transfers(db):
    served = _Served(db, "device")
    try:
        served.query(TOPK_SQL)
        served.query(TOPK_SQL)
        text = served.service.metrics_text()
        phases = served.service.stats()["phases"]
    finally:
        served.close()
    for name in ("masksearch_spans_total", "masksearch_span_seconds_total",
                 "masksearch_span_self_seconds_total"):
        assert f'{name}{{span="service.item"}}' in text, name
    assert 'masksearch_spans_total{span="tier.queue"} 2' in text
    assert "masksearch_compiles_total{" in text
    assert "masksearch_compile_seconds_total{" in text
    assert "masksearch_backend_h2d_bytes" in text
    assert "masksearch_backend_device_calls" in text
    assert "masksearch_kernel_dispatch_seconds" not in text
    assert "masksearch_jit_compiles_total" not in text
    # execute_many feeds the phase histograms: one executed query and one
    # result-cache hit
    assert phases["parse"]["count"] >= 2 and phases["plan"]["count"] >= 2
    assert phases["bounds"]["count"] >= 1 and phases["verify"]["count"] >= 1


# -- totals arithmetic ---------------------------------------------------------


class _Clock:
    def __init__(self, *ticks):
        self.ticks = list(ticks)

    def __call__(self):
        return self.ticks.pop(0)


def test_self_time_and_totals_on_nested_spans(monkeypatch):
    # a: 0..10 holding b (1..3), a recorded interval (3.5..4.0) and c
    # (4..4.5); self time of a = 10 - 2 - 0.5 - 0.5
    monkeypatch.setattr(trace_mod, "_now",
                        _Clock(0.0, 1.0, 3.0, 4.0, 4.5, 10.0))
    t = Tracer(enabled=True)
    with t.activate():
        with t.span("a") as a:
            with trace_mod.span("b"):
                pass
            t.record("q", 3.5, 4.0, rid=9)
            with trace_mod.span("c"):
                pass
            t.record("early", -1.0, -0.5)    # before a began: not a child's time
    assert [c.name for c in a.children] == ["b", "q", "c", "early"]
    assert a.dur_s == 10.0 and a.self_s == pytest.approx(7.0)
    tot = t.totals()
    assert tot["a"] == {"count": 1, "seconds": 10.0,
                        "self_seconds": pytest.approx(7.0)}
    assert tot["b"] == {"count": 1, "seconds": 2.0, "self_seconds": 2.0}
    assert tot["q"] == {"count": 1, "seconds": 0.5, "self_seconds": 0.5}
    assert tot["c"]["seconds"] == pytest.approx(0.5)
    assert a.children[1].attrs == {"rid": 9}
    assert t.trace_ids() == [a.attrs["query_id"]]


def test_record_outside_a_span_feeds_only_the_totals():
    t = Tracer(enabled=True)
    t.record("tier.queue", 1.0, 1.25)
    t.record("tier.queue", 2.0, 2.5)
    t.record("tier.queue", 3.0, 2.0)          # clock skew reads as 0
    assert t.totals() == {"tier.queue": {"count": 3, "seconds": 0.75,
                                         "self_seconds": 0.75}}
    assert t.trace_ids() == []                # no tree, nothing retained
    off = Tracer(enabled=False)
    off.record("tier.queue", 1.0, 2.0)
    assert off.totals() == {} and off.spans_started == 0


def test_kept_and_unkept_roots():
    t = Tracer(enabled=True)
    with t.span("tier.read", keep=False):
        pass
    with t.span("service.execute"):
        pass
    assert [t.get_trace(q).name for q in t.trace_ids()] == ["service.execute"]
    assert set(t.totals()) == {"tier.read", "service.execute"}


def test_structure_leaves_out_device_spans():
    t = Tracer(enabled=True)
    with t.activate():
        with t.span("verify.round") as root:
            root.set(batch=4)
            with trace_mod.span("device.call") as sp:
                sp.set(step="_device_multi_counts")
            with trace_mod.span("device.fetch"):
                pass
    assert root.structure() == ("verify.round", (("batch", 4),), ())
    assert [c.name for c in root.children] == ["device.call", "device.fetch"]
