"""Async serving tier: admission control, fair queueing, shedding,
streaming, and cross-tenant fused verification (DESIGN.md §14)."""

import json
import socket
import threading
import urllib.error
import urllib.request

import pytest

import promcheck
from repro.service import MaskSearchService
from repro.service.admission import (AdmissionController, FairQueue,
                                     TokenBucket)
from repro.service.asyncserver import serve_in_thread
from repro.service.errors import OverloadedError, RateLimitedError
from repro.service.server import _synthetic_store

TOPK_SQL = ("SELECT mask_id FROM MasksDatabaseView ORDER BY "
            "CP(mask, full_img, (0.2, 0.6)) DESC LIMIT {n};")
FILTER_SQL = ("SELECT mask_id FROM MasksDatabaseView WHERE "
              "CP(mask, full_img, (0.3, 0.7)) > {t};")


# -- admission primitives ---------------------------------------------------

def test_token_bucket_grant_and_refill():
    b = TokenBucket(rate=1.0, burst=2.0)
    assert b.try_take(0.0) == 0.0
    assert b.try_take(0.0) == 0.0
    wait = b.try_take(0.0)                 # empty: full token outstanding
    assert wait == pytest.approx(1.0)
    assert b.try_take(0.5) > 0.0           # half refilled: still short
    assert b.try_take(1.6) == 0.0          # refilled past one token
    b2 = TokenBucket(rate=10.0, burst=1.0)
    b2.try_take(0.0)
    assert b2.try_take(100.0) == 0.0       # refill clamps at burst


def test_fair_queue_depth_bound_and_force():
    q = FairQueue(depth=2)
    assert q.push("a", 1) and q.push("a", 2)
    assert not q.push("a", 3)              # at depth: shed
    assert q.push("a", 3, force=True)      # continuation work is exempt
    assert q.depth_of("a") == 3 and len(q) == 3


def test_fair_queue_drr_is_weighted_fair():
    q = FairQueue(depth=100, weights={"heavy": 2.0})
    for i in range(30):
        q.push("heavy", f"h{i}")
        q.push("light", f"l{i}")
    batch = q.pop_batch(18)
    heavy = sum(1 for t, _ in batch if t == "heavy")
    light = len(batch) - heavy
    # weight 2:1 → heavy drains ~2x light, and light is never starved
    assert heavy == pytest.approx(2 * light, abs=2)
    assert light >= 5
    # draining the rest empties both queues exactly
    rest = q.pop_batch(10_000)
    assert len(rest) == 60 - len(batch) and len(q) == 0


def test_fair_queue_single_tenant_fifo_order():
    q = FairQueue(depth=10)
    for i in range(5):
        q.push("t", i)
    assert [item for _, item in q.pop_batch(5)] == [0, 1, 2, 3, 4]


def test_admission_controller_sheds_with_retry_after():
    clk = [0.0]
    ac = AdmissionController(rate=1.0, burst=2.0, depth=1,
                             clock=lambda: clk[0])
    ac.admit("t", "job1")
    with pytest.raises(OverloadedError) as over:   # queue (depth 1) full
        ac.admit("t", "job2")
    assert over.value.retry_after > 0
    assert ac.queue.pop_batch(10) == [("t", "job1")]
    ac.admit("t", "job2")                  # burst token 2 of 2
    assert ac.queue.pop_batch(10) == [("t", "job2")]
    with pytest.raises(RateLimitedError) as rate:  # bucket empty
        ac.admit("t", "job3")
    assert rate.value.retry_after == pytest.approx(1.0)
    clk[0] = 1.0                           # one token refilled
    ac.admit("t", "job3")
    assert ac.stats.admitted == 3
    assert ac.stats.shed_queue_full == 1
    assert ac.stats.shed_rate_limited == 1


# -- the HTTP tier ----------------------------------------------------------

@pytest.fixture(scope="module")
def tier():
    store, rois = _synthetic_store(60, 32)
    service = MaskSearchService(store, provided_rois=rois)
    handle = serve_in_thread(service, tenant_rate=10_000, tenant_burst=10_000)
    yield service, handle
    handle.stop()
    service.close()


def _raw(base, method, path, body=None, tenant=None):
    data = json.dumps(body).encode() if body is not None else None
    headers = {"Content-Type": "application/json"} if data else {}
    if tenant:
        headers["X-Tenant"] = tenant
    req = urllib.request.Request(base + path, data=data, method=method,
                                 headers=headers)
    try:
        with urllib.request.urlopen(req, timeout=30) as resp:
            return resp.status, json.loads(resp.read()), resp.headers
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), e.headers


def test_tier_serves_both_namespaces(tier):
    service, handle = tier
    base = handle.base_url
    code, out, _ = _raw(base, "POST", "/v1/query",
                        {"sql": TOPK_SQL.format(n=5)})
    assert code == 200 and [it for it in out["ids"]]
    code, legacy, _ = _raw(base, "POST", "/query",
                           {"sql": TOPK_SQL.format(n=5)})
    assert code == 200 and legacy["ids"] == out["ids"]
    code, out, _ = _raw(base, "GET", "/v1/healthz")
    assert (code, out) == (200, {"ok": True})
    code, out, _ = _raw(base, "GET", "/v1/stats")
    assert code == 200 and "epoch" in out
    code, err, _ = _raw(base, "POST", "/v1/nope", {})
    assert code == 404 and err["error"]["code"] == "not_found"
    code, err, _ = _raw(base, "POST", "/query", {})
    assert code == 400 and isinstance(err["error"], str)   # legacy flat


def test_quota_shed_is_clean_429_with_retry_after():
    store, rois = _synthetic_store(40, 32)
    service = MaskSearchService(store, provided_rois=rois)
    handle = serve_in_thread(service, tenant_rate=0.001, tenant_burst=1)
    try:
        base = handle.base_url
        sql = TOPK_SQL.format(n=3)
        code, _, _ = _raw(base, "POST", "/v1/query", {"sql": sql},
                          tenant="greedy")
        assert code == 200                 # burst token
        code, err, headers = _raw(base, "POST", "/v1/query", {"sql": sql},
                                  tenant="greedy")
        assert code == 429
        assert err["error"]["code"] == "rate_limited"
        assert err["error"]["retry_after"] > 0
        assert int(headers["Retry-After"]) >= 1
        # quota is per tenant: another tenant still gets through
        code, _, _ = _raw(base, "POST", "/v1/query", {"sql": sql},
                          tenant="patient")
        assert code == 200
        # mutations are charged through the same buckets
        code, err, _ = _raw(base, "POST", "/v1/delete",
                            {"mask_ids": [0]}, tenant="greedy")
        assert code == 429 and err["error"]["code"] == "rate_limited"
        assert handle.tier.admission.stats.shed_rate_limited >= 2
    finally:
        handle.stop()
        service.close()


def test_connection_limit_sheds_overloaded():
    store, rois = _synthetic_store(20, 32)
    service = MaskSearchService(store, provided_rois=rois)
    handle = serve_in_thread(service, max_connections=1)
    try:
        host, port = handle.tier.host, handle.tier.port
        squatter = socket.create_connection((host, port), timeout=10)
        try:
            deadline = 50
            while handle.tier.stats.connections_open < 1 and deadline:
                threading.Event().wait(0.01)
                deadline -= 1
            code, err, headers = _raw(handle.base_url, "GET", "/v1/healthz")
            assert code == 429
            assert err["error"]["code"] == "overloaded"
            assert "Retry-After" in headers
            assert handle.tier.stats.shed_connections >= 1
        finally:
            squatter.close()
    finally:
        handle.stop()
        service.close()


def test_streaming_session_matches_oneshot(tier):
    service, handle = tier
    from repro.service import ServiceClient
    c = ServiceClient(handle.base_url)
    oneshot = c.query(TOPK_SQL.format(n=12))
    pages = list(c.stream_query(TOPK_SQL.format(n=12), page_size=5))
    assert len(pages) >= 2
    assert pages[-1]["exhausted"] and pages[-1]["cursor"] is None
    streamed = [it["id"] for p in pages for it in p["items"]]
    # the stream pages through the full ranking; its prefix is the one-shot
    assert streamed[:len(oneshot["ids"])] == oneshot["ids"]
    assert handle.tier.stats.stream_pages >= len(pages)
    # streams drop their session on completion
    assert len(service.sessions) == 0


def test_served_metrics_exposition_is_valid(tier):
    """The tier's ``/v1/metrics`` text, after it has answered a query, is
    a well-formed exposition that carries the query counter."""
    service, handle = tier
    code, _, _ = _raw(handle.base_url, "POST", "/v1/query",
                      {"sql": TOPK_SQL.format(n=4)})
    assert code == 200
    with urllib.request.urlopen(handle.base_url + "/v1/metrics",
                                timeout=30) as resp:
        assert resp.status == 200
        text = resp.read().decode()
    samples, typed = promcheck.parse(
        text, required=("masksearch_queries_total",
                        "masksearch_query_phase_seconds"))
    assert typed["masksearch_queries_total"] == "counter"
    assert sum(v for k, v in samples.items()
               if k.startswith("masksearch_queries_total")) >= 1


def test_cross_tenant_fusion_in_one_batch(tier):
    """The tentpole acceptance: queries from different tenants in one
    admitted batch merge into the same fused verification passes."""
    service, handle = tier
    before = service.scheduler.stats.cross_tenant_passes
    items = [{"op": "query", "sql": TOPK_SQL.format(n=3 + i),
              "tenant": f"tenant-{i % 3}"} for i in range(6)]
    results = service.execute_many(items)
    assert all(status == "ok" for status, _ in results)
    stats = service.scheduler.stats
    assert stats.cross_tenant_passes > before
    assert stats.cross_tenant_jobs >= 2
    assert stats.fused_tenant_width >= 3
    text = service.metrics_text()
    assert "masksearch_scheduler_cross_tenant_passes" in text
    assert "repro_async_tier_batches" in text
    assert "repro_admission_admitted" in text


def test_cross_tenant_fusion_over_http(tier):
    """Concurrent volleys from distinct tenants through the wire reach the
    batch dispatcher and fuse; retried volleys absorb scheduling jitter."""
    service, handle = tier
    base = handle.base_url
    before = service.scheduler.stats.cross_tenant_passes
    for attempt in range(8):
        barrier = threading.Barrier(6)
        codes: list = []

        def fire(i):
            barrier.wait()
            code, _, _ = _raw(base, "POST", "/v1/query",
                              {"sql": FILTER_SQL.format(t=120 + i)},
                              tenant=f"t{i}")
            codes.append(code)
        threads = [threading.Thread(target=fire, args=(i,))
                   for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert codes and all(c == 200 for c in codes)
        if service.scheduler.stats.cross_tenant_passes > before:
            break
    assert service.scheduler.stats.cross_tenant_passes > before, \
        "no cross-tenant fused pass in 8 concurrent volleys"
    assert handle.tier.stats.batches > 0


def test_execute_many_isolates_per_item_faults(tier):
    service, _ = tier
    results = service.execute_many([
        {"op": "query", "sql": TOPK_SQL.format(n=3)},
        {"op": "query", "sql": "SELEC nope"},
        {"op": "page", "session_id": "never-created"},
    ])
    assert results[0][0] == "ok"
    assert results[1][0] == "error" and isinstance(results[1][1], Exception)
    assert results[2][0] == "error"
    assert isinstance(results[2][1], KeyError)    # NotFoundError subclass


def test_tier_sessions_and_mutations(tier):
    service, handle = tier
    base = handle.base_url
    code, out, _ = _raw(base, "POST", "/v1/query",
                        {"sql": TOPK_SQL.format(n=6), "session": True,
                         "page_size": 2})
    assert code == 200 and out["cursor"].startswith("c1.")
    code, page, _ = _raw(base, "POST", "/v1/page", {"cursor": out["cursor"]})
    assert code == 200 and page["offset"] == 2
    size = service.store.cfg.height
    code, ing, _ = _raw(base, "POST", "/v1/ingest",
                        {"masks": [[[0.5] * size] * size],
                         "mask_ids": [8200], "image_ids": [8200]})
    assert code == 200 and ing["applied"]["appended"] == 1
    # append-only ingest keeps the pinned snapshot serveable: paging
    # continues (200) or — if the engine cannot serve it — is a clean
    # 409 stale_epoch envelope, never a 500
    code, out, _ = _raw(base, "POST", "/v1/page",
                        {"cursor": page["cursor"]})
    assert code in (200, 409)
    if code == 409:
        assert out["error"]["code"] == "stale_epoch"
    code, dele, _ = _raw(base, "POST", "/v1/delete", {"mask_ids": [8200]})
    assert code == 200 and dele["applied"]["deleted"] == 1
