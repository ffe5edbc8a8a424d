"""Observability suite (DESIGN.md §10): span tracing, EXPLAIN ANALYZE, the
metrics registry, and the service's /metrics + /trace surfaces.

Key invariants:
  * the span-tree *structure* (names, nesting, candidate/verified counts) is
    identical across host/device/mesh for CP rankings, dual-mask rankings,
    and aggregations — instrumentation lives in the backend-agnostic
    drivers, so this holds by construction and is asserted here;
  * with tracing disabled no Span is ever allocated
    (``Tracer.spans_started`` stays 0 — a counter assertion, not a timing);
  * EXPLAIN ANALYZE returns per-operator candidates / decided-by-bounds /
    verified / bytes / timings on every backend and for every plan kind;
  * the Prometheus exposition is well-formed and the Chrome trace export
    round-trips through json.
"""

import json

import numpy as np
import pytest

import promcheck

from repro.core import CHIConfig, MaskStore, queries
from repro.core.plan import run_plan
from repro.core.store import MASK_META_DTYPE
from repro.data.masks import object_boxes, saliency_masks
from repro.obs import GLOBAL_TRACER, Tracer, chrome_trace
from repro.obs import trace as trace_mod
from repro.obs.explain import explain_analyze, explain_plan
from repro.obs.metrics import MetricsRegistry, REGISTRY

B, H, W = 24, 32, 32
BACKENDS = ("host", "device", "mesh")

CP_SQL = ("SELECT mask_id FROM V "
          "ORDER BY CP(mask, roi, (0.8, 1.0)) / AREA(roi) ASC LIMIT 10;")
PAIR_SQL = ("SELECT image_id FROM V "
            "ORDER BY IOU(saliency, attention, 0.6, 0.6) ASC LIMIT 6;")
AGG_SQL = "SELECT SCALAR_AGG(AVG, CP(mask, full_img, (0.2, 0.6))) FROM V;"
FILTERED_SQL = ("SELECT mask_id FROM V "
                "WHERE CP(mask, full_img, (0.2, 0.6)) > 50 "
                "ORDER BY CP(mask, full_img, (0.2, 0.6)) DESC LIMIT 8;")


@pytest.fixture(scope="module")
def db():
    rois = object_boxes(B, H, W, seed=5)
    masks, _ = saliency_masks(B, H, W, seed=4, attacked_fraction=0.25,
                              boxes=rois)
    meta = np.zeros(B, MASK_META_DTYPE)
    meta["mask_id"] = np.arange(B)
    meta["image_id"] = np.arange(B) // 2
    meta["mask_type"] = np.arange(B) % 2 + 1   # pairs: (1, 2) per image
    cfg = CHIConfig(grid=4, num_bins=8, height=H, width=W)
    return MaskStore.create_memory(masks, meta, cfg), rois


# -- tracer mechanics --------------------------------------------------------


def test_disabled_tracer_allocates_no_spans(db):
    store, rois = db
    before = GLOBAL_TRACER.spans_started
    queries.run(CP_SQL, store, provided_rois=rois)
    assert GLOBAL_TRACER.spans_started == before
    assert trace_mod.span("anything") is trace_mod.NOOP_SPAN


def test_span_tree_nesting_and_ring_buffer():
    t = Tracer(enabled=True)
    with t.activate():
        with t.query_span(label="q") as root:
            with trace_mod.span("bounds") as sp:
                sp.set(candidates=7)
            with trace_mod.span("verify.round") as sp:
                sp.set(batch=3)
    assert [c.name for c in root.children] == ["bounds", "verify.round"]
    qid = root.attrs["query_id"]
    assert t.get_trace(qid) is root
    assert t.last_trace() is root
    assert t.spans_started == 3
    # ring-buffer bound
    t2 = Tracer(enabled=True, max_traces=2)
    with t2.activate():
        for _ in range(4):
            with t2.query_span():
                pass
    assert len(t2.trace_ids()) == 2


def test_trace_exports_round_trip():
    t = Tracer(enabled=True)
    with t.activate():
        with t.query_span(label="export") as root:
            with trace_mod.span("bounds") as sp:
                sp.set(candidates=np.int64(5), chi_bytes=np.int32(640))
    d = json.loads(json.dumps(root.to_dict()))
    assert d["name"] == "query" and d["children"][0]["name"] == "bounds"
    ch = json.loads(json.dumps(chrome_trace(root)))
    assert {e["name"] for e in ch["traceEvents"]} == {"query", "bounds"}
    assert all(e["ph"] == "X" for e in ch["traceEvents"])


# -- backend-invariant span structure ---------------------------------------


def _trace_structure(store, sql, rois, backend):
    plan = queries.parse(sql).plan
    t = Tracer(enabled=True)
    rep = explain_analyze(store, plan, provided_rois=rois, backend=backend,
                          verify_batch=5, tracer=t)
    return t.last_trace().structure(), rep


@pytest.mark.parametrize("sql", [CP_SQL, PAIR_SQL, AGG_SQL, FILTERED_SQL],
                         ids=["cp", "pair", "agg", "filtered_topk"])
def test_span_structure_identical_across_backends(db, sql):
    store, rois = db
    shapes = {}
    reports = {}
    for backend in BACKENDS:
        shapes[backend], reports[backend] = \
            _trace_structure(store, sql, rois, backend)
    assert shapes["device"] == shapes["host"]
    assert shapes["mesh"] == shapes["host"]
    # ...and the annotated per-operator counts agree too
    s0 = reports["host"]["tree"]["stats"]
    for backend in ("device", "mesh"):
        s = reports[backend]["tree"]["stats"]
        for key in ("candidates", "decided_by_bounds", "verified", "rounds"):
            assert s[key] == s0[key], (sql, backend, key)


# -- EXPLAIN [ANALYZE] -------------------------------------------------------


def test_explain_grammar_prefix():
    q = queries.parse("EXPLAIN ANALYZE " + CP_SQL)
    assert q.explain == "analyze" and q.kind == "topk"
    assert queries.parse("EXPLAIN " + CP_SQL).explain == "plan"
    assert queries.parse(CP_SQL).explain is None


def test_explain_plan_is_not_executed(db):
    store, _ = db
    io0 = store.io.bytes_read
    rep = queries.parse("EXPLAIN " + CP_SQL).run(store)
    assert rep["analyzed"] is False
    assert store.io.bytes_read == io0
    ops = [c["op"] for c in rep["tree"]["children"]]
    assert ops == ["CHIBounds", "Source"]


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("sql", [CP_SQL, PAIR_SQL, FILTERED_SQL],
                         ids=["cp", "pair", "filtered_topk"])
def test_explain_analyze_operator_stats(db, sql, backend):
    store, rois = db
    plan = queries.parse(sql).plan
    rep = explain_analyze(store, plan, provided_rois=rois, backend=backend,
                          verify_batch=5)
    assert rep["analyzed"] is True and rep["backend"] == backend
    root = rep["tree"]
    stats = root["stats"]
    for key in ("candidates", "decided_by_bounds", "verified", "rounds",
                "bytes_loaded", "bytes_saved", "bound_time_s",
                "verify_time_s"):
        assert key in stats, key
    assert stats["candidates"] > 0
    # pure rankings decide every candidate by bounds or verification;
    # filtered rankings may retire predicate-rejected rows without either
    decided = stats["decided_by_bounds"] + stats["verified"]
    if "WHERE" in sql:
        assert 0 < decided <= stats["candidates"]
    else:
        assert decided == stats["candidates"]
    ops = {c["op"]: c for c in root["children"]}
    assert "Verify" in ops and "CHIBounds" in ops and "Source" in ops
    assert len(ops["Verify"]["rounds"]) == stats["rounds"]
    assert sum(r["bytes_loaded"] for r in ops["Verify"]["rounds"]) \
        == stats["bytes_loaded"]
    for row in ops["CHIBounds"]["exprs"]:
        assert row["candidates"] == stats["candidates"]
        assert row["chi_bytes"] > 0
    if "WHERE" in sql:
        leaves = ops["Filter"]["leaves"]
        assert leaves and all(
            leaf["accepted_by_bounds"] + leaf["rejected_by_bounds"]
            + leaf["undecided"] == stats["candidates"] for leaf in leaves)
    # the whole report is JSON (the HTTP layer serves it verbatim)
    json.loads(json.dumps(rep))
    # ...and matches the plain execution result
    result, _ = run_plan(store, plan, provided_rois=rois, verify_batch=5,
                         backend=backend)
    assert rep["n_results"] == len(result[0])


def test_explain_analyze_scalar_agg(db):
    store, rois = db
    plan = queries.parse(AGG_SQL).plan
    rep = explain_analyze(store, plan, provided_rois=rois)
    (value, _) = run_plan(store, plan, provided_rois=rois)
    assert rep["value"] == pytest.approx(value)
    assert rep["tree"]["op"] == "Aggregate"


def test_explain_analyze_restores_tracer_state(db):
    store, rois = db
    t = Tracer(enabled=False)
    explain_analyze(store, queries.parse(CP_SQL).plan, provided_rois=rois,
                    tracer=t)
    assert t.enabled is False          # forced on only for the query
    assert t.last_trace() is not None  # ...but the trace was retained


def test_explain_plan_render_smoke():
    rep = explain_plan(queries.parse(FILTERED_SQL).plan)
    assert "TopK" in rep["text"] and "Filter" in rep["text"]


# -- metrics registry --------------------------------------------------------


def test_registry_counter_gauge_histogram():
    reg = MetricsRegistry()
    c = reg.counter("t_total", "help", ("kind",))
    c.labels(kind="a").inc()
    c.labels(kind="a").inc(2)
    g = reg.gauge("t_gauge", "help")
    g.set(4.5)
    h = reg.histogram("t_seconds", "help", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    samples, typed = promcheck.parse(reg.prometheus_text())
    assert samples['t_total{kind="a"}'] == 3
    assert samples["t_gauge"] == 4.5
    assert samples['t_seconds_bucket{le="0.1"}'] == 1
    assert samples['t_seconds_bucket{le="1"}'] == 2
    assert samples['t_seconds_bucket{le="+Inf"}'] == 3
    assert samples["t_seconds_count"] == 3
    assert samples["t_seconds_sum"] == pytest.approx(5.55)
    assert typed == {"t_total": "counter", "t_gauge": "gauge",
                     "t_seconds": "histogram"}
    summ = h.labels().summary()
    assert summ["count"] == 3 and 0.0 < summ["p50"] <= 1.0
    # idempotent re-registration; type mismatch rejected
    assert reg.counter("t_total") is c
    with pytest.raises(ValueError):
        reg.gauge("t_total")


def test_registry_collectors_reflect_dataclasses():
    import dataclasses as dc

    from repro.obs.metrics import dataclass_sampler

    @dc.dataclass
    class S:
        reads: int = 3
        frac: float = 0.5
        name: str = "x"       # non-numeric: skipped

    reg = MetricsRegistry()
    reg.register_collector(dataclass_sampler("t_s", "counter", "h",
                                             lambda: S()))
    samples, _ = promcheck.parse(reg.prometheus_text())
    assert samples == {"t_s_reads": 3.0, "t_s_frac": 0.5}


def test_kernel_launch_metrics_populated(db):
    store, rois = db
    queries.run(CP_SQL, store, provided_rois=rois)
    samples, _ = promcheck.parse(REGISTRY.prometheus_text())
    launches = {k: v for k, v in samples.items()
                if k.startswith("masksearch_kernel_launches_total")}
    assert any(v > 0 for v in launches.values()), launches
    assert any(k.startswith("masksearch_backend_resolutions_total")
               for k in samples)
