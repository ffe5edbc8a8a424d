"""Serving-path benchmark — the interactive service's cache and fusion wins.

Prints ``name,us_per_call,derived`` CSV rows (harness contract) and, with
``--json PATH``, writes a machine-readable record (``BENCH_serve.json``).

Measured:
  * serve_cold / serve_warm   — one-shot query latency, cold vs result-cache
                                hit (warm must load zero bytes).
  * serve_refine              — threshold sweep: bounds-cache reuse vs
                                re-planning each query cold.
  * serve_pagination          — 4 session pages vs 4 growing one-shot runs.
  * serve_fused / serve_serial — Q concurrent top-k queries through the
                                fused scheduler vs serial unshared runs
                                (bytes shared is the headline).
  * serve_filtered_topk / serve_filtered_naive — a predicate-tree WHERE
                                composed with ORDER BY … LIMIT: three-valued
                                bounds pruning vs the naive filter-then-rank
                                full scan (bytes avoided is the headline).

    PYTHONPATH=src python benchmarks/bench_serve.py --json BENCH_serve.json
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import tempfile
import time

import numpy as np


def _setup(n_masks: int, size: int, tmpdir: str):
    from repro.core import CHIConfig, MaskStore
    from repro.core.store import MASK_META_DTYPE
    from repro.data.masks import object_boxes, saliency_masks

    rois = object_boxes(n_masks, size, size, seed=1)
    masks, _ = saliency_masks(n_masks, size, size, seed=7,
                              attacked_fraction=0.2, boxes=rois,
                              in_box_fraction=0.9)
    meta = np.zeros(n_masks, MASK_META_DTYPE)
    meta["mask_id"] = np.arange(n_masks)
    meta["image_id"] = np.arange(n_masks) // 2
    meta["mask_type"] = np.arange(n_masks) % 2 + 1
    cfg = CHIConfig(grid=16, num_bins=16, height=size, width=size)
    MaskStore.create_disk(os.path.join(tmpdir, "db"), masks, meta, cfg)
    return os.path.join(tmpdir, "db"), rois


def _row(name: str, seconds: float, derived: str = ""):
    print(f"{name},{seconds * 1e6:.1f},{derived}", flush=True)


def _fresh_service(root, rois=None, **kw):
    from repro.core import MaskStore
    from repro.service import MaskSearchService
    return MaskSearchService(MaskStore.open_disk(root), provided_rois=rois,
                             **kw)


TOPK = ("SELECT mask_id FROM MasksDatabaseView ORDER BY "
        "CP(mask, full_img, (0.2, 0.6)) DESC LIMIT 25;")


def _phase_seconds(svc):
    """Per-phase wall-time totals from the service's phase histogram."""
    return {phase: summ["sum_s"]
            for phase, summ in svc.stats()["phases"].items()}


def bench_cold_warm(root, record):
    svc = _fresh_service(root)
    t0 = time.perf_counter()
    svc.query(TOPK)
    t_cold = time.perf_counter() - t0
    cold_bytes = svc.store.io.bytes_read
    cold_phases = _phase_seconds(svc)

    warm_times = []
    for _ in range(5):
        t0 = time.perf_counter()
        out = svc.query(TOPK)
        warm_times.append(time.perf_counter() - t0)
    t_warm = float(np.median(warm_times))
    warm_bytes = svc.store.io.bytes_read - cold_bytes
    assert out["cache_hit"] and warm_bytes == 0
    _row("serve_cold", t_cold, f"bytes={cold_bytes}")
    _row("serve_warm", t_warm, f"bytes={warm_bytes};"
         f"speedup={t_cold / max(t_warm, 1e-9):.0f}x")
    record["cold"] = {"latency_s": t_cold, "bytes_loaded": cold_bytes,
                      "phase_s": cold_phases}
    record["warm"] = {"latency_s": t_warm, "bytes_loaded": warm_bytes,
                      "speedup_vs_cold": t_cold / max(t_warm, 1e-9)}
    phases = ";".join(f"{k}={v * 1e3:.1f}ms"
                      for k, v in sorted(cold_phases.items()))
    _row("serve_cold_phases", sum(cold_phases.values()), phases)
    svc.close()


def bench_refine(root, rois, record):
    sweep = [0.10, 0.08, 0.06, 0.04, 0.02]
    sql = ("SELECT mask_id FROM MasksDatabaseView WHERE "
           "CP(mask, roi, (0.8, 1.0)) / AREA(roi) < {};")

    svc = _fresh_service(root, rois)
    t0 = time.perf_counter()
    for thr in sweep:
        svc.query(sql.format(thr))
    t_svc = time.perf_counter() - t0
    hits = svc.planner.bounds_cache.info.hits
    svc.close()

    # baseline: each refinement re-plans cold (fresh service per query)
    t0 = time.perf_counter()
    for thr in sweep:
        one = _fresh_service(root, rois)
        one.query(sql.format(thr))
        one.close()
    t_cold = time.perf_counter() - t0
    _row("serve_refine_sweep5", t_svc,
         f"bounds_hits={hits};vs_cold={t_cold / max(t_svc, 1e-9):.2f}x")
    record["refine"] = {"sweep": sweep, "latency_s": t_svc,
                        "bounds_cache_hits": hits,
                        "cold_latency_s": t_cold}


def bench_pagination(root, record):
    from repro.core import MaskStore, engine, queries
    svc = _fresh_service(root)
    t0 = time.perf_counter()
    page = svc.query(TOPK, session=True, page_size=25)
    for _ in range(3):
        page = svc.next_page(page["session"])
    t_sess = time.perf_counter() - t0
    sess_bytes = svc.store.io.bytes_read
    sess_verified = page["stats"]["n_verified"]
    sess_phases = _phase_seconds(svc)
    svc.close()

    store = MaskStore.open_disk(root)
    plan = queries.parse(TOPK)
    t0 = time.perf_counter()
    for k in (25, 50, 75, 100):
        engine.topk_query(store, plan.expr, k, desc=plan.desc)
    t_rerun = time.perf_counter() - t0
    rerun_bytes = store.io.bytes_read
    _row("serve_session_4pages", t_sess,
         f"bytes={sess_bytes};verified={sess_verified}")
    _row("serve_rerun_4pages", t_rerun,
         f"bytes={rerun_bytes};session_gain="
         f"{rerun_bytes / max(sess_bytes, 1):.2f}x_bytes")
    record["pagination"] = {
        "session": {"latency_s": t_sess, "bytes_loaded": sess_bytes,
                    "n_verified": sess_verified, "phase_s": sess_phases},
        "rerun": {"latency_s": t_rerun, "bytes_loaded": rerun_bytes},
    }


def bench_fused(root, record):
    from repro.core import MaskStore, queries
    sqls = ["SELECT mask_id FROM MasksDatabaseView ORDER BY "
            f"CP(mask, full_img, ({lv:.2f}, {lv + 0.4:.2f})) DESC LIMIT 25;"
            for lv in (0.15, 0.20, 0.25, 0.30, 0.35)]

    svc = _fresh_service(root, verify_batch=256)
    t0 = time.perf_counter()
    svc.submit_batch(sqls)
    t_fused = time.perf_counter() - t0
    fused_bytes = svc.store.io.bytes_read
    saved = svc.store.cache_stats.bytes_saved
    passes = svc.scheduler.stats.fused_passes
    svc.close()

    serial_store = MaskStore.open_disk(root)
    t0 = time.perf_counter()
    for s in sqls:
        queries.parse(s).run(serial_store)
    t_serial = time.perf_counter() - t0
    serial_bytes = serial_store.io.bytes_read
    assert fused_bytes < serial_bytes
    _row("serve_fused_q5", t_fused,
         f"bytes={fused_bytes};fused_passes={passes};bytes_saved={saved}")
    _row("serve_serial_q5", t_serial,
         f"bytes={serial_bytes};share_gain="
         f"{serial_bytes / max(fused_bytes, 1):.2f}x_bytes")
    record["fused"] = {
        "n_queries": len(sqls),
        "fused": {"latency_s": t_fused, "bytes_loaded": fused_bytes,
                  "fused_passes": passes, "cache_bytes_saved": saved},
        "serial_unshared": {"latency_s": t_serial,
                            "bytes_loaded": serial_bytes},
        "bytes_ratio": serial_bytes / max(fused_bytes, 1),
    }


FILTERED_TOPK = (
    "SELECT mask_id FROM MasksDatabaseView "
    "WHERE CP(mask, roi, (0.8, 1.0)) > 200 "
    "AND NOT CP(mask, full_img, (0.2, 0.6)) < 100 "
    "ORDER BY CP(mask, full_img, (0.2, 0.6)) DESC LIMIT 25;")


def bench_filtered_topk(root, rois, record):
    """Predicate tree + ranking through one run vs naive filter-then-rank."""
    from repro.core import MaskStore, queries
    from repro.core.plan import run_plan

    svc = _fresh_service(root, rois, verify_batch=256)
    t0 = time.perf_counter()
    out = svc.query(FILTERED_TOPK)
    t_idx = time.perf_counter() - t0
    idx_bytes = svc.store.io.bytes_read
    verified = out["stats"]["n_verified"]
    cands = out["stats"]["n_candidates"]
    n_hits = len(out["ids"])
    svc.close()

    store = MaskStore.open_disk(root)
    plan = queries.parse(FILTERED_TOPK).plan
    t0 = time.perf_counter()
    (ids0, _), _ = run_plan(store, plan, provided_rois=rois,
                            use_index=False)
    t_naive = time.perf_counter() - t0
    naive_bytes = store.io.bytes_read
    assert [int(x) for x in ids0] == out["ids"]      # pruning is exact

    _row("serve_filtered_topk", t_idx,
         f"bytes={idx_bytes};verified={verified}/{cands};hits={n_hits}")
    _row("serve_filtered_naive", t_naive,
         f"bytes={naive_bytes};prune_gain="
         f"{naive_bytes / max(idx_bytes, 1):.2f}x_bytes")
    record["filtered_topk"] = {
        "sql": FILTERED_TOPK,
        "indexed": {"latency_s": t_idx, "bytes_loaded": idx_bytes,
                    "n_verified": verified, "n_candidates": cands,
                    "n_hits": n_hits},
        "naive_filter_then_rank": {"latency_s": t_naive,
                                   "bytes_loaded": naive_bytes},
        "bytes_ratio": naive_bytes / max(idx_bytes, 1),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-masks", type=int, default=2000)
    ap.add_argument("--size", type=int, default=128)
    ap.add_argument("--json", default=None,
                    help="also write a JSON record to this path")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    import jax

    print("name,us_per_call,derived")
    tmpdir = tempfile.mkdtemp(prefix="masksearch_serve_")
    record = {"config": {"n_masks": args.n_masks, "size": args.size,
                         "jax_backend": jax.default_backend(),
                         "device_count": jax.device_count()}}
    try:
        t0 = time.perf_counter()
        root, rois = _setup(args.n_masks, args.size, tmpdir)
        _row("db_ingest_total", time.perf_counter() - t0,
             f"n={args.n_masks};size={args.size}")
        bench_cold_warm(root, record)
        bench_refine(root, rois, record)
        bench_pagination(root, record)
        bench_fused(root, record)
        bench_filtered_topk(root, rois, record)
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(record, f, indent=2)
        print(f"# wrote {args.json}", flush=True)


if __name__ == "__main__":
    main()
