"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (harness contract).

Tables reproduced (demo paper §4 + the full paper's workload experiment):
  * bench_filter_query   — §4 Scenario 1: Filter query, MaskSearch vs
                           full-scan.  Derived: measured speedup, modeled-EBS
                           speedup (paper's disk provisioning), %masks loaded.
  * bench_topk_query     — §4 Scenarios 1+2: Top-K (ASC normalized ROI
                           count; DESC dispersion).
  * bench_agg_iou        — §4 Scenario 3: IoU aggregation (GROUP BY image).
  * bench_multi_query    — full-paper multi-query workload: shared bounds
                           pass + shared verification loads.
  * bench_chi_build      — index-construction throughput (ingest path).
  * bench_cp_kernels     — verification-kernel microbench.

DB defaults are container-sized (5 000 masks @128²); pass --full for the
paper's 22 275 masks.  Modeled-EBS numbers use the paper's own provisioning
(125 MiB/s, 3000 IOPS) so the headline ~100× reproduces independent of this
machine's page cache.
"""

from __future__ import annotations

import argparse
import os
import shutil
import tempfile
import time

import numpy as np

import jax
import jax.numpy as jnp


def _setup(n_masks: int, size: int, tmpdir: str):
    from repro.core import CHIConfig, MaskStore
    from repro.core.store import MASK_META_DTYPE
    from repro.data.masks import object_boxes, saliency_masks

    rois = object_boxes(n_masks, size, size)
    masks, attacked = saliency_masks(n_masks, size, size, seed=7,
                                     attacked_fraction=0.2, boxes=rois,
                                     in_box_fraction=0.9)
    meta = np.zeros(n_masks, MASK_META_DTYPE)
    meta["mask_id"] = np.arange(n_masks)
    meta["image_id"] = np.arange(n_masks) // 2     # 2 mask types per image
    meta["mask_type"] = np.arange(n_masks) % 2 + 1
    # Thresholds on 0.05 multiples + one at 1.0: the workload's value
    # ranges (0.2, 0.6), (0.8, 1.0) align exactly, so the value dimension of
    # every bound is tight (the paper picks Θ to match the workload, §2).
    # Masks live in [0,1), so the 1.0 edge counts every pixel.
    thetas = tuple(round(0.05 * i, 2) for i in range(1, 20)) + (1.0,)
    cfg = CHIConfig(grid=16, num_bins=21, height=size, width=size,
                    thresholds=thetas)
    store = MaskStore.create_disk(os.path.join(tmpdir, "db"), masks, meta, cfg)
    return store, rois, masks, attacked


def _row(name: str, seconds: float, derived: str = ""):
    print(f"{name},{seconds * 1e6:.1f},{derived}", flush=True)


def _timed(fn, repeats: int = 5):
    fn()                                   # warmup (jit compiles)
    times = []
    out = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn()
        times.append(time.perf_counter() - t0)
    return float(np.median(times)), out


def bench_filter_query(store, rois, args):
    from repro.core import CP, RoiArea, filter_query
    from repro.core.exprs import BinOp
    expr = BinOp("/", CP("provided", 0.8, 1.0), RoiArea("provided"))
    thr = 0.05

    def run_indexed():
        store.io.reset()
        return filter_query(store, expr, "<", thr, provided_rois=rois)

    def run_scan():
        store.io.reset()
        return filter_query(store, expr, "<", thr, provided_rois=rois,
                            use_index=False)

    t_idx, (ids_i, st_i) = _timed(run_indexed, args.repeats)
    io_idx = store.io.modeled_ebs_time_s
    t_scan, (ids_s, st_s) = _timed(run_scan, args.repeats)
    io_scan = store.io.modeled_ebs_time_s
    assert set(ids_i) == set(ids_s), "index answer != full scan"
    _row("filter_masksearch", t_idx,
         f"loaded={st_i.load_fraction:.3%};modeled_ebs_s={io_idx:.2f}")
    _row("filter_fullscan", t_scan,
         f"loaded=100%;modeled_ebs_s={io_scan:.2f}")
    _row("filter_speedup", 0.0,
         f"measured={t_scan / max(t_idx, 1e-9):.1f}x;"
         f"modeled_ebs={io_scan / max(io_idx, 1e-9):.1f}x")


def bench_topk_query(store, rois, args):
    from repro.core import CP, RoiArea, topk_query
    from repro.core.exprs import BinOp
    expr1 = BinOp("/", CP("provided", 0.8, 1.0), RoiArea("provided"))
    expr2 = CP(None, 0.2, 0.6)

    for name, expr, desc in (("topk_s1_asc", expr1, False),
                             ("topk_s2_desc", expr2, True)):
        def run_idx(expr=expr, desc=desc):
            store.io.reset()
            return topk_query(store, expr, 25, desc=desc, provided_rois=rois)

        def run_scan(expr=expr, desc=desc):
            store.io.reset()
            return topk_query(store, expr, 25, desc=desc, provided_rois=rois,
                              use_index=False)

        t_idx, (ids_i, sc_i, st_i) = _timed(run_idx, args.repeats)
        io_idx = store.io.modeled_ebs_time_s
        t_scan, (ids_s, sc_s, _) = _timed(run_scan, args.repeats)
        io_scan = store.io.modeled_ebs_time_s
        assert np.allclose(np.sort(sc_i), np.sort(sc_s)), f"{name} mismatch"
        _row(f"{name}_masksearch", t_idx,
             f"loaded={st_i.load_fraction:.3%};modeled_ebs_s={io_idx:.2f}")
        _row(f"{name}_fullscan", t_scan, f"modeled_ebs_s={io_scan:.2f}")
        _row(f"{name}_speedup", 0.0,
             f"measured={t_scan / max(t_idx, 1e-9):.1f}x;"
             f"modeled_ebs={io_scan / max(io_idx, 1e-9):.1f}x")


def bench_agg_iou(store, rois, args):
    from repro.core import queries

    def run_idx():
        store.io.reset()
        return queries.run(queries.SCENARIO3_IOU, store)

    def run_scan():
        store.io.reset()
        return queries.run(queries.SCENARIO3_IOU, store, use_index=False)

    t_idx, ((ids_i, sc_i), st_i) = _timed(run_idx, max(args.repeats // 2, 1))
    io_idx = store.io.modeled_ebs_time_s
    t_scan, ((ids_s, sc_s), _) = _timed(run_scan, 1)
    io_scan = store.io.modeled_ebs_time_s
    assert np.allclose(np.sort(sc_i), np.sort(sc_s), atol=1e-9)
    _row("agg_iou_masksearch", t_idx,
         f"loaded={st_i.load_fraction:.3%};modeled_ebs_s={io_idx:.2f}")
    _row("agg_iou_fullscan", t_scan, f"modeled_ebs_s={io_scan:.2f}")
    _row("agg_iou_speedup", 0.0,
         f"measured={t_scan / max(t_idx, 1e-9):.1f}x;"
         f"modeled_ebs={io_scan / max(io_idx, 1e-9):.1f}x")


def bench_multi_query(store, rois, args):
    """Workload of 10 related queries (5 filter + 5 top-k) — one bounds
    pass per query over the in-memory CHI + shared verification loads."""
    from repro.core.multiquery import run_workload
    sqls = []
    for t in (0.02, 0.04, 0.06, 0.08, 0.10):
        sqls.append("SELECT mask_id FROM MasksDatabaseView WHERE "
                    f"CP(mask, roi, (0.8, 1.0)) / AREA(roi) < {t};")
    for lv in (0.15, 0.2, 0.25, 0.3, 0.35):
        sqls.append("SELECT mask_id FROM MasksDatabaseView ORDER BY "
                    f"CP(mask, full_img, ({lv}, {lv + 0.4})) DESC LIMIT 25;")

    def run_shared():
        store.io.reset()
        return run_workload(store, sqls, provided_rois=rois, share_loads=True)

    def run_unshared():
        store.io.reset()
        return run_workload(store, sqls, provided_rois=rois,
                            share_loads=False)

    def run_scan():
        store.io.reset()
        return run_workload(store, sqls, provided_rois=rois, use_index=False,
                            share_loads=False)

    t_sh, (_, ws_sh) = _timed(run_shared, max(args.repeats // 2, 1))
    io_sh = store.io.modeled_ebs_time_s
    t_un, (_, ws_un) = _timed(run_unshared, max(args.repeats // 2, 1))
    t_scan, (_, ws_scan) = _timed(run_scan, 1)
    io_scan = store.io.modeled_ebs_time_s
    _row("workload10_masksearch_shared", t_sh,
         f"files={ws_sh.files_loaded};modeled_ebs_s={io_sh:.2f}")
    _row("workload10_masksearch_unshared", t_un,
         f"files={ws_un.files_loaded}")
    _row("workload10_fullscan", t_scan,
         f"files={ws_scan.files_loaded};modeled_ebs_s={io_scan:.2f}")
    _row("workload10_speedup", 0.0,
         f"measured={t_scan / max(t_sh, 1e-9):.1f}x;"
         f"share_gain={t_un / max(t_sh, 1e-9):.2f}x;"
         f"modeled_ebs={io_scan / max(io_sh, 1e-9):.1f}x")


def bench_chi_build(store, masks, args):
    from repro.core.chi import build_chi
    from repro.kernels.ops import chi_cell_hist
    cfg = store.cfg
    sub = jnp.asarray(masks[:256])
    edges = jnp.asarray(cfg.interior_edges)

    build_jnp = lambda: jax.block_until_ready(build_chi(sub, cfg))
    t_jnp, _ = _timed(build_jnp, 3)
    kern = lambda: jax.block_until_ready(
        chi_cell_hist(sub, edges, cfg.grid, use_pallas=True, interpret=True))
    t_kern, _ = _timed(kern, 1)
    mb = sub.nbytes / 1e6
    _row("chi_build_jnp_256", t_jnp, f"MB_per_s={mb / t_jnp:.0f}")
    _row("chi_build_pallas_interp_256", t_kern,
         "correctness-path;TPU perf is the BlockSpec design")
    _row("chi_index_overhead", 0.0,
         f"index_bytes_frac="
         f"{cfg.index_bytes(len(store)) / cfg.mask_bytes(len(store)):.3%}")


def bench_cp_kernels(store, masks, args):
    from repro.kernels import ops
    sub = jnp.asarray(masks[:1024])
    rois = jnp.tile(jnp.asarray([[8, 8, store.cfg.height - 8,
                                  store.cfg.width - 8]], jnp.int32),
                    (sub.shape[0], 1))
    f = lambda: jax.block_until_ready(ops.cp_count(sub, rois, 0.25, 0.75))
    t, _ = _timed(f, args.repeats)
    _row("cp_count_1024", t, f"us_per_mask={t * 1e6 / sub.shape[0]:.2f}")
    qrois = jnp.broadcast_to(rois[None], (8,) + rois.shape)
    lvs = jnp.linspace(0.1, 0.8, 8)
    uvs = lvs + 0.15
    g = lambda: jax.block_until_ready(
        ops.cp_count_multi(sub, qrois, lvs, uvs, use_pallas=False))
    t8, _ = _timed(g, args.repeats)
    _row("cp_count_multi_q8_1024", t8,
         f"per_query_amortized={t8 / 8 / max(t, 1e-9):.2f}x_single")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-masks", type=int, default=5000)
    ap.add_argument("--size", type=int, default=128)
    ap.add_argument("--full", action="store_true",
                    help="paper-scale DB: 22275 masks")
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--keep-db", default=None)
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.full:
        args.n_masks = 22275

    print("name,us_per_call,derived")
    tmpdir = args.keep_db or tempfile.mkdtemp(prefix="masksearch_bench_")
    try:
        t0 = time.perf_counter()
        store, rois, masks, _ = _setup(args.n_masks, args.size, tmpdir)
        _row("db_ingest_total", time.perf_counter() - t0,
             f"n={args.n_masks};size={args.size}")
        bench_filter_query(store, rois, args)
        bench_topk_query(store, rois, args)
        bench_agg_iou(store, rois, args)
        bench_multi_query(store, rois, args)
        bench_chi_build(store, masks, args)
        bench_cp_kernels(store, masks, args)
    finally:
        if not args.keep_db:
            shutil.rmtree(tmpdir, ignore_errors=True)


if __name__ == "__main__":
    main()
