"""Dual-mask (pair) query benchmark — discrepancy queries vs decode-all-pairs.

Prints ``name,us_per_call,derived`` CSV rows (harness contract) and, with
``--json PATH``, writes a machine-readable record (``BENCH_pair.json``).

Measured (disk tier, metered bytes):
  * pair_iou_topk / pair_iou_naive   — ``ORDER BY IOU(saliency, attention,
                                       t, t) ASC LIMIT k`` through the
                                       cell-decomposed pair bounds vs the
                                       naive baseline that decodes every
                                       (saliency, attention) pair.
                                       ``bytes_ratio`` is the headline —
                                       the acceptance bar is ≥3×.
  * pair_filter / pair_filter_naive  — ``WHERE PAIR_DIFF(...) > T``: most
                                       images decided from the two roles'
                                       CHI rows alone.

    PYTHONPATH=src python benchmarks/bench_pair.py --json BENCH_pair.json
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import tempfile
import time

import numpy as np


def _setup(n_images: int, size: int, tmpdir: str) -> str:
    from repro.core import CHIConfig, MaskStore
    from repro.core.store import MASK_META_DTYPE
    from repro.data.masks import object_boxes, saliency_masks

    rng = np.random.default_rng(3)
    boxes = object_boxes(n_images, size, size, seed=4)
    model, _ = saliency_masks(n_images, size, size, seed=5, boxes=boxes,
                              in_box_fraction=1.0)
    misaligned = rng.random(n_images) < 0.08
    jitter, _ = saliency_masks(n_images, size, size, seed=6, boxes=boxes,
                               in_box_fraction=1.0)
    aligned = np.clip(0.9 * model + 0.25 * jitter, 0.0, 1.0 - 1e-6)
    off, _ = saliency_masks(n_images, size, size, seed=7, boxes=None)
    human = np.where(misaligned[:, None, None], off, aligned)

    masks = np.stack([model, human], axis=1).reshape(-1, size, size)
    n = len(masks)
    meta = np.zeros(n, MASK_META_DTYPE)
    meta["mask_id"] = np.arange(n)
    meta["image_id"] = np.arange(n) // 2
    meta["mask_type"] = np.arange(n) % 2 + 1
    cfg = CHIConfig(grid=16, num_bins=16, height=size, width=size)
    root = os.path.join(tmpdir, "db")
    MaskStore.create_disk(root, masks, meta, cfg)
    return root


def _row(name: str, seconds: float, derived: str = ""):
    print(f"{name},{seconds * 1e6:.1f},{derived}", flush=True)


def _run_pair(root, sql, verify_batch=64, use_index=True):
    from repro.core import MaskStore, queries
    from repro.core.plan import run_plan

    store = MaskStore.open_disk(root)
    plan = queries.parse(sql).plan
    t0 = time.perf_counter()
    payload, stats = run_plan(store, plan, use_index=use_index,
                              verify_batch=verify_batch)
    elapsed = time.perf_counter() - t0
    return payload, stats, store.io.bytes_read, elapsed


def bench_query(root, name, sql, record):
    payload, stats, idx_bytes, t_idx = _run_pair(root, sql)
    naive, nstats, naive_bytes, t_naive = _run_pair(root, sql,
                                                    use_index=False)
    ids = payload[0] if isinstance(payload, tuple) else payload
    ids0 = naive[0] if isinstance(naive, tuple) else naive
    assert list(ids) == list(ids0), (name, ids, ids0)   # pruning is exact
    if isinstance(payload, tuple):
        np.testing.assert_allclose(payload[1], naive[1])
    ratio = naive_bytes / max(idx_bytes, 1)
    _row(name, t_idx,
         f"bytes={idx_bytes};verified={stats.n_verified}/"
         f"{stats.n_candidates};hits={len(ids)}")
    _row(f"{name}_naive", t_naive,
         f"bytes={naive_bytes};prune_gain={ratio:.2f}x_bytes")
    record[name] = {
        "sql": sql,
        "indexed": {"latency_s": t_idx, "bytes_loaded": int(idx_bytes),
                    "n_verified": int(stats.n_verified),
                    "n_candidates": int(stats.n_candidates),
                    "n_decided_by_bounds": int(stats.n_decided_by_bounds),
                    "n_hits": int(len(ids))},
        "naive_decode_all_pairs": {"latency_s": t_naive,
                                   "bytes_loaded": int(naive_bytes)},
        "bytes_ratio": ratio,
        "latency_ratio": t_naive / max(t_idx, 1e-9),
    }


IOU_TOPK = ("SELECT image_id FROM MasksDatabaseView "
            "ORDER BY IOU(saliency, attention, 0.6, 0.6) ASC LIMIT 25;")
DIFF_FILTER = ("SELECT image_id FROM MasksDatabaseView "
               "WHERE PAIR_DIFF(saliency, attention, 0.6, 0.6) > 600;")


def _setup_binary(n_images: int, size: int, tmpdir: str, packed: bool) -> str:
    """Binarized variant of ``_setup``: same planted misalignment, values
    thresholded to {0, 1} so both the float and the packed disk tier can
    ingest them (the 0.6 thresholds in the SQL then select the set bits)."""
    from repro.core import CHIConfig, MaskStore
    from repro.core.store import MASK_META_DTYPE
    from repro.data.masks import object_boxes, saliency_masks

    rng = np.random.default_rng(3)
    boxes = object_boxes(n_images, size, size, seed=4)
    model, _ = saliency_masks(n_images, size, size, seed=5, boxes=boxes,
                              in_box_fraction=1.0)
    misaligned = rng.random(n_images) < 0.08
    off, _ = saliency_masks(n_images, size, size, seed=7, boxes=None)
    human = np.where(misaligned[:, None, None], off, model)
    masks = (np.stack([model, human], axis=1).reshape(-1, size, size)
             > 0.5).astype(np.float32)
    n = len(masks)
    meta = np.zeros(n, MASK_META_DTYPE)
    meta["mask_id"] = np.arange(n)
    meta["image_id"] = np.arange(n) // 2
    meta["mask_type"] = np.arange(n) % 2 + 1
    cfg = CHIConfig(grid=16, num_bins=16, height=size, width=size)
    root = os.path.join(tmpdir, "pdb" if packed else "fdb")
    MaskStore.create_disk(root, masks, meta, cfg, packed=packed)
    return root


def bench_packed(n_images, size, tmpdir, record):
    """Packed vs float disk tier on identical binary pair data: ids must
    match bit-for-bit and the packed leg's metered bytes are the headline
    (``bytes_ratio`` = float bytes / packed bytes, acceptance ≥ 8×)."""
    out = {"sql": IOU_TOPK}
    ids_by_tier = {}
    for tier, packed in (("float", False), ("packed", True)):
        root = _setup_binary(n_images, size, tmpdir, packed)
        payload, stats, nbytes, t = _run_pair(root, IOU_TOPK)
        ids_by_tier[tier] = list(payload[0] if isinstance(payload, tuple)
                                 else payload)
        _row(f"pair_packed_{tier}", t,
             f"bytes={nbytes};verified={stats.n_verified}/"
             f"{stats.n_candidates}")
        out[tier] = {"latency_s": t, "bytes_loaded": int(nbytes),
                     "n_verified": int(stats.n_verified),
                     "n_decided_by_bounds": int(stats.n_decided_by_bounds)}
    assert ids_by_tier["packed"] == ids_by_tier["float"], \
        "packed pair tier diverged from float"
    out["bytes_ratio"] = (out["float"]["bytes_loaded"]
                          / max(out["packed"]["bytes_loaded"], 1))
    out["latency_ratio"] = (out["float"]["latency_s"]
                            / max(out["packed"]["latency_s"], 1e-9))
    record["pair_packed"] = out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-images", type=int, default=1000)
    ap.add_argument("--size", type=int, default=128)
    ap.add_argument("--json", default=None,
                    help="also write a JSON record to this path")
    ap.add_argument("--packed", action="store_true",
                    help="also bench the bitpacked binary tier vs the "
                         "float tier on binarized pair data")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    import jax

    print("name,us_per_call,derived")
    tmpdir = tempfile.mkdtemp(prefix="masksearch_pair_")
    record = {"config": {"n_images": args.n_images, "size": args.size,
                         "jax_backend": jax.default_backend(),
                         "device_count": jax.device_count()}}
    try:
        t0 = time.perf_counter()
        root = _setup(args.n_images, args.size, tmpdir)
        _row("db_ingest_total", time.perf_counter() - t0,
             f"n_pairs={args.n_images};size={args.size}")
        bench_query(root, "pair_iou_topk", IOU_TOPK, record)
        bench_query(root, "pair_filter", DIFF_FILTER, record)
        if args.packed:
            bench_packed(args.n_images, args.size, tmpdir, record)
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(record, f, indent=2)
        print(f"# wrote {args.json}", flush=True)


if __name__ == "__main__":
    main()
