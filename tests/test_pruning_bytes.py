"""Pruning measured in bytes: on seed-deterministic data, the index-driven
plans load a fraction of the bytes that the unpruned path loads, and answer
exactly as it does.

  * filtered top-k through the service vs the naive filter-then-rank scan
    (disk tier, metered mask reads);
  * pair (saliency vs attention) IoU ranking and difference filter vs
    decoding every pair;
  * the packed binary tier vs the float tier on the same binary pair data;
  * the CHI pyramid ladder vs the single-grid bounds pass, in index bytes
    per candidate decided by bounds.

Each floor is the ratio this data gives divided by 2.5: it trips when a
pruning mechanism is lost, not when a bound shifts by a few candidates.
"""

import numpy as np
import pytest

from repro.core import CHIConfig, MaskStore, opt, queries
from repro.core.exprs import CP, And, Cmp
from repro.core.plan import LogicalPlan, run_plan
from repro.core.store import MASK_META_DTYPE
from repro.data.masks import object_boxes, saliency_masks
from repro.service import MaskSearchService

SLACK = 2.5


def _meta(n, image_id, mask_type):
    meta = np.zeros(n, MASK_META_DTYPE)
    meta["mask_id"] = np.arange(n)
    meta["image_id"] = image_id
    meta["mask_type"] = mask_type
    return meta


# -- filtered top-k: three-valued bounds pruning vs filter-then-rank ---------

FILTERED_TOPK = ("SELECT mask_id FROM MasksDatabaseView "
                 "WHERE CP(mask, roi, (0.8, 1.0)) > {roi_t} "
                 "AND NOT CP(mask, full_img, (0.2, 0.6)) < {full_t} "
                 "ORDER BY CP(mask, full_img, (0.2, 0.6)) DESC LIMIT 25;")


@pytest.fixture(scope="module")
def saliency_db(tmp_path_factory):
    n, size = 200, 64
    root = str(tmp_path_factory.mktemp("saliency_db"))
    rois = object_boxes(n, size, size, seed=1)
    masks, _ = saliency_masks(n, size, size, seed=7, attacked_fraction=0.2,
                              boxes=rois, in_box_fraction=0.9)
    cfg = CHIConfig(grid=16, num_bins=16, height=size, width=size)
    MaskStore.create_disk(root, masks,
                          _meta(n, np.arange(n) // 2, np.arange(n) % 2 + 1),
                          cfg)
    return root, rois


@pytest.mark.parametrize("roi_t,full_t,n_hits,ratio", [
    (200, 100, 0, 200.0),      # the bounds reject all but one candidate
    (10, 500, 25, 5.405),      # a full page of hits
], ids=["selective", "page"])
def test_filtered_topk_loads_fewer_bytes_than_naive(saliency_db, roi_t,
                                                    full_t, n_hits, ratio):
    root, rois = saliency_db
    sql = FILTERED_TOPK.format(roi_t=roi_t, full_t=full_t)
    svc = MaskSearchService(MaskStore.open_disk(root), provided_rois=rois,
                            verify_batch=256)
    out = svc.query(sql)
    indexed = svc.store.io.bytes_read
    svc.close()

    naive_store = MaskStore.open_disk(root)
    (ids0, _), _ = run_plan(naive_store, queries.parse(sql).plan,
                            provided_rois=rois, use_index=False)
    assert out["ids"] == [int(x) for x in ids0]
    assert len(out["ids"]) == n_hits
    assert naive_store.io.bytes_read >= ratio / SLACK * max(indexed, 1)


# -- pair queries: cell-decomposed pair bounds vs decode-all-pairs -----------

IOU_TOPK = ("SELECT image_id FROM MasksDatabaseView "
            "ORDER BY IOU(saliency, attention, 0.6, 0.6) ASC LIMIT 25;")
DIFF_FILTER = ("SELECT image_id FROM MasksDatabaseView "
               "WHERE PAIR_DIFF(saliency, attention, 0.6, 0.6) > 600;")
N_IMAGES, PAIR_SIZE = 400, 128


def _pair_masks(binary):
    """One (model saliency, human attention) pair per image; 8% of the
    humans look elsewhere.  ``binary`` thresholds both to {0, 1}."""
    n, s = N_IMAGES, PAIR_SIZE
    misaligned = np.random.default_rng(3).random(n) < 0.08
    boxes = object_boxes(n, s, s, seed=4)
    model, _ = saliency_masks(n, s, s, seed=5, boxes=boxes,
                              in_box_fraction=1.0)
    off, _ = saliency_masks(n, s, s, seed=7, boxes=None)
    if binary:
        aligned = model
    else:
        jitter, _ = saliency_masks(n, s, s, seed=6, boxes=boxes,
                                   in_box_fraction=1.0)
        aligned = np.clip(0.9 * model + 0.25 * jitter, 0.0, 1.0 - 1e-6)
    human = np.where(misaligned[:, None, None], off, aligned)
    masks = np.stack([model, human], axis=1).reshape(-1, s, s)
    return (masks > 0.5).astype(np.float32) if binary else masks


@pytest.fixture(scope="module")
def pair_dbs(tmp_path_factory):
    cfg = CHIConfig(grid=16, num_bins=16, height=PAIR_SIZE, width=PAIR_SIZE)
    n = 2 * N_IMAGES
    meta = _meta(n, np.arange(n) // 2, np.arange(n) % 2 + 1)
    roots = {}
    for name, binary, packed in (("float", False, False),
                                 ("binary", True, False),
                                 ("packed", True, True)):
        roots[name] = str(tmp_path_factory.mktemp(f"pair_{name}"))
        MaskStore.create_disk(roots[name], _pair_masks(binary), meta.copy(),
                              cfg, packed=packed)
    return roots


def _run_pair(root, sql, use_index):
    store = MaskStore.open_disk(root)
    payload, _ = run_plan(store, queries.parse(sql).plan,
                          use_index=use_index, verify_batch=64)
    return payload, store.io.bytes_read


@pytest.mark.parametrize("sql,lean,heavy,ratio", [
    (IOU_TOPK, ("float", True), ("float", False), 3.125),
    (DIFF_FILTER, ("float", True), ("float", False), 44.44),
    # 1 bit per pixel against 32: exactly 32x at W % 32 == 0
    (IOU_TOPK, ("packed", True), ("binary", True), 32.0),
], ids=["iou_topk", "diff_filter", "packed_iou_topk"])
def test_pair_pruning_loads_fewer_bytes(pair_dbs, sql, lean, heavy, ratio):
    got, lean_bytes = _run_pair(pair_dbs[lean[0]], sql, lean[1])
    want, heavy_bytes = _run_pair(pair_dbs[heavy[0]], sql, heavy[1])
    if isinstance(want, tuple):
        assert list(got[0]) == list(want[0])
        np.testing.assert_allclose(got[1], want[1])
    else:
        assert list(got) == list(want)
    assert heavy_bytes >= ratio / SLACK * max(lean_bytes, 1)


# -- CHI pyramid ladder vs one grid: index bytes per decided candidate -------


def test_ladder_bytes_per_decided_beats_single_grid():
    n, size = 300, 64
    rng = np.random.default_rng(17)
    masks = rng.random((n, size, size), dtype=np.float32)
    masks[:n // 2] *= 0.3                        # half the store: low-valued
    hot = slice(n // 2, n // 2 + n // 20)        # 5%: clearly hot
    masks[hot] = 0.5 + 0.5 * masks[hot]
    # 0.2 and 0.8 sit on CHI value edges, so the aligned full-image ROI is
    # answered exactly at every pyramid tier
    cfg = CHIConfig(grid=16, num_bins=16, height=size, width=size,
                    thresholds=tuple(round(0.1 + 0.05 * i, 2)
                                     for i in range(15)))
    store = MaskStore.create_memory(
        masks, _meta(n, np.arange(n), np.arange(n) % 3 + 1), cfg)
    area, full, inf = size * size, (0, 0, size, size), float("inf")
    lo, hi = float(np.float32(0.2)), float(np.float32(0.8))
    # plan order puts the weak conjunct first; the optimizer flips it
    plan = LogicalPlan(predicate=And(Cmp(CP(full, lo, inf), ">", 0.01 * area),
                                     Cmp(CP(full, hi, inf), ">", 0.25 * area)))
    legs = {}
    for name, pyramid in (("classic", False), ("ladder", True)):
        with opt.configure(pyramid=pyramid, reorder=pyramid):
            ids, stats = run_plan(store, plan)
        assert stats.n_decided_by_bounds > 0
        legs[name] = (list(ids), stats.n_decided_by_bounds,
                      stats.chi_bytes / stats.n_decided_by_bounds)
    assert legs["ladder"][:2] == legs["classic"][:2]
    assert legs["classic"][2] >= 22.02 / SLACK * legs["ladder"][2]
