"""Unit tests: engine semantics, SQL front-end, store tiers, multi-query."""

import numpy as np
import pytest

from repro.core import (CHIConfig, CP, MaskStore, engine, queries)
from repro.core.exprs import AggCP, BinOp, RoiArea
from repro.core.store import MASK_META_DTYPE
from repro.data.masks import object_boxes, saliency_masks

B, H, W = 60, 64, 64


@pytest.fixture(scope="module")
def db(tmp_path_factory):
    root = tmp_path_factory.mktemp("maskdb")
    rois = object_boxes(B, H, W, seed=2)
    masks, attacked = saliency_masks(B, H, W, seed=1, attacked_fraction=0.25,
                                     boxes=rois)
    meta = np.zeros(B, MASK_META_DTYPE)
    meta["mask_id"] = np.arange(B) + 1000
    meta["image_id"] = np.arange(B) // 2
    meta["mask_type"] = np.arange(B) % 2 + 1
    cfg = CHIConfig(grid=8, num_bins=16, height=H, width=W)
    store = MaskStore.create_disk(str(root), masks, meta, cfg)
    return store, rois, masks, attacked


def test_disk_roundtrip_and_reopen(db, tmp_path):
    store, rois, masks, _ = db
    reopened = MaskStore.open_disk(store.root)
    assert len(reopened) == B
    got = reopened.load(np.array([0, 5, 17]))
    np.testing.assert_array_equal(got, masks[[0, 5, 17]])
    assert reopened.io.files_read == 3
    assert reopened.io.bytes_read == 3 * H * W * 4
    assert reopened.io.modeled_ebs_time_s > 0


def test_filter_verification_reduces_io(db):
    store, rois, _, _ = db
    expr = BinOp("/", CP("provided", 0.8, 1.0), RoiArea("provided"))
    store.io.reset()
    ids, stats = engine.filter_query(store, expr, "<", 0.02,
                                     provided_rois=rois)
    assert stats.n_verified < stats.n_candidates  # the index pruned loads
    # partial ROI-row loads: strictly fewer bytes than full-mask verification
    assert 0 < stats.bytes_loaded < stats.n_verified * H * W * 4
    ids_scan, _ = engine.filter_query(store, expr, "<", 0.02,
                                      provided_rois=rois, use_index=False)
    assert set(ids) == set(ids_scan)


def test_topk_early_termination(db):
    store, rois, _, _ = db
    expr = BinOp("/", CP("provided", 0.8, 1.0), RoiArea("provided"))
    ids, scores, stats = engine.topk_query(store, expr, 5, desc=False,
                                           provided_rois=rois, verify_batch=8)
    assert len(ids) == 5
    assert np.all(np.diff(scores) >= 0)           # ascending
    assert stats.n_verified < stats.n_candidates
    _, scores_s, _ = engine.topk_query(store, expr, 5, desc=False,
                                       provided_rois=rois, use_index=False)
    np.testing.assert_allclose(scores, scores_s)


def test_scenario2_dispersion_finds_attacked(db):
    store, rois, _, attacked = db
    # ~12 of 60 masks are attacked; the dispersion ranking should put
    # attacked masks strictly on top (perfect separation on this data).
    (ids, scores), stats = queries.run(
        "SELECT mask_id FROM MasksDatabaseView ORDER BY "
        "CP(mask, full_img, (0.2, 0.6)) DESC LIMIT 10;", store)
    pos = store.positions_of(ids)
    hit = attacked[pos].mean()
    assert hit >= 0.9, f"dispersion query precision {hit}"


def test_scenario3_iou_groups(db):
    store, rois, masks, _ = db
    (img_ids, scores), stats = queries.run(queries.SCENARIO3_IOU, store)
    assert len(img_ids) == 25
    assert np.all(scores[:-1] <= scores[1:] + 1e-12)
    # brute-force check the winner
    im = img_ids[0]
    members = masks[store.meta["image_id"] == im] > 0.8
    inter = np.logical_and.reduce(members).sum()
    union = np.logical_or.reduce(members).sum()
    want = inter / union if union else 0.0
    assert abs(scores[0] - want) < 1e-9


def test_mask_type_predicate(db):
    store, _, _, _ = db
    q = queries.parse("SELECT mask_id FROM MasksDatabaseView WHERE "
                      "mask_type IN (1) AND CP(mask, full_img, (0.0, 1.0)) "
                      f"> {H * W - 1};")
    ids, _ = q.run(store)
    types = store.meta["mask_type"][store.positions_of(ids)]
    assert np.all(types == 1)
    assert len(ids) == B // 2  # full-range CP == area for every mask


def test_multiquery_shares_loads(db):
    """Related rankings through the service's batch entry point (one fused
    drive) answer as one query at a time does, and load fewer mask bytes
    than running them serially without sharing."""
    from repro.service import MaskSearchService
    store, rois, _, _ = db
    sqls = ["SELECT mask_id FROM MasksDatabaseView ORDER BY "
            f"CP(mask, full_img, ({lv}, {lv + 0.3})) DESC LIMIT 10;"
            for lv in (0.2, 0.25, 0.3)]
    svc = MaskSearchService(MaskStore.open_disk(store.root),
                            provided_rois=rois, verify_batch=8)
    out = svc.execute_many([{"op": "query", "sql": s} for s in sqls])
    shared_bytes = svc.store.io.bytes_read
    svc.close()

    serial = MaskStore.open_disk(store.root)
    want = [queries.run(s, serial, provided_rois=rois) for s in sqls]
    # 3.75x on this data; the floor is a 2.5th of that
    assert 0 < 1.5 * shared_bytes <= serial.io.bytes_read
    for (status, got), ((ids, scores), _) in zip(out, want):
        assert status == "ok", got
        assert got["ids"] == [int(x) for x in ids]
        np.testing.assert_allclose(got["scores"], scores)


def test_sql_parser_errors():
    with pytest.raises(SyntaxError):
        queries.parse("SELECT nothing FROM MasksDatabaseView;")
    with pytest.raises(SyntaxError):
        queries.parse("SELECT mask_id FROM V WHERE CP(mask, roi) < 5;")
    q = queries.parse("SELECT mask_id FROM V WHERE "
                      "CP(mask, (1, 2, 30, 40), (0.5, 1.0)) >= 10;")
    assert q.op == ">=" and q.threshold == 10


def test_ragged_groups_record_dropped_masks():
    """Grouped evaluation needs rectangular (n_groups, size) blocks, so
    ragged image groups are truncated to the smallest group size.  That
    used to be silent data loss; it must now be surfaced in
    ExecStats.n_dropped_masks (indexed and full-scan paths alike)."""
    b, h, w = 11, 32, 32
    masks = saliency_masks(b, h, w, seed=6)[0]
    meta = np.zeros(b, MASK_META_DTYPE)
    meta["mask_id"] = np.arange(b)
    # images of 3, 2, 2, and 4 masks → size 2, with 1 + 2 = 3 dropped
    meta["image_id"] = np.array([0, 0, 0, 1, 1, 2, 2, 3, 3, 3, 3])
    cfg = CHIConfig(grid=4, num_bins=8, height=h, width=w)
    store = MaskStore.create_memory(masks, meta, cfg)

    expr = AggCP("union", 0.8, None)
    _, _, stats = engine.topk_query(store, expr, 3, group_by_image=True)
    assert stats.n_dropped_masks == 3
    assert stats.n_candidates == 4                    # 4 image groups
    _, _, stats_scan = engine.topk_query(store, expr, 3,
                                         group_by_image=True,
                                         use_index=False)
    assert stats_scan.n_dropped_masks == 3

    # even groups drop nothing
    even = np.zeros(6, MASK_META_DTYPE)
    even["mask_id"] = np.arange(6)
    even["image_id"] = np.arange(6) // 2
    store2 = MaskStore.create_memory(masks[:6], even, cfg)
    _, _, stats2 = engine.topk_query(store2, expr, 2, group_by_image=True)
    assert stats2.n_dropped_masks == 0
    # and per-mask (ungrouped) runs never report drops
    ids, fstats = engine.filter_query(store, CP(None, 0.0, 1.0), ">", -1.0)
    assert fstats.n_dropped_masks == 0


def test_execution_detail_bounds_histogram(db):
    """The GUI's 'Execution Detail' bound distribution, as library data."""
    from repro.core.exprs import MaskEvalContext
    store, rois, _, _ = db
    ctx = MaskEvalContext(store, np.arange(len(store)), rois)
    lb, ub = ctx.bounds(CP("provided", 0.8, 1.0))
    assert np.all(lb <= ub)
    assert (ub - lb).max() > 0  # something undecided → histogram non-trivial
