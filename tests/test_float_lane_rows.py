"""The float tier's resident rows on the device (DESIGN.md §7): float
masks whose ``H·W`` is a multiple of 128 are held in lanes
``(n, H·W/128, 128)``, other float shapes as 2-D rows ``(n, H·W)``.

Over either form the served device backend answers every template kind of
the GUI mix (top-k over an ROI or the whole mask, plain or normalised,
filters, filtered top-k, IoU top-k, grouped MASK_AGG) with the ids,
scores and order of the host backend, with CP ranges on the CHI's bin
edges; and the resident bytes the answers report sum to what the
backend's ``gathered_bytes`` counted over the same queries.
"""

import numpy as np
import pytest

from repro.core import CHIConfig, MaskStore
from repro.core.store import MASK_META_DTYPE
from repro.data.masks import object_boxes, saliency_masks
from repro.service import MaskSearchService

N = 48

QUERIES = [
    "SELECT mask_id FROM MasksDatabaseView ORDER BY "
    "CP(mask, roi, (0.25, 0.75)) / AREA(roi) DESC LIMIT 10;",
    "SELECT mask_id FROM MasksDatabaseView ORDER BY "
    "CP(mask, roi, (0.0625, 0.5)) ASC LIMIT 5;",
    "SELECT mask_id FROM MasksDatabaseView ORDER BY "
    "CP(mask, full_img, (0.5, 1.0)) DESC LIMIT 10;",
    "SELECT mask_id FROM MasksDatabaseView WHERE "
    "CP(mask, roi, (0.375, 0.875)) / AREA(roi) > 0.15;",
    "SELECT mask_id FROM MasksDatabaseView WHERE "
    "CP(mask, roi, (0.5, 1.0)) / AREA(roi) > 0.1 ORDER BY "
    "CP(mask, full_img, (0.125, 0.4375)) DESC LIMIT 5;",
    "SELECT image_id FROM MasksDatabaseView ORDER BY "
    "IOU(saliency, attention, 0.35, 0.6) DESC LIMIT 5;",
    "SELECT image_id, CP(intersect(mask > 0.45), full_img, (0.5, 2.0)) / "
    "CP(union(mask > 0.45), full_img, (0.5, 2.0)) AS iou FROM "
    "MasksDatabaseView WHERE mask_type IN (1, 2) GROUP BY image_id "
    "ORDER BY iou ASC LIMIT 5;",
]


def _store(size):
    boxes = object_boxes(N, size, size, seed=3)
    masks, _ = saliency_masks(N, size, size, seed=2, attacked_fraction=0.2,
                              boxes=boxes)
    meta = np.zeros(N, MASK_META_DTYPE)
    meta["mask_id"] = np.arange(N)
    meta["image_id"] = np.arange(N) // 2            # saliency, attention
    meta["mask_type"] = np.arange(N) % 2 + 1
    cfg = CHIConfig(grid=4, num_bins=16, height=size, width=size)
    return MaskStore.create_memory(np.asarray(masks, np.float32), meta,
                                   cfg), boxes


def _answers(svc):
    out = []
    for sql in QUERIES:
        (status, body), = svc.execute_many([{"op": "query", "sql": sql}])
        assert status == "ok", body
        out.append(body)
    return out


@pytest.mark.parametrize("size,row", [(32, (8, 128)), (36, (36 * 36,))],
                         ids=["lanes", "rows"])
def test_device_answers_equal_host_over_float_rows(size, row):
    store, boxes = _store(size)
    host = MaskSearchService(store, provided_rois=boxes, verify_batch=8)
    device = MaskSearchService(store, provided_rois=boxes, backend="device",
                               verify_batch=8)
    try:
        assert store.device_masks().shape == (N,) + row
        want, got = _answers(host), _answers(device)
    finally:
        host.close()
        device.close()
    for sql, w, g in zip(QUERIES, want, got):
        assert g["ids"] == w["ids"], sql
        assert g.get("scores") == w.get("scores"), sql
    assert any(g["ids"] for g in got)
    assert sum(g["stats"]["n_verified"] for g in got) > 0


def test_resident_bytes_sum_to_the_gathered_bytes():
    """Each answer's ``resident_bytes`` is its share of the rows the
    device steps read: over a serial run of queries they sum to the
    change of ``BackendStats.gathered_bytes``, a whole number of stored
    rows, and the host backend reads none.  Of those rows, the CP and
    grouped verification kernels read every one in place from the lane
    rows (``inplace_rows``); only the pair pass (the IoU query) gathers."""
    store, boxes = _store(32)
    host = MaskSearchService(store, provided_rois=boxes, verify_batch=8)
    device = MaskSearchService(store, provided_rois=boxes, backend="device",
                               verify_batch=8)
    try:
        g0 = device.backend.stats.gathered_bytes
        i0 = device.backend.stats.inplace_rows
        got = _answers(device)
        delta = device.backend.stats.gathered_bytes - g0
        inplace = device.backend.stats.inplace_rows - i0
        text = device.metrics_text()
        assert "masksearch_backend_gathered_bytes" in text
        assert "masksearch_backend_inplace_rows" in text
        assert all(b["stats"]["resident_bytes"] == 0 for b in _answers(host))
    finally:
        host.close()
        device.close()
    per_query = [b["stats"]["resident_bytes"] for b in got]
    assert sum(per_query) == delta > 0
    assert delta % store.row_nbytes == 0
    assert all(b > 0 for b, a in zip(per_query, got)
               if a["stats"]["n_verified"])
    pair = [b for b, sql in zip(per_query, QUERIES) if "IOU(" in sql]
    cp_and_grouped = sum(per_query) - sum(pair)
    assert inplace * store.row_nbytes == cp_and_grouped > 0
    assert sum(pair) > 0


def test_packed_steps_read_no_rows_in_place():
    """The packed tier's steps gather their batches: its window counts
    gathered bytes and no in-place rows."""
    store, boxes = _store(32)
    masks = (store.resident_masks() > 0.5).astype(np.float32)
    packed = MaskStore.create_memory(masks, store.meta.copy(), store.cfg,
                                     packed=True)
    device = MaskSearchService(packed, provided_rois=boxes,
                               backend="device", verify_batch=8)
    try:
        got = _answers(device)
        stats = device.backend.stats
        assert "masksearch_backend_inplace_rows 0" in device.metrics_text()
    finally:
        device.close()
    assert sum(b["stats"]["resident_bytes"] for b in got) \
        == stats.gathered_bytes > 0
    assert stats.inplace_rows == 0
