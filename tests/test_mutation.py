"""Mutable mask database (DESIGN.md §8): epoch-versioned append/update/
delete, incremental CHI maintenance, snapshot consistency for resumable
runs, and epoch-keyed cache invalidation across every cache tier
(planner result/bounds caches, sessions, the shared-load cache)."""

import threading

import numpy as np
import pytest

from repro.core import (CHIConfig, MaskStore, StaleRunError, build_chi_np)
from repro.core.engine import TopKRun
from repro.core.exprs import CP, Cmp
from repro.core.plan import LogicalPlan, run_plan
from repro.core.store import MASK_META_DTYPE
from repro.data.masks import object_boxes, saliency_masks
from repro.service import MaskSearchService
from repro.service.planner import LRUCache

B, H, W = 18, 32, 32
CFG = CHIConfig(grid=4, num_bins=8, height=H, width=W)

TOPK_SQL = ("SELECT mask_id FROM MasksDatabaseView ORDER BY "
            "CP(mask, full_img, (0.2, 0.6)) DESC LIMIT {k};")


def _data(n, seed=0, id_base=0, h=H, w=W):
    boxes = object_boxes(n, h, w, seed=seed + 1)
    masks, _ = saliency_masks(n, h, w, seed=seed, attacked_fraction=0.3,
                              boxes=boxes)
    meta = np.zeros(n, MASK_META_DTYPE)
    meta["mask_id"] = id_base + np.arange(n)
    meta["image_id"] = (id_base + np.arange(n)) // 2
    meta["mask_type"] = np.arange(n) % 3 + 1
    return np.asarray(masks, np.float32), meta


def _mk_memory(n=B, seed=0):
    masks, meta = _data(n, seed=seed)
    return MaskStore.create_memory(masks, meta, CFG), masks


# ---------------------------------------------------------------------------
# store-level mutation semantics
# ---------------------------------------------------------------------------


def test_append_indexes_only_the_delta():
    store, masks = _mk_memory()
    new_masks, new_meta = _data(6, seed=7, id_base=1000)
    chunks_before = len(store.chi_chunks)
    epoch = store.append(new_masks, new_meta)
    assert epoch == store.epoch == 1
    assert len(store) == B + 6
    # the delta landed as its own chunk; nothing existing was rebuilt
    assert len(store.chi_chunks) == chunks_before + 1
    assert len(store.chi_chunks[-1]) == 6
    all_masks = np.concatenate([masks, new_masks])
    np.testing.assert_array_equal(store.chi_host(),
                                  build_chi_np(all_masks, CFG))
    # duplicate / colliding ids refuse
    with pytest.raises(ValueError):
        store.append(new_masks[:1], new_meta[:1])


def test_update_patches_chi_rows_in_place():
    store, masks = _mk_memory()
    new = np.clip(masks[[2, 5, 11]] * 0.4 + 0.1, 0, 1)
    epoch = store.update([2, 5, 11], new)
    assert epoch == 1
    ref = masks.copy()
    ref[[2, 5, 11]] = new
    np.testing.assert_array_equal(store.chi_host(), build_chi_np(ref, CFG))
    np.testing.assert_array_equal(store.resident_masks()[[2, 5, 11]], new)
    with pytest.raises(KeyError):
        store.update([9999], new[:1])


def test_delete_compacts_and_keeps_ids_stable():
    store, masks = _mk_memory()
    epoch = store.delete([0, 7, 17])
    assert epoch == 1 and len(store) == B - 3
    keep = np.ones(B, bool)
    keep[[0, 7, 17]] = False
    np.testing.assert_array_equal(store.mask_ids, np.arange(B)[keep])
    np.testing.assert_array_equal(store.chi_host(),
                                  build_chi_np(masks[keep], CFG))
    # positions renumber; lookups by id still resolve
    assert store.positions_of([1])[0] == 0


def test_random_mutation_sequence_matches_rebuild():
    """After any interleaving of append/update/delete, the chunked CHI must
    equal a from-scratch build and queries must match a fresh store."""
    rng = np.random.default_rng(42)
    store, masks = _mk_memory()
    current = masks.copy()
    ids = list(range(B))
    next_id = 1000
    for step in range(8):
        op = rng.integers(3)
        if op == 0:                                        # append
            n = int(rng.integers(1, 4))
            add, meta = _data(n, seed=100 + step, id_base=next_id)
            next_id += n
            store.append(add, meta)
            current = np.concatenate([current, add])
            ids.extend(meta["mask_id"])
        elif op == 1 and len(ids):                          # update
            n = int(rng.integers(1, min(4, len(ids)) + 1))
            sel = rng.choice(len(ids), size=n, replace=False)
            upd_ids = [ids[i] for i in sel]
            new = np.clip(rng.random((n, H, W)).astype(np.float32), 0, 1)
            store.update(upd_ids, new)
            current[sel] = new
        elif len(ids) > 4:                                  # delete
            n = int(rng.integers(1, 3))
            sel = np.sort(rng.choice(len(ids), size=n, replace=False))[::-1]
            del_ids = [ids[i] for i in sel]
            store.delete(del_ids)
            keep = np.ones(len(ids), bool)
            keep[sel] = False
            current = current[keep]
            ids = [m for i, m in enumerate(ids) if keep[i]]
        np.testing.assert_array_equal(store.chi_host(),
                                      build_chi_np(current, CFG))
        np.testing.assert_array_equal(store.resident_masks(), current)
        # query equivalence against a freshly built store
        meta = np.zeros(len(ids), MASK_META_DTYPE)
        meta["mask_id"] = ids
        fresh = MaskStore.create_memory(current, meta, CFG)
        plan = LogicalPlan(order_by=CP(None, 0.2, 0.6),
                           k=min(5, max(len(ids), 1)))
        (got_ids, got_scores), _ = run_plan(store, plan)
        (ref_ids, ref_scores), _ = run_plan(fresh, plan)
        np.testing.assert_array_equal(got_ids, ref_ids)
        np.testing.assert_array_equal(got_scores, ref_scores)


# ---------------------------------------------------------------------------
# disk-tier persistence round-trips (satellite)
# ---------------------------------------------------------------------------


def test_disk_roundtrip_preserves_config_meta_chi_epoch(tmp_path):
    masks, meta = _data(10, seed=3)
    root = str(tmp_path / "db")
    store = MaskStore.create_disk(root, masks, meta, CFG)
    assert store.epoch == 0

    add_masks, add_meta = _data(4, seed=9, id_base=500)
    store.append(add_masks, add_meta)
    new = np.clip(masks[[1, 3]] * 0.2, 0, 1)
    store.update([1, 3], new)
    assert store.epoch == 2

    current = np.concatenate([masks, add_masks])
    current[[1, 3]] = new

    re = MaskStore.open_disk(root)
    assert re.epoch == 2
    assert re.cfg == CFG
    np.testing.assert_array_equal(re.meta, store.meta)
    assert len(re.chi_chunks) == len(store.chi_chunks)
    np.testing.assert_array_equal(re.chi_host(), build_chi_np(current, CFG))
    np.testing.assert_array_equal(re.load_all(), current)

    # delete compacts the chunk files and persists too
    re.delete([500, 501])
    re2 = MaskStore.open_disk(root)
    assert re2.epoch == 3
    assert len(re2.chi_chunks) == 1
    keep = np.ones(14, bool)
    keep[[10, 11]] = False
    np.testing.assert_array_equal(re2.chi_host(),
                                  build_chi_np(current[keep], CFG))
    np.testing.assert_array_equal(re2.load_all(), current[keep])


# ---------------------------------------------------------------------------
# snapshot consistency for resumable runs
# ---------------------------------------------------------------------------


def _partial_run(store, **kw):
    run = TopKRun(store, CP(None, 0.2, 0.6), verify_batch=2, **kw)
    run.target(6)
    batch = run.take_batch()
    if len(batch):
        run.self_verify(batch)
    return run


def test_memory_run_finishes_on_snapshot_after_update():
    store, masks = _mk_memory()
    reference = TopKRun(store, CP(None, 0.2, 0.6), verify_batch=2)
    reference.ensure(6)
    run = _partial_run(store)
    # rewrite bytes the run still needs — the run's pinned view must win
    store.update(list(range(B)),
                 np.clip(masks[::-1].copy() * 0.5, 0, 1))
    assert not run.fresh() and run.resumable()
    run.ensure(6)
    got, ref = run.result(), reference.result()
    np.testing.assert_array_equal(got[0], ref[0])
    np.testing.assert_array_equal(got[1], ref[1])


@pytest.mark.parametrize("backend", ["device", "mesh"])
def test_stale_run_on_refreshed_backend_raises(backend):
    store, masks = _mk_memory()
    run = _partial_run(store, backend=backend)
    store.update([0], np.clip(masks[:1] * 0.5, 0, 1))
    assert not run.resumable()
    with pytest.raises(StaleRunError):
        run.ensure(6)


def test_disk_run_staleness_tracks_dirty_ids(tmp_path):
    masks, meta = _data(12, seed=3)
    root = str(tmp_path / "db")
    store = MaskStore.create_disk(root, masks, meta, CFG)

    # run restricted to the first half; dirty the second half → untouched
    run = _partial_run(store, positions=np.arange(6))
    store.update([10, 11], np.clip(masks[[10, 11]] * 0.5, 0, 1))
    assert run.resumable()
    run.ensure(6)                                     # finishes cleanly

    # second run: dirty a mask still pending → clean StaleRunError
    run2 = _partial_run(store, positions=np.arange(6))
    rest = run2.pending[run2.cursor:]
    if not len(rest):
        pytest.skip("bounds decided everything; nothing pending")
    dirty_pos = int(run2.ctx.positions[rest[0]])
    dirty_id = int(store.meta["mask_id"][dirty_pos])
    store.update([dirty_id], np.clip(masks[[dirty_pos]] * 0.5, 0, 1))
    assert not run2.resumable()
    with pytest.raises(StaleRunError):
        run2.ensure(6)


# ---------------------------------------------------------------------------
# shared-load cache: invalidation, bound + eviction (satellite)
# ---------------------------------------------------------------------------


def test_shared_cache_invalidates_updated_positions():
    store, masks = _mk_memory()
    store.enable_cache()
    store.load(np.array([0, 1, 2]))
    new = np.clip(masks[[1]] * 0.25, 0, 1)
    store.update([1], new)
    assert store.cache_stats.invalidations == 1
    out = store.load(np.array([0, 1, 2]))
    np.testing.assert_array_equal(out[1], new[0])     # fresh bytes, not cache
    np.testing.assert_array_equal(out, store.resident_masks()[:3])


def test_shared_cache_capacity_bound_and_eviction():
    store, masks = _mk_memory()
    row_bytes = H * W * 4
    assert store.enable_cache(capacity_bytes=4 * row_bytes)
    store.load(np.arange(4))                          # fills the capacity
    store.load(np.arange(4, 8))                       # 4 misses → 4 evictions
    assert store.cache_stats.evictions == 4
    assert store._cache_used <= 4
    # correctness under eviction churn
    for lo in (0, 4, 2, 6):
        out = store.load(np.arange(lo, lo + 4))
        np.testing.assert_array_equal(out, masks[lo:lo + 4])


def test_shared_cache_remaps_across_append_and_delete():
    store, masks = _mk_memory()
    store.enable_cache()
    store.load(np.arange(6))
    add_masks, add_meta = _data(3, seed=5, id_base=700)
    store.append(add_masks, add_meta)
    assert len(store._cache_map) == len(store)
    out = store.load(np.array([B, B + 1]))            # the appended rows
    np.testing.assert_array_equal(out, add_masks[:2])

    hits_before = store.cache_stats.hits
    store.delete([0, 2])                              # renumber positions
    out = store.load(store.positions_of([1, 3, 4]))
    np.testing.assert_array_equal(out, masks[[1, 3, 4]])
    # surviving rows still count as hits — the bytes never re-read
    assert store.cache_stats.hits > hits_before


# ---------------------------------------------------------------------------
# service: no pre-epoch cache entry is ever served
# ---------------------------------------------------------------------------


def test_service_result_and_bounds_caches_roll_with_epoch():
    store, masks = _mk_memory()
    svc = MaskSearchService(store)
    sql = TOPK_SQL.format(k=5)
    out1 = svc.query(sql)
    assert svc.query(sql)["cache_hit"]

    # refined query hits the bounds cache within one epoch
    refined = ("SELECT mask_id FROM MasksDatabaseView WHERE "
               "CP(mask, full_img, (0.2, 0.6)) > {};")
    svc.query(refined.format(50))
    hits0 = svc.planner.bounds_cache.info.hits
    svc.query(refined.format(80))
    assert svc.planner.bounds_cache.info.hits == hits0 + 1

    # mutation: every pre-epoch entry becomes unreachable
    r = svc.ingest(np.clip(masks[:3][:, ::-1] * 0.7, 0, 1),
                   mask_ids=[0, 1, 2], on_conflict="update")
    assert r["updated"] == 3 and svc.store.epoch == 1
    info = svc.planner.bounds_cache.info
    hits1, misses1 = info.hits, info.misses
    svc.query(refined.format(90))                     # only epoch-0 entries
    assert info.hits == hits1 and info.misses == misses1 + 1
    out2 = svc.query(sql)
    assert not out2["cache_hit"]

    # the recomputed result matches a from-scratch database
    fresh = MaskStore.create_memory(store.resident_masks(),
                                    store.meta.copy(), CFG)
    ref = MaskSearchService(fresh).query(sql)
    assert out2["ids"] == ref["ids"] and out2["scores"] == ref["scores"]
    svc.close()


def test_session_pages_stay_on_pinned_epoch():
    store, masks = _mk_memory()
    svc = MaskSearchService(store)
    sql = TOPK_SQL.format(k=9)
    full = svc.query(sql)                              # pre-mutation truth
    page = svc.query(sql, session=True, page_size=3)
    sid = page["session"]
    got = list(page["page"]["ids"])
    svc.ingest(np.clip(masks[:4] * 0.1, 0, 1), mask_ids=[0, 1, 2, 3],
               on_conflict="update")
    for _ in range(2):
        nxt = svc.next_page(sid)
        got.extend(nxt["page"]["ids"])
    assert got == full["ids"]                          # snapshot-consistent

    # fused multi-session paging reports staleness per session instead of
    # silently mixing epochs (device-resident backends can't snapshot)
    out = svc.next_pages({sid: None})
    assert "page" in out[sid] or out[sid].get("stale")
    svc.close()


def test_failed_batch_is_not_dropped_on_stale_error(tmp_path):
    """A StaleRunError mid-batch must leave the batch pending: a retried
    ensure() raises again rather than finishing with the lost batch's
    candidates silently missing (regression: take_batch used to commit
    the cursor before verification succeeded)."""
    masks, meta = _data(12, seed=3)
    root = str(tmp_path / "db")
    store = MaskStore.create_disk(root, masks, meta, CFG)
    run = _partial_run(store)
    rest = run.pending[run.cursor:]
    if not len(rest):
        pytest.skip("bounds decided everything; nothing pending")
    dirty_pos = int(run.ctx.positions[rest[0]])
    store.update([int(store.meta["mask_id"][dirty_pos])],
                 np.clip(masks[[dirty_pos]] * 0.5, 0, 1))
    n_verified = run.stats.n_verified
    for _ in range(2):                                 # retries keep failing
        with pytest.raises(StaleRunError):
            run.ensure(6)
        assert run.stats.n_verified == n_verified
    assert not run.resumable()                         # never "finishes"


def test_append_capacity_survives_update_and_delete():
    """update/delete replace the mask buffer copy-on-write but keep its
    spare capacity, so the model-iteration loop (update → append → …)
    pays O(delta) appends, not an O(B) regrow each time."""
    store, masks = _mk_memory()
    add_masks, add_meta = _data(4, seed=6, id_base=400)
    store.append(add_masks, add_meta)                  # grows capacity ≥ 2B
    cap = len(store._masks_buf)
    assert cap > len(store)
    store.update([0, 1], np.clip(masks[:2] * 0.5, 0, 1))
    assert len(store._masks_buf) == cap                # capacity retained
    buf = store._masks_buf
    more_masks, more_meta = _data(3, seed=7, id_base=500)
    store.append(more_masks, more_meta)
    assert store._masks_buf is buf                     # no regrow needed
    store.delete([400, 401])
    assert len(store._masks_buf) == cap


def test_service_delete_reports_unique_count():
    store, _ = _mk_memory()
    svc = MaskSearchService(store)
    out = svc.delete([3, 3, 5])
    assert out["deleted"] == 2 and out["n_masks"] == B - 2
    svc.close()


def test_finished_device_session_pages_after_mutation():
    """A device-backend run with no verification work left is resumable
    after a mutation — its results are run-local (regression: the stale
    precheck used to reject it before checking finished())."""
    store, masks = _mk_memory()
    run = TopKRun(store, CP(None, 0.2, 0.6), verify_batch=len(store),
                  backend="device")
    run.ensure(6)                                     # everything verified
    svc_like_ids, _ = run.result()
    store.append(*_data(2, seed=8, id_base=900))
    assert not run.fresh() and run.resumable()
    run.ensure(6)                                     # no-op, no raise
    got_ids, _ = run.result()
    np.testing.assert_array_equal(got_ids, svc_like_ids)


def test_ingest_update_applies_supplied_metadata():
    """on_conflict='update' must apply caller-supplied meta fields to the
    existing rows (omitted fields keep their values) — a retrained
    model's masks re-ingest under a new model_id."""
    store, masks = _mk_memory()
    svc = MaskSearchService(store)
    before = store.meta[store.positions_of([1, 2])].copy()
    svc.ingest(np.clip(masks[[1, 2]] * 0.5, 0, 1), mask_ids=[1, 2],
               model_ids=7, on_conflict="update")
    after = store.meta[store.positions_of([1, 2])]
    assert list(after["model_id"]) == [7, 7]
    np.testing.assert_array_equal(after["image_id"], before["image_id"])
    np.testing.assert_array_equal(after["mask_type"], before["mask_type"])
    # bytes-only upsert leaves metadata untouched
    svc.ingest(np.clip(masks[[1]] * 0.25, 0, 1), mask_ids=[1],
               on_conflict="update")
    assert store.meta[store.positions_of([1])[0]]["model_id"] == 7
    svc.close()


def test_service_ingest_append_and_delete():
    store, _ = _mk_memory()
    svc = MaskSearchService(store)
    r = svc.ingest(np.zeros((2, H, W), np.float32), image_ids=[90, 90])
    assert r["appended"] == 2 and r["n_masks"] == B + 2
    assert r["mask_ids"] == [B, B + 1]                 # auto-assigned
    with pytest.raises(ValueError):
        svc.ingest(np.zeros((1, H, W)), mask_ids=[0])  # on_conflict=error
    d = svc.delete([B, B + 1])
    assert d["n_masks"] == B and d["epoch"] == 2
    assert svc.stats()["epoch"] == 2
    svc.close()


# ---------------------------------------------------------------------------
# planner LRU thread-safety (satellite)
# ---------------------------------------------------------------------------


def test_lru_cache_concurrent_access():
    cache = LRUCache(32)
    errors = []

    def worker(seed):
        rng = np.random.default_rng(seed)
        try:
            for _ in range(3000):
                k = f"k{int(rng.integers(100))}"
                if rng.random() < 0.5:
                    cache.put(k, rng.integers(1000))
                else:
                    cache.get(k)
        except Exception as e:                          # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert len(cache) <= 32
    assert cache.info.size == len(cache)


# ---------------------------------------------------------------------------
# bitpacked binary-mask tier: mutation + persistence (DESIGN.md §12)
# ---------------------------------------------------------------------------


def _binary_data(n, seed=0, id_base=0, h=H, w=W):
    masks, meta = _data(n, seed=seed, id_base=id_base, h=h, w=w)
    return (masks > 0.5).astype(np.float32), meta


def test_packed_mutation_sequence_matches_float_rebuild():
    """append/update/delete on a packed store: the chunked CHI always equals
    a from-scratch float build, the packed words always unpack to the
    current masks, and queries match a fresh float store bit-for-bit."""
    from repro.core.packing import unpack_masks

    masks, meta = _binary_data(B)
    store = MaskStore.create_memory(masks, meta, CFG, packed=True)
    assert store.packed and store.row_nbytes == H * ((W + 31) // 32) * 4
    current = masks.copy()
    ids = list(range(B))
    next_id = 1000
    rng = np.random.default_rng(7)
    for step in range(6):
        op = rng.integers(3)
        if op == 0:                                        # append
            add, ameta = _binary_data(2, seed=50 + step, id_base=next_id)
            next_id += 2
            store.append(add, ameta)
            current = np.concatenate([current, add])
            ids.extend(ameta["mask_id"])
        elif op == 1:                                      # update
            sel = rng.choice(len(ids), size=2, replace=False)
            new = (rng.random((2, H, W)) < 0.5).astype(np.float32)
            store.update([ids[i] for i in sel], new)
            current[sel] = new
        elif len(ids) > 4:                                 # delete
            sel = np.sort(rng.choice(len(ids), size=2, replace=False))[::-1]
            store.delete([ids[i] for i in sel])
            keep = np.ones(len(ids), bool)
            keep[sel] = False
            current = current[keep]
            ids = [m for i, m in enumerate(ids) if keep[i]]
        np.testing.assert_array_equal(store.chi_host(),
                                      build_chi_np(current, CFG))
        np.testing.assert_array_equal(
            unpack_masks(store.resident_masks(), W), current)
        fmeta = np.zeros(len(ids), MASK_META_DTYPE)
        fmeta["mask_id"] = ids
        fresh = MaskStore.create_memory(current, fmeta, CFG)
        plan = LogicalPlan(order_by=CP(None, 0.5, 1.5),
                           k=min(5, max(len(ids), 1)))
        (got_ids, got_scores), _ = run_plan(store, plan)
        (ref_ids, ref_scores), _ = run_plan(fresh, plan)
        np.testing.assert_array_equal(got_ids, ref_ids)
        np.testing.assert_array_equal(got_scores, ref_scores)
    # the binary contract survives mutation: grayscale bytes refuse
    with pytest.raises(ValueError, match="binary"):
        store.update([ids[0]], np.full((1, H, W), 0.5, np.float32))
    with pytest.raises(ValueError, match="binary"):
        store.append(np.full((1, H, W), 0.25, np.float32),
                     _binary_data(1, id_base=9000)[1])


def test_packed_disk_roundtrip_preserves_flag_and_words(tmp_path):
    from repro.core.packing import unpack_masks

    masks, meta = _binary_data(10, seed=3)
    root = str(tmp_path / "pdb")
    store = MaskStore.create_disk(root, masks, meta, CFG, packed=True)
    add_masks, add_meta = _binary_data(4, seed=9, id_base=500)
    store.append(add_masks, add_meta)
    new = (np.arange(H * W).reshape(H, W) % 3 == 0)[None].astype(np.float32)
    store.update([1], new)
    current = np.concatenate([masks, add_masks])
    current[1] = new[0]

    re = MaskStore.open_disk(root)
    assert re.packed and re.epoch == 2 and re.cfg == CFG
    assert re.row_nbytes == store.row_nbytes
    np.testing.assert_array_equal(unpack_masks(re.load_all(), W), current)
    np.testing.assert_array_equal(re.chi_host(), build_chi_np(current, CFG))
    # metered IO is packed bytes: one row load costs row_nbytes, not H*W*4
    io0 = re.io.bytes_read
    re.load(np.array([0]))
    assert re.io.bytes_read - io0 == re.row_nbytes < H * W * 4


def test_stale_run_error_surfaces_as_conflict():
    """A filter predicate whose residue needs rewritten disk bytes reports
    StaleRunError (never silently mixes epochs) through run_plan too."""
    store, masks = _mk_memory()
    run = _partial_run(store, backend="device")
    store.delete([0])
    with pytest.raises(StaleRunError):
        run.ensure(6)
    # but a fresh plan over the mutated store is fine on every backend
    plan = LogicalPlan(predicate=Cmp(CP(None, 0.2, 0.6), ">", 100.0))
    for backend in ("host", "device", "mesh"):
        run_plan(store, plan, backend=backend)


@pytest.mark.parametrize("packed,size,row", [
    (False, 32, (8, 128)), (True, 32, (32,)), (False, 36, (36 * 36,))],
    ids=["float", "packed", "float_rows"])
def test_device_rows_follow_append_update_delete(packed, size, row):
    """The device tier holds one mask per leading index: packed words as
    2-D rows ``(n, H·W')``, float pixels in lanes ``(n, H·W/128, 128)``
    where ``H·W`` is a multiple of 128 (32×32) and as 2-D rows where it is
    not (36×36).  After each append, update and delete it equals the host
    copy mask for mask, and the device backend answers a plan through each
    of its gathering steps (fused CP counts, the megakernel, MASK_AGG
    groups, the pair pass) exactly as the host backend does."""
    from repro.core.backend import get_backend
    from repro.core.exprs import AggCP, pair_iou

    def data(n, seed=0, id_base=0):
        make = _binary_data if packed else _data
        return make(n, seed=seed, id_base=id_base, h=size, w=size)

    cfg = CHIConfig(grid=4, num_bins=8, height=size, width=size)
    masks, meta = data(B)
    store = MaskStore.create_memory(masks, meta, cfg, packed=packed)
    get_backend(store, "device")          # the resident upload, epoch 0
    plans = [
        LogicalPlan(order_by=CP(None, 0.5, 1.5), k=5),
        LogicalPlan(predicate=Cmp(CP((4, 4, 28, 28), 0.5, 1.5), ">", 40.0),
                    order_by=CP((2, 6, 30, 26), 0.5, 1.5), k=4),
        LogicalPlan(select="image_id", order_by=AggCP("intersect", 0.5, None),
                    k=3),
        LogicalPlan(order_by=pair_iou(1, 2, 0.6, 0.6), k=3, desc=False),
    ]
    rng = np.random.default_rng(11)

    def check():
        n = len(store)
        rows = np.asarray(store.device_masks())
        assert rows.shape == (n,) + row
        np.testing.assert_array_equal(
            rows.reshape((n,) + store.row_shape), store.resident_masks())
        verified = 0
        for plan in plans:
            (want, want_scores), want_stats = run_plan(store, plan,
                                                       verify_batch=4)
            (got, got_scores), got_stats = run_plan(store, plan,
                                                    verify_batch=4,
                                                    backend="device")
            assert list(got) == list(want), plan
            np.testing.assert_array_equal(got_scores, want_scores)
            assert got_stats.n_verified == want_stats.n_verified, plan
            verified += got_stats.n_verified
        assert verified > 0

    add, add_meta = data(4, seed=3, id_base=1000)
    store.append(add, add_meta)
    check()
    upd = rng.choice(store.mask_ids, size=3, replace=False)
    store.update(upd, data(3, seed=5)[0])
    check()
    store.delete(rng.choice(store.mask_ids, size=2, replace=False))
    check()
