"""CHI pyramid + cost-based filter ordering (core/opt.py, DESIGN.md §13):
tier nesting, disk/mutation round-trips, bit-identity of the refinement
ladder across backends and representations, tier-aware cache keys, and the
EXPLAIN/metrics surfaces."""

import numpy as np
import pytest

from repro.core import exprs as E
from repro.core import opt
from repro.core.backend import get_backend
from repro.core.chi import CHIConfig, tier_slice
from repro.core.exprs import CP, And, Cmp, MaskEvalContext, Or, TypeIn
from repro.core.plan import LogicalPlan, run_plan
from repro.core.store import MASK_META_DTYPE, MaskStore
from repro.obs.explain import explain_analyze
from repro.obs.metrics import get_registry
from repro.service.planner import bounds_key

H = W = 64
INF = float("inf")


def _meta(b):
    meta = np.zeros(b, MASK_META_DTYPE)
    meta["mask_id"] = np.arange(b)
    meta["image_id"] = np.arange(b)
    meta["mask_type"] = np.arange(b) % 3 + 1
    return meta


def _masks(b, seed=0, skew=True):
    rng = np.random.default_rng(seed)
    m = rng.random((b, H, W)).astype(np.float32)
    if skew:
        m[: b // 2] *= 0.3          # half the store is low-valued
    return m


@pytest.fixture(scope="module")
def store16():
    b = 48
    masks = _masks(b, seed=1)
    # bin-edge masks: constant at each threshold and one float32 ulp around
    # it — the exact values the nextafter32 query-edge mapping must bound
    for i, t in enumerate((0.2, 0.5, 0.8)):
        masks[i] = np.float32(t)
        masks[i + 3] = np.nextafter(np.float32(t), np.float32(np.inf))
        masks[i + 6] = np.nextafter(np.float32(t), np.float32(-np.inf))
    cfg = CHIConfig(grid=16, num_bins=4, height=H, width=W,
                    thresholds=(0.2, 0.5, 0.8))
    return MaskStore.create_memory(masks, _meta(b), cfg)


def _exact_cp(masks, roi, lv, uv):
    r0, c0, r1, c1 = roi
    sub = masks[:, r0:r1, c0:c1]
    return ((sub >= lv) & (sub < uv)).sum(axis=(1, 2)).astype(np.float64)


def test_tier_nesting_contains_fine_and_exact(store16):
    """Every coarse-tier [lb, ub] contains the finer tier's interval and
    the exact CP value — the soundness-by-construction ladder invariant."""
    store = store16
    tiers = store.cfg.tier_grids
    assert tiers == (4, 8, 16)
    pos = np.arange(len(store))
    masks = store.load(pos)
    for roi in [(0, 0, H, W), (3, 5, 61, 59), (17, 2, 40, 33)]:
        for lv, uv in [(0.2, INF), (0.5, INF), (0.8, INF), (0.2, 0.5)]:
            expr = CP(roi, lv, uv)
            exact = _exact_cp(masks, roi, lv, uv)
            prev = None
            for g in tiers:
                ctx = MaskEvalContext(store, pos)
                ctx.tier = None if g == tiers[-1] else g
                lb, ub = ctx.bounds(expr)
                assert np.all(lb <= exact) and np.all(exact <= ub), \
                    (roi, lv, uv, g)
                if prev is not None:
                    plb, pub = prev
                    assert np.all(plb <= lb) and np.all(ub <= pub), \
                        f"tier {g} not nested in coarser interval"
                prev = (lb, ub)


def test_pyramid_tables_are_exact_tier_slices(store16):
    finest = store16.chi_host()
    for g in store16.cfg.tier_grids[:-1]:
        np.testing.assert_array_equal(
            store16.chi_tier_host(g),
            tier_slice(finest, store16.cfg.grid, g))


def test_pyramid_roundtrip_disk_and_mutation(tmp_path):
    b = 24
    cfg = CHIConfig(grid=8, num_bins=8, height=H, width=W)
    store = MaskStore.create_disk(tmp_path / "db", _masks(b, seed=3),
                                  _meta(b), cfg)
    store = MaskStore.open_disk(tmp_path / "db")

    def check(st):
        finest = st.chi_host()
        for g in st.cfg.tier_grids[:-1]:
            np.testing.assert_array_equal(
                st.chi_tier_host(g), tier_slice(finest, st.cfg.grid, g))

    check(store)
    extra = _masks(4, seed=4)
    emeta = _meta(4)
    emeta["mask_id"] += b
    emeta["image_id"] += b
    store.append(extra, emeta)
    check(store)
    store.update([1, 2], _masks(2, seed=5))
    check(store)
    store.delete([0, 5, b + 1])
    check(store)


def _skewed_pred():
    # conjunct 0: barely selective; conjunct 1: rejects nearly everything
    return And(Cmp(CP((0, 0, H, W), 0.2, INF), ">", 20.0),
               Cmp(CP((0, 0, H, W), 0.8, INF), ">", 790.0))


def _reassoc(pred):
    assert isinstance(pred, And)
    return And(pred.right, pred.left)


@pytest.mark.parametrize("backend", ["host", "device", "mesh"])
def test_ladder_bit_identity_across_backends(backend):
    b = 60
    cfg = CHIConfig(grid=8, num_bins=8, height=H, width=W)
    store = MaskStore.create_memory(_masks(b, seed=7), _meta(b), cfg)
    pred = And(_skewed_pred(), TypeIn((1, 2)))
    plan = LogicalPlan(predicate=pred)
    be = get_backend(store, backend)
    looked0 = be.chi_lookup_bytes
    with opt.configure(pyramid=False, reorder=False):
        ids_classic, st_classic = run_plan(store, plan, backend=backend)
    looked1 = be.chi_lookup_bytes
    with opt.configure(pyramid=True, reorder=True):
        ids_ladder, st_ladder = run_plan(store, plan, backend=backend)
        looked2 = be.chi_lookup_bytes
        ids_re, _ = run_plan(
            store, LogicalPlan(predicate=_reassoc(pred)), backend=backend)
    np.testing.assert_array_equal(ids_classic, ids_ladder)
    np.testing.assert_array_equal(sorted(ids_classic), sorted(ids_re))
    assert st_classic.n_decided_by_bounds == st_ladder.n_decided_by_bounds
    assert st_classic.n_verified == st_ladder.n_verified
    if backend != "device":
        assert st_ladder.chi_bytes <= st_classic.chi_bytes
        return
    # The device step looks up the same 16 entries per candidate at every
    # tier, so a coarse rung costs no fewer bytes than the finest: each
    # bounds pass counts 64 B per candidate, the classic path one pass per
    # CP conjunct over every candidate.
    assert st_classic.chi_bytes == looked1 - looked0 \
        == 64 * 2 * st_classic.n_candidates
    assert st_ladder.chi_bytes == looked2 - looked1 > 0


def test_ladder_bit_identity_packed():
    b = 40
    rng = np.random.default_rng(11)
    masks = (rng.random((b, H, W)) < 0.4).astype(np.float32)
    masks[: b // 3] = 0.0                      # skew: a third is empty
    cfg = CHIConfig(grid=8, num_bins=8, height=H, width=W)
    meta = _meta(b)
    fstore = MaskStore.create_memory(masks, meta, cfg)
    pstore = MaskStore.create_memory(masks, meta.copy(), cfg, packed=True)
    pred = And(Cmp(CP((0, 0, H, W), 0.5, 1.5), ">", 10.0),
               Cmp(CP((8, 8, 56, 56), 0.5, 1.5), ">", 1200.0))
    plan = LogicalPlan(predicate=pred)
    with opt.configure(pyramid=False, reorder=False):
        ids_f, _ = run_plan(fstore, plan)
    with opt.configure(pyramid=True, reorder=True):
        ids_fo, _ = run_plan(fstore, plan)
        ids_po, _ = run_plan(pstore, plan)
    np.testing.assert_array_equal(ids_f, ids_fo)
    np.testing.assert_array_equal(ids_f, ids_po)


def test_filtered_topk_identity_under_optimizer():
    b = 60
    cfg = CHIConfig(grid=8, num_bins=8, height=H, width=W)
    store = MaskStore.create_memory(_masks(b, seed=9), _meta(b), cfg)
    plan = LogicalPlan(predicate=_skewed_pred(),
                       order_by=CP((0, 0, H, W), 0.5, INF), k=7)
    with opt.configure(pyramid=False, reorder=False):
        (ids_c, sc_c), st_c = run_plan(store, plan)
    with opt.configure(pyramid=True, reorder=True):
        (ids_o, sc_o), st_o = run_plan(store, plan)
    np.testing.assert_array_equal(ids_c, ids_o)
    np.testing.assert_array_equal(sc_c, sc_o)
    assert st_c.n_verified == st_o.n_verified


def test_or_and_not_predicates_identical_under_optimizer():
    b = 48
    cfg = CHIConfig(grid=8, num_bins=8, height=H, width=W)
    store = MaskStore.create_memory(_masks(b, seed=13), _meta(b), cfg)
    preds = [
        Or(Cmp(CP((0, 0, H, W), 0.8, INF), ">", 790.0),
           Cmp(CP((0, 0, H, W), 0.2, INF), "<", 900.0)),
        And(E.Not(Cmp(CP((0, 0, H, W), 0.8, INF), ">", 790.0)),
            Cmp(CP((0, 0, H, W), 0.2, INF), ">", 20.0)),
    ]
    for pred in preds:
        plan = LogicalPlan(predicate=pred)
        with opt.configure(pyramid=False, reorder=False):
            ids_c, _ = run_plan(store, plan)
        with opt.configure(pyramid=True, reorder=True):
            ids_o, _ = run_plan(store, plan)
        np.testing.assert_array_equal(ids_c, ids_o)


def test_bounds_key_carries_tier_and_trailing_epoch():
    expr = CP((0, 0, H, W), 0.5, INF)
    plan = LogicalPlan(predicate=Cmp(expr, ">", 1.0))
    k4 = bounds_key(expr, plan, "none", "host", epoch=3, tier=4)
    k16 = bounds_key(expr, plan, "none", "host", epoch=3, tier=16)
    assert k4 != k16
    assert "|t4|" in k4 and "|t16|" in k16
    # the epoch must stay the trailing component (evict_dead_epochs
    # parses it off the end)
    assert k4.rsplit("|", 1)[-1] == "e3"
    assert k16.rsplit("|", 1)[-1] == "e3"


def test_explain_reports_ladder_and_order(store16):
    pred = And(Cmp(CP((0, 0, H, W), 0.5, INF), ">", 3500.0),
               Cmp(CP((0, 0, H, W), 0.2, INF), ">", 20.0))
    rep = explain_analyze(store16, LogicalPlan(predicate=pred))
    filt = next(c for c in rep["tree"]["children"] if c["op"] == "Filter")
    assert filt["tier_grids"] == [4, 8, 16]
    assert sorted(filt["order"]) == [0, 1]
    assert all("start_tier" in leaf for leaf in filt["leaves"])
    evaluated = [leaf for leaf in filt["leaves"] if leaf["evaluated"]]
    assert evaluated and all("actual_reject" in leaf for leaf in evaluated)
    chib = next(c for c in rep["tree"]["children"] if c["op"] == "CHIBounds")
    tier_rows = [r for r in chib["exprs"] if "tier" in r]
    assert tier_rows and all(r["chi_bytes"] > 0 for r in tier_rows)
    assert rep["stats"]["chi_bytes"] > 0
    assert "start_tier" in rep["text"]


def test_selectivity_error_histogram_observed(store16):
    fam = get_registry().histogram(
        "masksearch_selectivity_abs_error",
        "|estimated - actual| per-conjunct rejection-rate error")

    def count():
        return sum(child.count for _, child in fam.samples())

    before = count()
    run_plan(store16, LogicalPlan(predicate=_skewed_pred()))
    assert count() > before


def test_configure_restores_flags():
    assert opt.PYRAMID and opt.REORDER
    with opt.configure(pyramid=False, reorder=False):
        assert not opt.PYRAMID and not opt.REORDER
    assert opt.PYRAMID and opt.REORDER
