"""Plan-IR tests: predicate trees, filtered top-k, the unified Run API, and
the compat shim — including the PR's acceptance query.

Key invariants:
  * the ISSUE acceptance query parses, prunes through the predicate tree
    (``n_verified < n_candidates``), and matches the full-scan baseline;
  * randomized predicate-tree plans always agree with ``use_index=False``
    (and three-valued bounds decisions are individually sound);
  * legacy ``Query.run`` results are bit-identical to the engine functions
    they used to call directly;
  * SCALAR_AGG over an empty candidate set returns NaN, never raises.
"""

import numpy as np
import pytest

from repro.core import CHIConfig, MaskStore, engine, queries
from repro.core.engine import (FilteredTopKRun, FilterRun, MinMaxAggRun,
                               ScalarAggRun, TopKRun)
from repro.core.exprs import (And, BinOp, Cmp, CP, MaskEvalContext,
                              Not, Or, RoiArea, TypeIn)
from repro.core.plan import LogicalPlan, compile_plan, run_plan, \
    simplify_predicate
from repro.core.store import MASK_META_DTYPE
from repro.data.masks import object_boxes, saliency_masks

B, H, W = 48, 192, 192

ACCEPTANCE_SQL = (
    "SELECT mask_id FROM MasksDatabaseView "
    "WHERE CP(mask, roi, (0.8, 1.0)) > 500 "
    "AND NOT CP(mask, full_img, (0.2, 0.6)) < 100 "
    "ORDER BY CP(mask, full_img, (0.2, 0.6)) DESC LIMIT 25")


@pytest.fixture(scope="module")
def db():
    rois = object_boxes(B, H, W, seed=2)
    masks, _ = saliency_masks(B, H, W, seed=1, attacked_fraction=0.3,
                              boxes=rois, in_box_fraction=0.8)
    meta = np.zeros(B, MASK_META_DTYPE)
    meta["mask_id"] = np.arange(B) + 1000
    meta["image_id"] = np.arange(B) // 2
    meta["mask_type"] = np.arange(B) % 2 + 1
    cfg = CHIConfig(grid=8, num_bins=16, height=H, width=W)
    return MaskStore.create_memory(masks, meta, cfg), rois


@pytest.fixture(scope="module")
def small_db():
    b, h, w = 24, 32, 32
    rois = object_boxes(b, h, w, seed=5)
    masks, _ = saliency_masks(b, h, w, seed=4, attacked_fraction=0.25,
                              boxes=rois)
    meta = np.zeros(b, MASK_META_DTYPE)
    meta["mask_id"] = np.arange(b)
    meta["image_id"] = np.arange(b) // 2
    meta["mask_type"] = np.arange(b) % 3 + 1
    cfg = CHIConfig(grid=4, num_bins=8, height=h, width=w)
    return MaskStore.create_memory(masks, meta, cfg), rois


# -- the acceptance query ----------------------------------------------------


def test_acceptance_query_parses_prunes_and_matches_baseline(db):
    store, rois = db
    q = queries.parse(ACCEPTANCE_SQL)
    assert q.kind == "filtered_topk" and q.k == 25 and q.desc

    (ids, scores), stats = q.run(store, provided_rois=rois, verify_batch=8)
    assert len(ids) > 0
    assert stats.n_verified < stats.n_candidates  # predicate-tree pruning
    (ids0, scores0), stats0 = q.run(store, provided_rois=rois,
                                    use_index=False)
    assert list(ids) == list(ids0)
    np.testing.assert_allclose(scores, scores0)
    assert stats0.n_verified == stats0.n_candidates == B


def test_existing_flat_callers_unchanged(db):
    """`queries.run()` keeps its one-shot signature and result shapes."""
    store, rois = db
    (ids, scores), stats = queries.run(queries.SCENARIO2_TOPK, store)
    assert len(ids) == 25 and len(scores) == 25
    ids_f, stats_f = queries.run(
        "SELECT mask_id FROM V WHERE CP(mask, full_img, (0.2, 0.6)) "
        "> 300;", store)
    assert stats_f.n_candidates == B
    value, _ = queries.run(
        "SELECT SCALAR_AGG(AVG, CP(mask, full_img, (0.5, 1.0))) FROM V;",
        store)
    assert np.isfinite(value)


# -- compat shim: bit-identical to the engine functions ----------------------


def test_query_run_filter_bit_identical(db):
    store, rois = db
    sql = ("SELECT mask_id FROM MasksDatabaseView WHERE "
           "CP(mask, roi, (0.8, 1.0)) / AREA(roi) < 0.05;")
    q = queries.parse(sql)
    ids_q, _ = q.run(store, provided_rois=rois)
    expr = BinOp("/", CP("provided", 0.8, 1.0), RoiArea("provided"))
    ids_e, _ = engine.filter_query(store, expr, "<", 0.05,
                                   provided_rois=rois)
    np.testing.assert_array_equal(ids_q, ids_e)


def test_query_run_topk_bit_identical(db):
    store, rois = db
    q = queries.parse(queries.SCENARIO2_TOPK)
    (ids_q, scores_q), _ = q.run(store)
    ids_e, scores_e, _ = engine.topk_query(store, CP(None, 0.2, 0.6), 25,
                                           desc=True)
    np.testing.assert_array_equal(ids_q, ids_e)
    np.testing.assert_array_equal(scores_q, scores_e)


@pytest.mark.parametrize("agg", ["SUM", "AVG", "MIN", "MAX"])
def test_query_run_scalar_agg_bit_identical(db, agg):
    store, _ = db
    q = queries.parse(f"SELECT SCALAR_AGG({agg}, "
                      "CP(mask, full_img, (0.4, 0.8))) FROM V;")
    value_q, _ = q.run(store)
    value_e, _ = engine.scalar_agg(store, CP(None, 0.4, 0.8), agg)
    assert value_q == value_e


def test_query_field_mutation_seen_at_run_time(db):
    """Pre-redesign callers mutate the flat fields after parse() and re-run;
    the shim must rebuild the plan from the current fields."""
    store, _ = db
    q = queries.parse("SELECT mask_id FROM V WHERE "
                      "CP(mask, full_img, (0.2, 0.6)) > 100;")
    q.threshold = 2000.0
    ids, _ = q.run(store)
    ids_e, _ = engine.filter_query(store, CP(None, 0.2, 0.6), ">", 2000.0)
    np.testing.assert_array_equal(ids, ids_e)

    q2 = queries.parse(queries.SCENARIO2_TOPK)
    q2.k = 7
    q2.desc = False
    (ids2, scores2), _ = q2.run(store)
    ids_e2, scores_e2, _ = engine.topk_query(store, CP(None, 0.2, 0.6), 7,
                                             desc=False)
    np.testing.assert_array_equal(ids2, ids_e2)
    np.testing.assert_array_equal(scores2, scores_e2)


def test_query_run_forwards_positions(db):
    """Pre-redesign Query.run forwarded positions= to the engine."""
    store, _ = db
    rows = np.arange(0, B, 3)
    q = queries.parse("SELECT mask_id FROM V WHERE "
                      "CP(mask, full_img, (0.2, 0.6)) > 300;")
    ids, stats = q.run(store, positions=rows)
    ids_e, _ = engine.filter_query(store, CP(None, 0.2, 0.6), ">", 300.0,
                                   positions=rows)
    np.testing.assert_array_equal(ids, ids_e)
    assert stats.n_candidates == len(rows)


def test_programmatic_image_id_plan_groups(db):
    """select="image_id" implies grouping even without group_by_image —
    a hand-built plan must not silently return mask ids."""
    store, _ = db
    from repro.core.exprs import AggCP
    plan = LogicalPlan(select="image_id", order_by=AggCP("union", 0.8, None),
                       k=5)
    assert plan.grouped
    (ids, scores), _ = run_plan(store, plan)
    image_ids = set(int(x) for x in np.unique(store.meta["image_id"]))
    assert set(int(x) for x in ids) <= image_ids
    (ids0, scores0), _ = run_plan(store, plan, use_index=False)
    assert list(ids) == list(ids0)
    np.testing.assert_allclose(scores, scores0)


def test_hand_built_query_derives_plan(db):
    """Legacy code paths that construct Query records directly still run."""
    store, _ = db
    q = queries.Query(kind="topk", select="mask_id", expr=CP(None, 0.2, 0.6),
                      k=5, desc=True)
    (ids, scores), _ = q.run(store)
    ids_e, scores_e, _ = engine.topk_query(store, CP(None, 0.2, 0.6), 5)
    np.testing.assert_array_equal(ids, ids_e)


# -- the unified Run API -----------------------------------------------------


def test_compile_plan_kinds(db):
    store, rois = db
    pred = Cmp(CP(None, 0.2, 0.6), ">", 300.0)
    rank = CP(None, 0.5, 1.0)
    cases = [
        (LogicalPlan(predicate=pred), FilterRun),
        (LogicalPlan(order_by=rank, k=5), TopKRun),
        (LogicalPlan(predicate=pred, order_by=rank, k=5), FilteredTopKRun),
        (LogicalPlan(agg="AVG", agg_expr=rank), ScalarAggRun),
        (LogicalPlan(agg="MAX", agg_expr=rank), MinMaxAggRun),
    ]
    for plan, run_cls in cases:
        run = compile_plan(store, plan, provided_rois=rois)
        assert isinstance(run, run_cls), plan.kind
        # the uniform surface
        run.target(plan.k)
        while not run.finished():
            batch = run.take_batch()
            if not len(batch):
                break
            run.self_verify(batch)
        run.result()


def test_shared_expression_keeps_partial_row_loads(db):
    """Filtering and ranking by the *same* expression is one distinct CP
    term — the ROI-row partial-load optimization must stay enabled, and
    self-verification must evaluate the shared term once per batch."""
    store, rois = db
    expr = CP(None, 0.2, 0.6)
    run = FilteredTopKRun(store, Cmp(expr, ">", 100.0), expr, desc=True,
                          verify_batch=8)
    assert run.ctx.partial_rows
    run.ensure(5)
    ids, scores = run.result()
    ids0, scores0, _ = engine.filtered_topk_query(
        store, Cmp(expr, ">", 100.0), expr, 5, desc=True, use_index=False)
    assert list(ids) == list(ids0)
    np.testing.assert_allclose(scores, scores0)


def test_min_max_respects_grouping(small_db):
    """compile_plan must not drop group_by_image for MIN/MAX (it groups the
    candidate set exactly like SUM/AVG does)."""
    store, _ = small_db
    from repro.core.exprs import AggCP
    expr = AggCP("union", 0.8, None)
    plan = LogicalPlan(agg="MAX", agg_expr=expr, group_by_image=True)
    run = compile_plan(store, plan)
    assert run.n == len(np.unique(store.meta["image_id"]))
    run.ensure(1)
    value = run.result()
    value_e, _ = engine.scalar_agg(store, expr, "MAX")
    assert value == value_e


def test_filtered_topk_resumable_target_growth(db):
    """target(k) can grow: pagination over a filtered ranking equals the
    one-shot larger LIMIT (same contract TopKRun has)."""
    store, rois = db
    pred = Cmp(CP("provided", 0.8, 1.0), ">", 200.0)
    rank = CP(None, 0.2, 0.6)
    run = FilteredTopKRun(store, pred, rank, desc=True, provided_rois=rois,
                          verify_batch=4)
    run.ensure(3)
    first3 = run.result()
    run.ensure(9)
    ids9, scores9 = run.result()
    ids_one, scores_one, _ = engine.filtered_topk_query(
        store, pred, rank, 9, desc=True, provided_rois=rois)
    assert list(ids9) == list(ids_one)
    np.testing.assert_allclose(scores9, scores_one)
    assert list(first3[0]) == list(ids9[:3])


def test_simplify_predicate_extracts_type_conjuncts():
    cp = Cmp(CP(None, 0.0, 0.5), ">", 1.0)
    types, residue = simplify_predicate(
        And(TypeIn((1, 2)), And(cp, TypeIn((2, 3)))))
    assert types == (2,)
    assert residue == cp
    types2, residue2 = simplify_predicate(Or(TypeIn((1,)), cp))
    assert types2 is None and isinstance(residue2, Or)


def test_type_in_below_not_executes(small_db):
    store, _ = small_db
    q = queries.parse("SELECT mask_id FROM V WHERE "
                      "NOT mask_type IN (1) AND "
                      "CP(mask, full_img, (0.0, 1.0)) >= 0;")
    ids, _ = q.run(store)
    types = store.meta["mask_type"][store.positions_of(ids)]
    assert len(ids) > 0 and np.all(types != 1)


# -- empty candidate sets ----------------------------------------------------


@pytest.mark.parametrize("agg,want_nan", [("MIN", True), ("MAX", True),
                                          ("AVG", True), ("SUM", False)])
def test_scalar_agg_empty_candidate_set(small_db, agg, want_nan):
    store, _ = small_db
    value, stats = engine.scalar_agg(store, CP(None, 0.2, 0.6), agg,
                                     mask_types=(99,))
    assert stats.n_candidates == 0
    if want_nan:
        assert np.isnan(value)
    else:
        assert value == 0.0
    # and through SQL, where it used to IndexError
    q = queries.parse(f"SELECT SCALAR_AGG({agg}, "
                      "CP(mask, full_img, (0.2, 0.6))) FROM V "
                      "WHERE mask_type IN (99);")
    value_q, _ = q.run(store)
    assert (np.isnan(value_q) if want_nan else value_q == 0.0)


def test_filtered_topk_empty_result(small_db):
    store, rois = small_db
    q = queries.parse(
        "SELECT mask_id FROM V WHERE CP(mask, full_img, (0.0, 1.0)) < -1 "
        "ORDER BY CP(mask, full_img, (0.2, 0.6)) DESC LIMIT 5;")
    (ids, scores), stats = q.run(store, provided_rois=rois)
    assert len(ids) == 0 and len(scores) == 0


# -- randomized plan equivalence (numpy fallback; hypothesis version in
#    test_plan_properties.py) -------------------------------------------------


def _random_expr(rng):
    ranges = [(0.0, 0.3), (0.2, 0.6), (0.5, 1.0), (0.8, 1.0)]
    rois = [None, "provided", (4, 4, 28, 28)]
    lv, uv = ranges[rng.integers(len(ranges))]
    roi = rois[rng.integers(len(rois))]
    base = CP(roi, lv, uv)
    if rng.random() < 0.3:
        return BinOp("/", base, RoiArea(roi))
    if rng.random() < 0.3:
        lv2, uv2 = ranges[rng.integers(len(ranges))]
        op = "+-*"[rng.integers(3)]
        return BinOp(op, base, CP(rois[rng.integers(len(rois))], lv2, uv2))
    return base


def _random_pred(rng, depth=0):
    if depth < 2 and rng.random() < 0.55:
        kind = rng.integers(3)
        if kind == 0:
            return And(_random_pred(rng, depth + 1),
                       _random_pred(rng, depth + 1))
        if kind == 1:
            return Or(_random_pred(rng, depth + 1),
                      _random_pred(rng, depth + 1))
        return Not(_random_pred(rng, depth + 1))
    expr = _random_expr(rng)
    op = ("<", "<=", ">", ">=")[rng.integers(4)]
    threshold = float(rng.choice([0.0, 0.02, 10.0, 100.0, 400.0]))
    return Cmp(expr, op, threshold)


def test_random_predicate_plans_match_baseline(small_db):
    store, rois = small_db
    rng = np.random.default_rng(0)
    for trial in range(20):
        pred = _random_pred(rng)
        # three-valued bounds decisions are individually sound
        ctx = MaskEvalContext(store, np.arange(len(store)), rois,
                              partial_rows=False)
        accept, reject = pred.decide(ctx.bounds, ctx)
        exact = pred.exact(ctx, np.arange(len(store)))
        assert np.all(exact[accept]), f"trial {trial}: accept unsound"
        assert not np.any(exact[reject]), f"trial {trial}: reject unsound"
        assert not np.any(accept & reject), f"trial {trial}: contradiction"
        # full plan equals the full-scan baseline
        plan = LogicalPlan(predicate=pred)
        ids, _ = run_plan(store, plan, provided_rois=rois, verify_batch=5)
        ids0, _ = run_plan(store, plan, provided_rois=rois, use_index=False)
        assert sorted(ids) == sorted(ids0), f"trial {trial}"


def test_random_filtered_topk_plans_match_baseline(small_db):
    store, rois = small_db
    rng = np.random.default_rng(1)
    for trial in range(12):
        pred = _random_pred(rng)
        rank = _random_expr(rng)
        desc = bool(rng.integers(2))
        plan = LogicalPlan(predicate=pred, order_by=rank, k=5, desc=desc)
        (ids, scores), _ = run_plan(store, plan, provided_rois=rois,
                                    verify_batch=3)
        (ids0, scores0), _ = run_plan(store, plan, provided_rois=rois,
                                      use_index=False)
        assert list(ids) == list(ids0), f"trial {trial}"
        np.testing.assert_allclose(scores, scores0, err_msg=f"trial {trial}")


def test_division_bounds_contain_zero_denominator_quotient():
    """x / 0 evaluates to 0, so a denominator whose lower bound is 0 must
    leave the quotient's lower bound at 0 — else the index prunes a mask
    whose exact score is 0 (a 0/0 pair ratio) from an ascending ranking."""
    from repro.core.exprs import _exact_binop, _interval_binop
    llb, lub = np.array([5.0, 0.0, 5.0]), np.array([10.0, 0.0, 10.0])
    rlb, rub = np.array([0.0, 0.0, 2.0]), np.array([4.0, 3.0, 4.0])
    lb, ub = _interval_binop("/", llb, lub, rlb, rub)
    np.testing.assert_array_equal(lb, [0.0, 0.0, 1.25])
    np.testing.assert_array_equal(ub, [np.inf, 0.0, 5.0])
    exact = _exact_binop("/", np.array([7.0]), np.array([0.0]))
    assert lb[0] <= exact[0] <= ub[0]
