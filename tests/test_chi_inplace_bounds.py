"""The device CP-leaf bounds step reads the 16 CHI entries each candidate
needs at ``(pos, i, j, k)`` of the resident table, with no row gather
(``core/backend.py:_device_cp_bounds``, DESIGN.md §7).  Its (lb, ub) must
equal, bit for bit, the 8-corner lookups over the gathered rows, at every
table shape it is handed, and every backend's bounds must agree at every
pyramid tier.  The CHI bytes a bounds pass reports follow what it read:
64 B per candidate and CP leaf for the device step, whole rows for the
host and the mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CHIConfig, MaskStore, opt
from repro.core.backend import CHI_LOOKUPS, _device_cp_bounds, get_backend
from repro.core.chi import gather_rows
from repro.core.distributed import _bounds_from_corners, device_resolve
from repro.core.exprs import BinOp, Cmp, CP, MaskEvalContext, tier_context
from repro.core.plan import LogicalPlan, run_plan
from repro.core.store import MASK_META_DTYPE
from repro.service.api import MaskSearchService

H = W = 64
NB = 8
INF = float("inf")


@jax.jit
def _gathered_bounds(tables, pos, rois, rb, cb, ks):
    """The row-gather form of the step: the candidates' rows gathered
    through the 2-D view, then the same corner lookups over them."""
    corners, area = device_resolve(rois, rb, cb)
    return _bounds_from_corners(gather_rows(tables, pos), corners, area,
                                ks[0], ks[1], ks[2], ks[3])


def _rois(rng, p):
    r0 = rng.integers(0, H, p)
    c0 = rng.integers(0, W, p)
    rois = np.stack([r0, c0, r0 + rng.integers(0, H, p),
                     c0 + rng.integers(0, W, p)], 1)
    rois[0] = (0, 0, H, W)                   # the whole image
    rois[1] = (10, 10, 10, 30)               # empty: no rows
    rois[2] = (20, 40, 5, 50)                # empty: ends before it starts
    return np.minimum(rois, H).astype(np.int32)


POSITIONS = {
    "all": lambda rng, n: np.arange(n),
    "subset": lambda rng, n: np.sort(rng.choice(n, n // 3, replace=False)),
    "unordered": lambda rng, n: rng.permutation(n)[: n // 2],
    "repeated": lambda rng, n: rng.integers(0, n, 2 * n),
}
KS = [(2, 6, 1, 7), (0, NB, 0, NB), (0, 0, 0, 0), (NB, NB, NB, NB),
      (3, 3, 3, 3), (5, 2, 4, 4)]


@pytest.mark.parametrize("grid", [16, 8, 4])
@pytest.mark.parametrize("kind", sorted(POSITIONS))
def test_step_equals_lookups_over_gathered_rows(grid, kind):
    """Random tables (any int32 values, not only prefix sums), the finest
    grid and two tier shapes, positions that are a subset, unordered or
    repeated, empty ROIs, and value indices at 0, at NB and with kl = ku
    or kl > ku."""
    rng = np.random.default_rng(grid * 31 + len(kind))
    n = 40
    tables = jnp.asarray(rng.integers(-2**31, 2**31 - 1,
                                      (n, grid + 1, grid + 1, NB + 1)),
                         jnp.int32)
    pos = POSITIONS[kind](rng, n).astype(np.int32)
    rois = _rois(rng, len(pos))
    cfg = CHIConfig(grid=grid, num_bins=NB, height=H, width=W)
    rb = jnp.asarray(cfg.row_bounds, jnp.int32)
    cb = jnp.asarray(cfg.col_bounds, jnp.int32)
    for ks in KS:
        ks = jnp.asarray(ks, jnp.int32)
        got = _device_cp_bounds(tables, pos, rois, rb, cb, ks)
        want = _gathered_bounds(tables, pos, rois, rb, cb, ks)
        for g_arr, w_arr in zip(got, want):
            assert g_arr.dtype == w_arr.dtype == jnp.int32
            np.testing.assert_array_equal(np.asarray(g_arr),
                                          np.asarray(w_arr))


def _meta(b):
    meta = np.zeros(b, MASK_META_DTYPE)
    meta["mask_id"] = np.arange(b)
    meta["image_id"] = np.arange(b) // 2
    meta["mask_type"] = np.arange(b) % 2 + 1
    return meta


@pytest.fixture(scope="module")
def store():
    b = 30
    rng = np.random.default_rng(5)
    masks = rng.random((b, H, W)).astype(np.float32)
    masks[: b // 2] *= 0.4
    cfg = CHIConfig(grid=16, num_bins=NB, height=H, width=W)
    return MaskStore.create_memory(masks, _meta(b), cfg)


EXPRS = [CP((0, 0, H, W), 0.5, INF), CP((3, 5, 50, 61), 0.25, 0.75),
         CP((8, 8, 8, 40), 0.1, 0.9), CP(None, 0.0, 0.125),
         BinOp("/", CP((4, 4, 60, 60), 0.6, INF),
               CP((4, 4, 60, 60), 0.0, INF))]


@pytest.mark.parametrize("tier", [4, 8, 16])
def test_device_bounds_equal_host_bounds_at_each_tier(store, tier):
    """Every pyramid tier's table (coarse tiers are the store's device
    tier tables) gives the device step the host's bounds, bit for bit,
    over all candidates and over an unordered subset."""
    assert tier in store.cfg.tier_grids
    rois = np.tile(np.array([[2, 3, 40, 50]], np.int64), (len(store), 1))
    ctx = MaskEvalContext(store, np.arange(len(store)), rois)
    host, device = get_backend(store, "host"), get_backend(store, "device")
    g = None if tier == store.cfg.grid else tier
    for idx in (np.arange(len(store)), np.array([17, 3, 3, 29, 0, 11])):
        sub = tier_context(ctx, idx, g)
        for expr in EXPRS:
            want = host.bounds(sub, expr)
            got = device.bounds(sub, expr)
            np.testing.assert_array_equal(got[0], want[0], err_msg=repr(expr))
            np.testing.assert_array_equal(got[1], want[1], err_msg=repr(expr))


def _plan(leaves):
    """A filter whose one bounded expression holds 1 or 3 CP leaves."""
    expr = CP((0, 0, H, W), 0.5, INF)
    if leaves == 3:
        expr = BinOp("+", expr, BinOp("/", CP((4, 4, 60, 60), 0.6, INF),
                                      CP((4, 4, 60, 60), 0.0, INF)))
    return LogicalPlan(predicate=Cmp(expr, ">", 900.0))


@pytest.mark.parametrize("leaves", [1, 3])
def test_device_cp_leaf_counts_its_lookups(store, leaves):
    """On the device backend a CP leaf adds 16 entries × 4 B = 64 B per
    candidate to ``chi_bytes``, and counts its candidates in
    ``chi_inplace_rows``."""
    device = get_backend(store, "device")
    rows0 = device.stats.chi_inplace_rows
    with opt.configure(pyramid=False, reorder=False):
        _, stats = run_plan(store, _plan(leaves), backend="device")
    rows = device.stats.chi_inplace_rows - rows0
    assert CHI_LOOKUPS * 4 == 64
    assert rows == leaves * stats.n_candidates > 0
    assert stats.chi_bytes == 64 * rows


@pytest.mark.parametrize("backend", ["host", "mesh"])
@pytest.mark.parametrize("leaves", [1, 3])
def test_host_and_mesh_count_whole_chi_rows(store, backend, leaves):
    """The host and mesh backends read whole CHI rows: one row of
    (G+1)²·(NB+1) int32 per candidate and bounded expression, as before,
    and their passes leave the device counter alone."""
    device = get_backend(store, "device")
    rows0 = device.stats.chi_inplace_rows
    with opt.configure(pyramid=False, reorder=False):
        _, stats = run_plan(store, _plan(leaves), backend=backend)
    row = (store.cfg.grid + 1) ** 2 * (NB + 1) * 4
    assert stats.chi_bytes == stats.n_candidates * row > 0
    assert device.stats.chi_inplace_rows == rows0


def test_device_pair_leaf_counts_whole_rows():
    """Pair leaves still gather both roles' whole rows on the device, and
    count them as before: two rows per candidate pair."""
    b = 16
    rng = np.random.default_rng(9)
    masks = rng.random((b, H, W)).astype(np.float32)
    cfg = CHIConfig(grid=4, num_bins=NB, height=H, width=W)
    st = MaskStore.create_memory(masks, _meta(b), cfg)
    svc = MaskSearchService(st, backend="device")
    try:
        rows0 = svc.backend.stats.chi_inplace_rows
        out = svc.query(
            "SELECT image_id FROM MasksDatabaseView ORDER BY "
            "IOU(saliency, attention, 0.6, 0.6) ASC LIMIT 4;")
        assert svc.backend.stats.chi_inplace_rows == rows0
        assert "masksearch_backend_chi_inplace_rows" in svc.metrics_text()
    finally:
        svc.close()
    row = (cfg.grid + 1) ** 2 * (NB + 1) * 4
    assert out["stats"]["chi_bytes"] == 2 * row * (b // 2)
