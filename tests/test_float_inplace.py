"""The in-place float verification kernels (DESIGN.md §7): CP and grouped
MASK_AGG counts read straight from the resident lane rows
``f32[n, H·W/128, 128]`` by scalar-prefetched positions, against the jnp
references over the gathered batch ``rows[pos].reshape(B, H, W)``.

Interpret mode on the CPU runs the Pallas kernels themselves.  The shapes
put image rows across lane rows (16×24: 5⅓ image rows a lane row; 448×448:
3½ lane rows an image row); the ROIs put their edges inside and across lane
rows, and include empty, single-pixel, whole-image and out-of-image ones;
the pixel values sit exactly on the bounds and the threshold.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels import cp_count, ops as kops, ref

LV, UV, T = 0.25, 0.75, 0.5
VALUES = np.array([0.0, 0.125, LV, 0.5, UV, 0.875, 1.0], np.float32)


def _rows(n, h, w, seed=0):
    """``n`` masks of values drawn from ``VALUES`` (the bounds and the
    threshold among them), as the store holds them: ``(n, L, 128)``."""
    rng = np.random.default_rng(seed)
    masks = rng.choice(VALUES, size=(n, h, w))
    return jnp.asarray(masks.reshape(n, h * w // 128, 128))


def _rois(h, w, b, seed=1):
    """``b`` ROIs: fixed edge cases first, then random boxes."""
    fixed = [
        (0, 0, h, w),                      # whole image
        (-3, -5, h + 2, w + 9),            # beyond it on every side
        (0, 0, 0, 0), (2, 5, 2, 9),        # empty rows
        (3, 7, 5, 7), (4, 9, 1, 3),        # empty columns; inverted
        (1, 1, 2, 2),                      # one pixel
        (h // 3, w // 5, h - 1, w - 3),    # edges inside lane rows
        (0, w // 2, h, w),                 # right half, every image row
        (h - 1, 0, h + 4, w),              # the last image row
        (h // 2, -2, h // 2 + 1, w // 3),  # one image row, from col 0
    ]
    rng = np.random.default_rng(seed)
    r0 = rng.integers(-1, h, b)
    c0 = rng.integers(-1, w, b)
    boxes = np.stack([r0, c0, r0 + rng.integers(0, h + 1, b),
                      c0 + rng.integers(0, w + 1, b)], axis=1)
    out = np.concatenate([np.asarray(fixed), boxes])[:b]
    return out.astype(np.int32)


def _positions(n, b, seed=2):
    """Unsorted positions with repeats, adjacent repeats among them."""
    rng = np.random.default_rng(seed)
    pos = rng.integers(0, n, b)
    pos[1] = pos[0]
    return jnp.asarray(pos, jnp.int32)


def _cp_case(h, w, n, b, q):
    rows, pos = _rows(n, h, w), _positions(n, b)
    rois = np.stack([np.roll(_rois(h, w, b, seed=qi), qi, axis=0)
                     for qi in range(q)])
    lvs = np.array([LV, 0.0, 0.5, -1.0, UV, 0.125, LV, 0.875][:q], np.float32)
    uvs = np.array([UV, 0.5, 1.0, 2.0, 0.875, LV, UV, 1.0][:q], np.float32)
    want = ref.cp_count_multi_ref(rows[pos].reshape(b, h, w),
                                  jnp.asarray(rois), jnp.asarray(lvs),
                                  jnp.asarray(uvs))
    got = kops.cp_count_multi_inplace(rows, pos, jnp.asarray(rois),
                                      jnp.asarray(lvs), jnp.asarray(uvs),
                                      row_shape=(h, w), interpret=True)
    return np.asarray(got), np.asarray(want)


def _group_case(h, w, n, groups, s, thresh=T):
    rows, pos = _rows(n, h, w), _positions(n, groups * s)
    rois = jnp.asarray(_rois(h, w, groups))
    grp = rows[pos].reshape((groups, s, h, w))
    want = ref.mask_agg_counts_ref(grp, rois, jnp.float32(thresh))
    got = kops.mask_agg_counts_inplace(rows, pos, rois, jnp.float32(thresh),
                                       s=s, row_shape=(h, w), interpret=True)
    return np.asarray(got), np.asarray(want)


SHAPES = [(16, 24), (32, 32), (24, 16), (448, 448)]


@pytest.mark.parametrize("q", [1, 3])
@pytest.mark.parametrize("h,w", SHAPES, ids=[f"{h}x{w}" for h, w in SHAPES])
def test_inplace_cp_matches_the_gathered_reference(h, w, q):
    n, b = (6, 13) if h == 448 else (20, 40)
    got, want = _cp_case(h, w, n, b, q)
    assert got.shape == (q, b)
    np.testing.assert_array_equal(got, want)
    assert want.any() and not want.all()


@pytest.mark.parametrize("s", [2, 3])
@pytest.mark.parametrize("h,w", SHAPES, ids=[f"{h}x{w}" for h, w in SHAPES])
def test_inplace_group_matches_the_gathered_reference(h, w, s):
    n, groups = (6, 12) if h == 448 else (20, 30)
    (inter, union), (w_inter, w_union) = _group_case(h, w, n, groups, s)
    np.testing.assert_array_equal(inter, w_inter)
    np.testing.assert_array_equal(union, w_union)
    assert w_union.any() and (w_union > w_inter).any()


@pytest.mark.parametrize("kind,h,w,members", [
    ("cp", 2048, 600, 1),                  # 4.9 MB rows: 3 tiles of 3,200
    ("group", 1024, 600, 2),               # 2 members: 3 tiles of 1,600
])
def test_inplace_rows_in_tiles_match_the_gathered_reference(kind, h, w,
                                                            members):
    """Rows that overflow the tile budget are read in tiles of lane rows
    on a second grid axis, the counts summed across them; here the tile
    edges fall inside image rows (3,200 lane rows end 682⅔ image rows
    in), and the ROIs cross them."""
    _, lb, _ = cp_count.lane_geometry(_rows(1, h, w), w, members)
    assert lb < h * w // 128
    if kind == "cp":
        got, want = _cp_case(h, w, 4, 12, 2)
        np.testing.assert_array_equal(got, want)
        assert want.any() and not want.all()
    else:
        (inter, union), (w_inter, w_union) = _group_case(h, w, 6, 12, 2)
        np.testing.assert_array_equal(inter, w_inter)
        np.testing.assert_array_equal(union, w_union)
        assert (w_union > w_inter).any()


@pytest.mark.parametrize("thresh", [T, LV, 0.0, 1.0])
def test_inplace_group_threshold_on_the_values(thresh):
    """``m > t`` with pixels exactly at ``t``: strict, as the reference."""
    (inter, union), (w_inter, w_union) = _group_case(16, 24, 12, 20, 2,
                                                     thresh)
    np.testing.assert_array_equal(inter, w_inter)
    np.testing.assert_array_equal(union, w_union)


def test_inplace_cp_counts_the_bounds_half_open():
    """A mask of one value counts all its ROI pixels exactly when
    ``lv ≤ v < uv``: at ``lv`` it counts, at ``uv`` it does not."""
    h, w = 16, 24
    rows = jnp.asarray(np.repeat(VALUES, h * w).reshape(len(VALUES),
                                                        h * w // 128, 128))
    pos = jnp.arange(len(VALUES), dtype=jnp.int32)
    rois = jnp.asarray(np.tile([[[0, 0, h, w]]], (1, len(VALUES), 1)),
                       jnp.int32)
    got = kops.cp_count_multi_inplace(
        rows, pos, rois, jnp.asarray([LV], jnp.float32),
        jnp.asarray([UV], jnp.float32), row_shape=(h, w), interpret=True)
    inside = (VALUES >= LV) & (VALUES < UV)
    np.testing.assert_array_equal(np.asarray(got)[0], inside * h * w)


@pytest.mark.parametrize("kind", ["cp", "group"])
def test_inplace_batches_split_over_the_smem_budget(kind):
    """Batches whose per-row scalars pass ``_SMEM_WORDS`` run as several
    launches (``_over_rows``), and the parts come back in order."""
    if kind == "cp":
        q, b = 8, 1000                         # 41 words a row: 2 launches
        assert b * (5 * q + 1) > cp_count._SMEM_WORDS
        got, want = _cp_case(16, 24, 50, b, q)
    else:
        s, groups = 2, 4200                    # 8 words a group: 2 launches
        assert groups * (6 + s) > cp_count._SMEM_WORDS
        got, want = _group_case(16, 24, 50, groups, s)
    np.testing.assert_array_equal(got, want)


def test_inplace_reference_path_gathers_the_batch():
    """Off the kernel (``use_pallas=False``) the wrappers are the
    references over ``rows[pos].reshape(B, H, W)``."""
    h, w = 16, 24
    rows, pos = _rows(10, h, w), _positions(10, 7)
    rois = jnp.asarray(_rois(h, w, 7)[None])
    lvs, uvs = jnp.asarray([LV], jnp.float32), jnp.asarray([UV], jnp.float32)
    got = kops.cp_count_multi_inplace(rows, pos, rois, lvs, uvs,
                                      row_shape=(h, w), use_pallas=False)
    want = kops.cp_count_multi(rows[pos].reshape(7, h, w), rois, lvs, uvs,
                               use_pallas=False)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("w", [3, 7, 24, 448, 4095])
def test_image_column_is_exact_below_2_24_pixels(w):
    """``image_column`` is ``f mod W`` for every flat index ``f < 2**24``:
    its float32 estimate of ``f // W`` is off by at most one and the
    integer correction takes the rest."""
    f = jnp.arange(2**24, dtype=jnp.int32)
    got = cp_count.image_column(f, w)
    assert bool(jnp.all(got == f % w))
