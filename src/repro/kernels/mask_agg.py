"""Pallas TPU kernel: fused MASK_AGG (thresholded intersection/union counts).

Scenario-3 IoU queries aggregate the masks of one image (model saliency +
human attention), threshold them, and count intersection/union pixels inside
an ROI.  Materializing the binary masks costs 2× the mask bytes in HBM
traffic; this kernel fuses threshold → AND/OR-reduce-over-types → ROI mask →
count into one pass, emitting two scalars per group.

Tiling: grid ``(N, H/bh)``; block ``(1, S, bh, W)`` — all S member masks of a
group stream together (S is small: 2–8 mask types).  Intersection is a min-
reduce over the type axis, union a max-reduce; both stay in VMEM.  ROI
corners and the threshold are SMEM scalars (scalar prefetch); both counts
accumulate into one whole-array SMEM output.

The in-place variant (``mask_agg_counts_inplace_pallas``) reads the
members straight from the resident lane rows ``f32[n, L, 128]``: grid
``(N, L/lb)``, one input per member, member ``k`` of group ``i`` at store
row ``pos[i·S + k]`` through a scalar-prefetched index map (``cp_count``'s
in-place section has the layout, the tiles and the ROI test).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .cp_count import (LANES, SMEM_OUT, _over_rows, _pick_bh, flat_index,
                       flat_rois, fold, image_column, lane_geometry,
                       lane_tile, roi_inside, roi_inside_flat, roi_or_whole,
                       store_rows, value_scalars)


def _agg_kernel(rois_ref, t_ref, masks_ref, out_ref, *, bh: int, w: int,
                nb: int):
    i, row_tile = pl.program_id(0), pl.program_id(1)

    @pl.when(row_tile == 0)
    def _init():
        out_ref[i] = 0
        out_ref[nb + i] = 0

    m = masks_ref[0].astype(jnp.float32)               # (S, bh, W)
    binary = (m > t_ref[0]).astype(jnp.int32)
    inter = jnp.min(binary, axis=0)                    # AND over mask types
    union = jnp.max(binary, axis=0)                    # OR  over mask types
    inside = roi_inside(rois_ref, 4 * i, (bh, w),
                        row_tile * bh).astype(jnp.int32)
    out_ref[i] += jnp.sum(inter * inside)
    out_ref[nb + i] += jnp.sum(union * inside)


def mask_agg_counts_pallas(group_masks: jax.Array, rois: jax.Array, thresh, *,
                           interpret: bool = False):
    """(N, S, H, W), (N, 4), scalar → (inter (N,), union (N,)) int32."""
    n, s, h, w = group_masks.shape
    bh = _pick_bh(h, w, group_masks.dtype.itemsize,
                  budget_bytes=2 * 1024 * 1024 // max(s, 1))
    thresh = value_scalars(thresh, group_masks.dtype)
    rois = rois.astype(jnp.int32)

    def call(lo, hi):
        nb = hi - lo
        kernel = functools.partial(_agg_kernel, bh=bh, w=w, nb=nb)
        out = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(nb, h // bh),
                in_specs=[pl.BlockSpec((1, s, bh, w),
                                       lambda i, j, r, t: (i, 0, j, 0))],
                out_specs=SMEM_OUT),
            out_shape=jax.ShapeDtypeStruct((2 * nb,), jnp.int32),
            interpret=interpret,
        )(rois[lo:hi].reshape(-1), thresh, group_masks[lo:hi])
        return tuple(out.reshape(2, nb))

    return _over_rows(call, n, 6)


def _member_map(s: int, k: int):
    """Index map of member ``k``: tile ``j`` of the store row of group
    ``i``'s member."""
    return lambda i, j, p, r, t: (p[i * s + k], j, 0)


def _agg_inplace_body(members, t_ref, corners, *, s: int, chunk: int,
                      w: int, row0):
    """The chunk body of the in-place MASK_AGG kernel over blocks whose
    first lane row is ``row0`` of the mask, with or without the ROI test:
    each member thresholded, then AND/OR-reduced."""
    base = flat_index(chunk)

    def body(roi: bool):
        def step(start, accs):
            inter = union = lane_tile(members[0], start, chunk) > t_ref[0]
            for k in range(1, s):
                binary = lane_tile(members[k], start, chunk) > t_ref[0]
                inter, union = inter & binary, union | binary
            if roi:
                f = base + (row0 + start) * LANES
                inside = roi_inside_flat(corners, f, image_column(f, w))
                inter, union = inter & inside, union & inside
            return (accs[0] + fold(inter.astype(jnp.int32)),
                    accs[1] + fold(union.astype(jnp.int32)))
        return step

    return body


def _agg_inplace_kernel(pos_ref, rois_ref, t_ref, *refs, s: int, nb: int,
                        lb: int, chunk: int, h: int, w: int):
    del pos_ref                                    # read by the index maps
    members, out_ref = refs[:s], refs[s]
    i, tile = pl.program_id(0), pl.program_id(1)

    @pl.when(tile == 0)
    def _init():
        out_ref[i] = 0
        out_ref[nb + i] = 0

    corners = [rois_ref[4 * i + k] for k in range(4)]
    body = _agg_inplace_body(members, t_ref, corners, s=s, chunk=chunk, w=w,
                             row0=tile * lb)
    zero = jnp.zeros(fold(flat_index(chunk)).shape, jnp.int32)
    n = lb // chunk
    inter, union = roi_or_whole([corners], body, h=h, w=w, chunk=chunk, n=n,
                                first=tile * n, init=(zero, zero))
    out_ref[i] += jnp.sum(inter)
    out_ref[nb + i] += jnp.sum(union)


def mask_agg_counts_inplace_pallas(rows: jax.Array, pos: jax.Array,
                                   rois: jax.Array, thresh, *, s: int,
                                   width: int, interpret: bool = False):
    """Store ``(n, L, 128)``, member positions ``(N·S,)`` (group-major),
    ``(N, 4)``, scalar → ``(inter (N,), union (N,))`` int32: the counts of
    ``mask_agg_counts`` over the masks of width ``width`` at ``pos``, each
    read once where it lies in the store."""
    h, lb, chunk = lane_geometry(rows, width, members=s)
    ng = pos.shape[0] // s
    thresh = value_scalars(thresh, rows.dtype)
    rois = flat_rois(rois, h, width)
    pos = store_rows(pos, rows.shape[0])

    def call(lo, hi):
        nb = hi - lo
        kernel = functools.partial(_agg_inplace_kernel, s=s, nb=nb, lb=lb,
                                   chunk=chunk, h=h, w=width)
        member = [pl.BlockSpec((1, lb, LANES), _member_map(s, k))
                  for k in range(s)]
        out = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(nb, rows.shape[1] // lb),
                in_specs=member,
                out_specs=SMEM_OUT),
            out_shape=jax.ShapeDtypeStruct((2 * nb,), jnp.int32),
            interpret=interpret,
        )(pos[lo * s:hi * s], rois[lo:hi].reshape(-1), thresh,
          *([rows] * s))
        return tuple(out.reshape(2, nb))

    return _over_rows(call, ng, 6 + s)
