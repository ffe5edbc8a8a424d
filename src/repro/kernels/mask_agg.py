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
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .cp_count import SMEM_OUT, _over_rows, _pick_bh, roi_inside, value_scalars


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
