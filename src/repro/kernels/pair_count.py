"""Pallas TPU kernel: fused dual-mask pair counts.

The dual-mask query class (saliency-vs-attention discrepancy, DESIGN.md §9)
verifies per-image *pairs*: threshold mask A at ``ta``, mask B at ``tb``,
and count, inside the pair's ROI, the pixels of the intersection (A∩B), the
union (A∪B) and the difference (A∖B).  IoU and every other pair statistic
the plan IR can express derive from these three counts, so the kernel
computes all of them in **one pass over both masks** — each byte of either
mask is streamed HBM→VMEM exactly once per verification batch, the same
budget a single-mask CP pays.

Tiling mirrors ``cp_count``: grid ``(B, H/bh)``; each step loads one
``(1, bh, W)`` tile of each mask (lane dimension = W kept whole), builds
the ROI predicate from ``broadcasted_iota`` and the SMEM ROI scalars, and
accumulates the three counts into one whole-array SMEM output across the
sequential row-tile axis.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .cp_count import SMEM_OUT, _over_rows, _pick_bh, roi_inside, value_scalars


def _pair_kernel(rois_ref, t_ref, a_ref, b_ref, out_ref, *, bh: int, w: int,
                 nb: int):
    i, row_tile = pl.program_id(0), pl.program_id(1)

    @pl.when(row_tile == 0)
    def _init():
        for k in range(3):
            out_ref[k * nb + i] = 0

    ba = a_ref[0].astype(jnp.float32) > t_ref[0]      # (bh, W)
    bb = b_ref[0].astype(jnp.float32) > t_ref[1]
    inside = roi_inside(rois_ref, 4 * i, (bh, w), row_tile * bh)
    out_ref[i] += jnp.sum((inside & ba & bb).astype(jnp.int32))
    out_ref[nb + i] += jnp.sum((inside & (ba | bb)).astype(jnp.int32))
    out_ref[2 * nb + i] += jnp.sum((inside & ba & ~bb).astype(jnp.int32))


def pair_counts_pallas(masks_a: jax.Array, masks_b: jax.Array,
                       rois: jax.Array, ta, tb, *,
                       interpret: bool = False):
    """(B,H,W)×2, (B,4) → (inter, union, diff) each (B,) int32.

    ``diff`` is |A∖B| inside the ROI; |B∖A| is the same call with the roles
    swapped (the expression layer normalizes that at parse time).
    """
    b, h, w = masks_a.shape
    bh = _pick_bh(h, w, masks_a.dtype.itemsize)
    thresh = jnp.concatenate([value_scalars(ta, masks_a.dtype),
                              value_scalars(tb, masks_b.dtype)])
    rois = rois.astype(jnp.int32)

    def call(s, e):
        nb = e - s
        kernel = functools.partial(_pair_kernel, bh=bh, w=w, nb=nb)
        tile = pl.BlockSpec((1, bh, w), lambda i, j, r, t: (i, j, 0))
        out = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(nb, h // bh),
                in_specs=[tile, tile],
                out_specs=SMEM_OUT),
            out_shape=jax.ShapeDtypeStruct((3 * nb,), jnp.int32),
            interpret=interpret,
        )(rois[s:e].reshape(-1), thresh, masks_a[s:e], masks_b[s:e])
        return tuple(out.reshape(3, nb))

    return _over_rows(call, b, 7)
