"""Pallas TPU kernel: batched CP (count-pixels-in-range-inside-ROI).

This is the engine's verification hot path: for every survivor mask, count
pixels whose value lies in ``[lv, uv)`` inside the mask's ROI.  It is a
bandwidth-bound streaming reduction — exactly the op the paper pays disk I/O
for; on TPU the cost is the HBM→VMEM stream, so the kernel's job is to touch
each mask byte exactly once with aligned tiles and keep everything else in
registers/VMEM.

Tiling: grid ``(B, H/bh)``; each step loads a ``(1, bh, W)`` VMEM tile (lane
dimension = W, kept whole; ``bh`` is the whole mask height when the mask
fits the tile budget, else a multiple of 8 rows).  The per-row scalars —
ROI corners and the ``[lv, uv)`` bounds — arrive through scalar prefetch in
SMEM, and the ROI predicate is built from ``broadcasted_iota`` offset by the
grid position, so no per-pixel index tensor ever hits HBM.  Counts
accumulate into a whole-array SMEM output across the sequential row-tile
axis.

The ``(Q,)`` *multi-query* variant (`cp_count_multi`) reuses one tile load
for every descriptor in the workload — the paper's multi-query optimization
moved inside the kernel: arithmetic intensity rises from O(1) to O(Q) per
byte.

SMEM is small (1 MiB on v5e), so a call whose per-row scalars would exceed
``_SMEM_WORDS`` is split into row chunks (``_over_rows``); each chunk is one
launch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Per-launch budget of 32-bit SMEM words for scalar-prefetch inputs plus
# SMEM outputs (128 KiB of v5e's 1 MiB).
_SMEM_WORDS = 32 * 1024

SMEM_OUT = pl.BlockSpec(memory_space=pltpu.SMEM)


def _pick_bh(h: int, w: int, itemsize: int = 4,
             budget_bytes: int = 2 * 1024 * 1024) -> int:
    """Row-tile height: the whole mask when it fits the VMEM budget, else
    the largest multiple of 8 that divides H and fits (the TPU tiling rule
    for a block's second-to-last dimension); the whole mask if none does.

    ``itemsize`` is the element byte width of the streamed tiles (float32
    masks and packed uint32 words are both 4, but the packed tier's ``w``
    is a *word* count — callers pass ``arr.dtype.itemsize`` so the budget
    math holds for any representation)."""
    if h * w * max(itemsize, 1) <= budget_bytes:
        return h
    max_rows = budget_bytes // max(w * max(itemsize, 1), 1)
    for bh in range(min(h, max_rows) // 8 * 8, 0, -8):
        if h % bh == 0:
            return bh
    return h


def _over_rows(call, b: int, words_per_row: int):
    """Run ``call(start, stop)`` over static batch-row ranges sized so one
    launch's SMEM scalars stay within ``_SMEM_WORDS``, and concatenate the
    results along their last (batch) axis.  ``call`` returns an array or a
    tuple of arrays; an empty batch launches nothing."""
    if b == 0:
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            jax.eval_shape(lambda: call(0, 0)))
    step = max(_SMEM_WORDS // max(words_per_row, 1), 1)
    parts = [call(s, min(s + step, b)) for s in range(0, b, step)]
    if len(parts) == 1:
        return parts[0]
    return jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=-1), *parts)


def roi_inside(rois_ref, base, shape, row0):
    """ROI predicate over one ``shape`` tile whose first row is ``row0``:
    the corners ``(r0, c0, r1, c1)`` are SMEM scalars at ``base``."""
    r0, c0, r1, c1 = (rois_ref[base + k] for k in range(4))
    rr = jax.lax.broadcasted_iota(jnp.int32, shape, 0) + row0
    cc = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return (rr >= r0) & (rr < r1) & (cc >= c0) & (cc < c1)


def value_scalars(values, dtype) -> jax.Array:
    """Value bounds/thresholds as float32 SMEM scalars, rounded through the
    masks' dtype first so the in-kernel float32 compare matches a compare
    in that dtype exactly."""
    return jnp.asarray(values, dtype).astype(jnp.float32).reshape(-1)


def _cp_kernel(rois_ref, bounds_ref, mask_ref, out_ref, *, bh: int, w: int):
    i, row_tile = pl.program_id(0), pl.program_id(1)

    @pl.when(row_tile == 0)
    def _init():
        out_ref[i] = 0

    m = mask_ref[0].astype(jnp.float32)               # (bh, W)
    inside = roi_inside(rois_ref, 4 * i, (bh, w), row_tile * bh)
    in_range = (m >= bounds_ref[0]) & (m < bounds_ref[1])
    out_ref[i] += jnp.sum((inside & in_range).astype(jnp.int32))


def cp_count_pallas(masks: jax.Array, rois: jax.Array, lv, uv, *,
                    interpret: bool = False) -> jax.Array:
    """(B, H, W), (B, 4) → (B,) int32.  See module docstring."""
    b, h, w = masks.shape
    bh = _pick_bh(h, w, masks.dtype.itemsize)
    bounds = value_scalars(jnp.stack([jnp.asarray(lv), jnp.asarray(uv)]),
                           masks.dtype)
    rois = rois.astype(jnp.int32)
    kernel = functools.partial(_cp_kernel, bh=bh, w=w)

    def call(s, e):
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(e - s, h // bh),
                in_specs=[pl.BlockSpec((1, bh, w),
                                       lambda i, j, r, v: (i, j, 0))],
                out_specs=SMEM_OUT),
            out_shape=jax.ShapeDtypeStruct((e - s,), jnp.int32),
            interpret=interpret,
        )(rois[s:e].reshape(-1), bounds, masks[s:e])

    return _over_rows(call, b, 5)


def _cp_multi_kernel(rois_ref, bounds_ref, mask_ref, out_ref, *,
                     bh: int, w: int, q: int, nb: int):
    i, row_tile = pl.program_id(0), pl.program_id(1)

    @pl.when(row_tile == 0)
    def _init():
        for qi in range(q):
            out_ref[qi * nb + i] = 0

    m = mask_ref[0].astype(jnp.float32)               # (bh, W) — loaded ONCE
    for qi in range(q):                               # static unroll over Q
        inside = roi_inside(rois_ref, 4 * (qi * nb + i), (bh, w),
                            row_tile * bh)
        in_range = (m >= bounds_ref[qi]) & (m < bounds_ref[q + qi])
        out_ref[qi * nb + i] += jnp.sum((inside & in_range).astype(jnp.int32))


def cp_count_multi_pallas(masks: jax.Array, rois: jax.Array,
                          lvs: jax.Array, uvs: jax.Array, *,
                          interpret: bool = False) -> jax.Array:
    """(B,H,W), (Q,B,4), (Q,), (Q,) → (Q,B) int32 — Q descriptors per tile load."""
    b, h, w = masks.shape
    q = rois.shape[0]
    bh = _pick_bh(h, w, masks.dtype.itemsize)
    bounds = value_scalars(jnp.concatenate([lvs, uvs]), masks.dtype)
    rois = rois.astype(jnp.int32)

    def call(s, e):
        nb = e - s
        kernel = functools.partial(_cp_multi_kernel, bh=bh, w=w, q=q, nb=nb)
        out = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(nb, h // bh),
                in_specs=[pl.BlockSpec((1, bh, w),
                                       lambda i, j, r, v: (i, j, 0))],
                out_specs=SMEM_OUT),
            out_shape=jax.ShapeDtypeStruct((q * nb,), jnp.int32),
            interpret=interpret,
        )(rois[:, s:e].reshape(-1), bounds, masks[s:e])
        return out.reshape(q, nb)

    return _over_rows(call, b, 5 * q)
