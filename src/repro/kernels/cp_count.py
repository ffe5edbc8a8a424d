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


# -- reading the resident lane rows in place ---------------------------------
#
# The device tier holds float masks of ``H·W`` pixels in lanes of 128,
# ``f32[n, L, 128]`` with ``L = H·W/128`` (``MaskStore.device_row_shape``).
# The in-place kernels take that whole store and the batch's positions: the
# positions are a scalar-prefetch operand, and each input block's index map
# picks store row ``pos[i]``, so the pipeline DMAs every row from HBM into
# VMEM once and no batch is gathered, copied or relaid out beforehand.  A
# block is the whole row where it fits the tile budget (``lane_geometry``),
# else a tile of ``lb`` lane rows on a second, sequential grid axis, across
# which the counts accumulate.
#
# Pixel ``(y, x)`` lies at flat index ``f = 128·r + l`` (lane row ``r``,
# lane ``l``), so a row of width W may straddle lane rows.  The ROI test is
# exact on ``f``: ``r0 ≤ y < r1`` is ``r0·W ≤ f < r1·W`` (the corners are
# clipped to ``[0, H]`` first, which changes no answer), and ``x = f mod W``
# is computed in the kernel, exactly (``image_column``).

LANES = 128


def lane_geometry(rows: jax.Array, width: int,
                  members: int = 1) -> tuple[int, int, int]:
    """``(H, lb, chunk)`` of a lane-row store of masks ``width`` wide, read
    ``members`` rows at a time: ``lb`` lane rows per block, the whole row
    where ``members`` blocks fit the tile budget (picked as ``_pick_bh``
    picks row tiles), and ``chunk`` lane rows per inner step, the largest
    multiple of 8 up to 64 that divides ``lb`` (whole sublane tiles, a few
    vregs per value), else the whole block."""
    l = rows.shape[1]
    h = l * LANES // width
    if h * width >= 2**24:
        raise ValueError(f"{h}x{width} masks: image_column is exact below "
                         f"2**24 pixels")
    lb = _pick_bh(l, LANES, rows.dtype.itemsize,
                  budget_bytes=2 * 1024 * 1024 // members)
    chunk = next((c for c in range(64, 0, -8) if lb % c == 0), lb)
    return h, lb, chunk


def flat_rois(rois: jax.Array, h: int, w: int) -> jax.Array:
    """ROI corners ``(…, 4)`` as ``(r0, c0, r1, c1)`` → ``(r0·W, c0, r1·W,
    c1)`` with the rows clipped to ``[0, H]``: the row bounds as flat pixel
    indices, exact for every ``(y, x)`` of an ``H × W`` mask."""
    rois = rois.astype(jnp.int32)
    rows = jnp.clip(rois[..., 0::2], 0, h) * w
    return jnp.stack([rows[..., 0], rois[..., 1], rows[..., 1],
                      rois[..., 3]], axis=-1)


def store_rows(pos: jax.Array, n: int) -> jax.Array:
    """Positions as int32 store rows, clamped to ``[0, n)`` as a gather
    ``rows[pos]`` clamps them, so no DMA reads past the store."""
    return jnp.clip(pos.astype(jnp.int32), 0, n - 1)


def lane_tile(rows_ref, start, chunk: int):
    """Lane rows ``[start, start + chunk)`` of a ``(1, lb, 128)`` block."""
    return rows_ref[0, pl.ds(start, chunk), :].astype(jnp.float32)


def flat_index(chunk: int):
    """``f`` of a chunk's pixels relative to its first lane row."""
    return (jax.lax.broadcasted_iota(jnp.int32, (chunk, LANES), 0) * LANES
            + jax.lax.broadcasted_iota(jnp.int32, (chunk, LANES), 1))


def image_column(f, w: int):
    """``x = f mod W`` of flat pixel indices ``0 ≤ f < 2**24``, exactly: a
    float32 estimate of ``f // W``, off by at most one, corrected in
    integers."""
    x = f - (f.astype(jnp.float32) * (1.0 / w)).astype(jnp.int32) * w
    x = jnp.where(x < 0, x + w, x)
    return jnp.where(x >= w, x - w, x)


def roi_inside_flat(corners, f, x):
    """The ROI predicate from flat ``corners`` (``flat_rois``) over a
    chunk's flat indices ``f`` and image columns ``x``."""
    f0, c0, f1, c1 = corners
    return (f >= f0) & (f < f1) & (x >= c0) & (x < c1)


def fold(v: jax.Array) -> jax.Array:
    """Int32 partial sums of a ``(chunk, 128)`` tile, kept as one
    ``(8, 128)`` vreg where the chunk is whole sublane tiles."""
    c = v.shape[0]
    if c % 8:
        return v
    return v.reshape(c // 8, 8, LANES).sum(axis=0)


def over_chunks(body, span, n: int, chunk: int, init):
    """``body(start, carry)`` over the chunks ``[lo, hi) = span`` of a block
    of ``n`` chunks of ``chunk`` lane rows: a loop on the chip, a plain
    call on chunk 0 where the block is one chunk (its start then stays a
    static 0; the body's own tests keep the count exact)."""
    if n == 1:
        return body(0, init)
    return jax.lax.fori_loop(
        span[0], span[1],
        lambda c, acc: body(pl.multiple_of(c * chunk, chunk), acc), init)


def roi_span(corners, chunk: int):
    """The chunks ``[lo, hi)`` of a row that hold pixels of the ROIs'
    image rows (flat ``corners``, one per ROI): the others count 0."""
    step = chunk * LANES
    lo = functools.reduce(jnp.minimum, [c[0] for c in corners])
    hi = functools.reduce(jnp.maximum, [c[2] for c in corners])
    return lo // step, (hi + step - 1) // step


def whole_image(corners, h: int, w: int):
    """Whether every ROI covers the whole ``h × w`` image, so the count
    needs no ROI test."""
    return functools.reduce(jnp.logical_and, [
        (c[0] <= 0) & (c[2] >= h * w) & (c[1] <= 0) & (c[3] >= w)
        for c in corners])


def roi_or_whole(corners, body, *, h: int, w: int, chunk: int, n: int,
                 first, init):
    """``body(roi)`` counted over a block of ``n`` chunks whose first is
    chunk ``first`` of the row: without an ROI test where every ROI covers
    the whole image, else over the block's chunks of the ROIs' rows."""
    lo, hi = roi_span(corners, chunk)
    span = jnp.clip(lo - first, 0, n), jnp.clip(hi - first, 0, n)
    return jax.lax.cond(
        whole_image(corners, h, w),
        lambda: over_chunks(body(False), (0, n), n, chunk, init),
        lambda: over_chunks(body(True), span, n, chunk, init))


def _cp_inplace_body(rows_ref, bounds_ref, corners, *, q: int, chunk: int,
                     w: int, row0):
    """The chunk body of the in-place CP kernel over a block whose first
    lane row is ``row0`` of the mask, with or without the ROI test: one
    tile load answers all Q descriptors."""
    base = flat_index(chunk)

    def body(roi: bool):
        def step(start, accs):
            m = lane_tile(rows_ref, start, chunk)
            if roi:
                f = base + (row0 + start) * LANES
                x = image_column(f, w)
            out = []
            for qi in range(q):
                hit = (m >= bounds_ref[qi]) & (m < bounds_ref[q + qi])
                if roi:
                    hit = hit & roi_inside_flat(corners[qi], f, x)
                out.append(accs[qi] + fold(hit.astype(jnp.int32)))
            return tuple(out)
        return step

    return body


def _cp_inplace_kernel(pos_ref, rois_ref, bounds_ref, rows_ref, out_ref, *,
                       q: int, nb: int, lb: int, chunk: int, h: int, w: int):
    del pos_ref                                    # read by the index map
    i, tile = pl.program_id(0), pl.program_id(1)

    @pl.when(tile == 0)
    def _init():
        for qi in range(q):
            out_ref[qi * nb + i] = 0

    corners = [[rois_ref[4 * (qi * nb + i) + k] for k in range(4)]
               for qi in range(q)]
    body = _cp_inplace_body(rows_ref, bounds_ref, corners, q=q, chunk=chunk,
                            w=w, row0=tile * lb)
    zero = jnp.zeros(fold(flat_index(chunk)).shape, jnp.int32)
    n = lb // chunk
    accs = roi_or_whole(corners, body, h=h, w=w, chunk=chunk, n=n,
                        first=tile * n, init=tuple(zero for _ in range(q)))
    for qi in range(q):
        out_ref[qi * nb + i] += jnp.sum(accs[qi])


def cp_count_multi_inplace_pallas(rows: jax.Array, pos: jax.Array,
                                  rois: jax.Array, lvs: jax.Array,
                                  uvs: jax.Array, *, width: int,
                                  interpret: bool = False) -> jax.Array:
    """Store ``(n, L, 128)``, positions ``(B,)``, ``(Q, B, 4)``, ``(Q,)``,
    ``(Q,)`` → ``(Q, B)`` int32: ``cp_count_multi`` over the masks of width
    ``width`` at ``pos``, each read once where it lies in the store."""
    h, lb, chunk = lane_geometry(rows, width)
    b, q = pos.shape[0], rois.shape[0]
    bounds = value_scalars(jnp.concatenate([lvs, uvs]), rows.dtype)
    rois = flat_rois(rois, h, width)
    pos = store_rows(pos, rows.shape[0])

    def call(s, e):
        nb = e - s
        kernel = functools.partial(_cp_inplace_kernel, q=q, nb=nb, lb=lb,
                                   chunk=chunk, h=h, w=width)
        out = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(nb, rows.shape[1] // lb),
                in_specs=[pl.BlockSpec((1, lb, LANES),
                                       lambda i, j, p, r, v: (p[i], j, 0))],
                out_specs=SMEM_OUT),
            out_shape=jax.ShapeDtypeStruct((q * nb,), jnp.int32),
            interpret=interpret,
        )(pos[s:e], rois[:, s:e].reshape(-1), bounds, rows)
        return out.reshape(q, nb)

    return _over_rows(call, b, 5 * q + 1)
