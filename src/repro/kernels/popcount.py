"""Pallas TPU kernels over the bitpacked binary-mask tier (DESIGN.md §12).

Binary masks are stored 1 bit/pixel as little-endian uint32 words
(core/packing.py), so the verification ops become bitwise AND/OR plus
popcount over ``(1, bh, words)`` tiles — the same streaming-reduction shape
as the float kernels at 1/32 the HBM traffic.  Four invariants make the
math exact and width-free:

* tail bits past ``W`` in a row's last word are zero (pack-time invariant),
* ROI column spans are already clipped to ``W`` (``cp.normalize_rois``),
* the ROI column predicate is a per-word *span mask* — for word ``k`` the
  uint32 with bits ``[clip(c0-32k, 0, 32), clip(c1-32k, 0, 32))`` set — so
  word-edge partial coverage costs one mask, not a per-bit test,
* on binary values the CP range test collapses to two flags:
  ``f1 = (lv <= 1 < uv)`` and ``f0 = (lv <= 0 < uv)``; the count inside the
  ROI is exactly ``f1·ones + f0·(area − ones)`` where ``ones`` is the
  popcount of ``mask & span`` and ``area`` the popcount of the span —
  bit-identical to the float kernel's ``(m >= lv) & (m < uv)`` sum.

Thresholded ops (pair / MASK_AGG, ``value > t`` on {0, 1}) build an
*effective word* per role: ``(t < 1 ? word : 0) | (t < 0 ? ~word : 0)``;
the complement's garbage tail bits are annihilated by the span mask at
count time.

``_fused_verify_popcount_kernel`` is the bounds+verify megakernel: one
launch takes the whole verification batch, every CP descriptor of the
plan, and the CHI verdicts (``decided``/``lb`` per (descriptor, mask)),
and emits exact counts — CHI-decided entries pass their bound through,
undecided ones are counted from the packed words.  That collapses the
Q-launches-per-batch float verify path to a single dispatch.

Kernel bodies are integer-only by construction; the ``popcount-no-float``
masklint rule enforces it (no float loads inside ``*_popcount_kernel``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .cp_count import SMEM_OUT, _over_rows, _pick_bh

_WORD = 32


def _popcount32(x):
    """Bit-twiddle popcount of uint32 lanes → int32 (no f64, no LUTs)."""
    x = x.astype(jnp.uint32)
    x = x - ((x >> 1) & jnp.uint32(0x55555555))
    x = (x & jnp.uint32(0x33333333)) + ((x >> 2) & jnp.uint32(0x33333333))
    x = (x + (x >> 4)) & jnp.uint32(0x0F0F0F0F)
    return ((x * jnp.uint32(0x01010101)) >> 24).astype(jnp.int32)


def _mask_lt(n):
    """uint32 with bits [0, clip(n, 0, 32)) set, elementwise over int32 n."""
    shift = jnp.clip(n, 0, _WORD - 1).astype(jnp.uint32)
    partial = (jnp.uint32(1) << shift) - jnp.uint32(1)
    return jnp.where(n >= _WORD, jnp.uint32(0xFFFFFFFF), partial)


def _span_mask(lo, hi):
    """uint32 with bits [clip(lo,0,32), clip(hi,0,32)) set."""
    return _mask_lt(hi) & ~_mask_lt(lo)


def _effective_word(w, f1, f0):
    """Thresholded-binary word: bits where ``value > t`` holds given the
    flags ``f1 = (t < 1)``, ``f0 = (t < 0)`` (int32 0/1).  May carry tail
    garbage from the complement — AND with a span mask before counting."""
    zero = jnp.uint32(0)
    return jnp.where(f1 > 0, w, zero) | jnp.where(f0 > 0, ~w, zero)


def _range_flags(lv, uv):
    """CP range [lv, uv) on binary values → (f1, f0) int32 flags."""
    lv = jnp.asarray(lv, jnp.float32)
    uv = jnp.asarray(uv, jnp.float32)
    f1 = ((lv <= 1.0) & (1.0 < uv)).astype(jnp.int32)
    f0 = ((lv <= 0.0) & (0.0 < uv)).astype(jnp.int32)
    return f1, f0


def _thresh_flags(t):
    """``value > t`` on binary values → (f1, f0) int32 flags."""
    t = jnp.asarray(t, jnp.float32)
    return (t < 1.0).astype(jnp.int32), (t < 0.0).astype(jnp.int32)


def _tile_valid(rois_ref, base, bh, nw, row_tile):
    """Per-word ROI coverage for one (bh, nw) tile: uint32 span masks on
    rows inside [r0, r1), zero elsewhere.  The corners are SMEM scalars
    at ``base``."""
    r0, c0, r1, c1 = (rois_ref[base + k] for k in range(4))
    rr = jax.lax.broadcasted_iota(jnp.int32, (bh, nw), 0) + row_tile * bh
    base_col = jax.lax.broadcasted_iota(jnp.int32, (bh, nw), 1) * _WORD
    span = _span_mask(c0 - base_col, c1 - base_col)
    return jnp.where((rr >= r0) & (rr < r1), span, jnp.uint32(0))


def _tile_spec(bh, nw):
    """``(1, bh, words)`` tile of one batch row, for a rank-2 grid with two
    scalar-prefetch operands."""
    return pl.BlockSpec((1, bh, nw), lambda i, j, r, f: (i, j, 0))


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------


def _cp_popcount_kernel(rois_ref, flags_ref, mask_ref, out_ref, *,
                        bh: int, nw: int):
    i, row_tile = pl.program_id(0), pl.program_id(1)

    @pl.when(row_tile == 0)
    def _init():
        out_ref[i] = 0

    m = mask_ref[0]                                   # (bh, nw) uint32
    valid = _tile_valid(rois_ref, 4 * i, bh, nw, row_tile)
    ones = jnp.sum(_popcount32(m & valid))
    area = jnp.sum(_popcount32(valid))
    out_ref[i] += flags_ref[0] * ones + flags_ref[1] * (area - ones)


def cp_count_packed_pallas(packed: jax.Array, rois: jax.Array, lv, uv, *,
                           interpret: bool = False) -> jax.Array:
    """(B, H, words) uint32, (B, 4) → (B,) int32 exact CP counts."""
    b, h, nw = packed.shape
    bh = _pick_bh(h, nw, packed.dtype.itemsize)
    flags = jnp.stack(_range_flags(lv, uv))
    rois = rois.astype(jnp.int32)
    kernel = functools.partial(_cp_popcount_kernel, bh=bh, nw=nw)

    def call(s, e):
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(e - s, h // bh),
                in_specs=[_tile_spec(bh, nw)], out_specs=SMEM_OUT),
            out_shape=jax.ShapeDtypeStruct((e - s,), jnp.int32),
            interpret=interpret,
        )(rois[s:e].reshape(-1), flags, packed[s:e])

    return _over_rows(call, b, 5)


def _cp_multi_popcount_kernel(rois_ref, flags_ref, mask_ref, out_ref, *,
                              bh: int, nw: int, q: int, nb: int):
    i, row_tile = pl.program_id(0), pl.program_id(1)

    @pl.when(row_tile == 0)
    def _init():
        for qi in range(q):
            out_ref[qi * nb + i] = 0

    m = mask_ref[0]                                   # (bh, nw) — loaded ONCE
    for qi in range(q):                               # static unroll over Q
        valid = _tile_valid(rois_ref, 4 * (qi * nb + i), bh, nw, row_tile)
        ones = jnp.sum(_popcount32(m & valid))
        area = jnp.sum(_popcount32(valid))
        out_ref[qi * nb + i] += (flags_ref[qi] * ones
                                 + flags_ref[q + qi] * (area - ones))


def cp_count_multi_packed_pallas(packed: jax.Array, rois: jax.Array,
                                 lvs: jax.Array, uvs: jax.Array, *,
                                 interpret: bool = False) -> jax.Array:
    """(B,H,words), (Q,B,4), (Q,), (Q,) → (Q,B) int32."""
    b, h, nw = packed.shape
    q = rois.shape[0]
    bh = _pick_bh(h, nw, packed.dtype.itemsize)
    flags = jnp.concatenate(_range_flags(lvs, uvs))   # [f1s; f0s]
    rois = rois.astype(jnp.int32)

    def call(s, e):
        nb = e - s
        kernel = functools.partial(_cp_multi_popcount_kernel, bh=bh, nw=nw,
                                   q=q, nb=nb)
        out = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(nb, h // bh),
                in_specs=[_tile_spec(bh, nw)], out_specs=SMEM_OUT),
            out_shape=jax.ShapeDtypeStruct((q * nb,), jnp.int32),
            interpret=interpret,
        )(rois[:, s:e].reshape(-1), flags, packed[s:e])
        return out.reshape(q, nb)

    return _over_rows(call, b, 5 * q)


def _agg_popcount_kernel(rois_ref, flags_ref, masks_ref, out_ref, *,
                         bh: int, nw: int, s: int, nb: int):
    i, row_tile = pl.program_id(0), pl.program_id(1)

    @pl.when(row_tile == 0)
    def _init():
        out_ref[i] = 0
        out_ref[nb + i] = 0

    m = masks_ref[0]                                  # (S, bh, nw) uint32
    f1 = flags_ref[0]
    f0 = flags_ref[1]
    inter_w = _effective_word(m[0], f1, f0)
    union_w = inter_w
    for si in range(1, s):                            # static unroll over S
        eff = _effective_word(m[si], f1, f0)
        inter_w = inter_w & eff
        union_w = union_w | eff
    valid = _tile_valid(rois_ref, 4 * i, bh, nw, row_tile)
    out_ref[i] += jnp.sum(_popcount32(inter_w & valid))
    out_ref[nb + i] += jnp.sum(_popcount32(union_w & valid))


def mask_agg_counts_packed_pallas(group_packed: jax.Array, rois: jax.Array,
                                  thresh, *, interpret: bool = False):
    """(N, S, H, words), (N, 4), scalar → (inter (N,), union (N,)) int32."""
    n, s, h, nw = group_packed.shape
    bh = _pick_bh(h, nw, group_packed.dtype.itemsize,
                  budget_bytes=2 * 1024 * 1024 // max(s, 1))
    flags = jnp.stack(_thresh_flags(thresh))
    rois = rois.astype(jnp.int32)

    def call(lo, hi):
        nb = hi - lo
        kernel = functools.partial(_agg_popcount_kernel, bh=bh, nw=nw, s=s,
                                   nb=nb)
        out = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(nb, h // bh),
                in_specs=[pl.BlockSpec((1, s, bh, nw),
                                       lambda i, j, r, f: (i, 0, j, 0))],
                out_specs=SMEM_OUT),
            out_shape=jax.ShapeDtypeStruct((2 * nb,), jnp.int32),
            interpret=interpret,
        )(rois[lo:hi].reshape(-1), flags, group_packed[lo:hi])
        return tuple(out.reshape(2, nb))

    return _over_rows(call, n, 6)


def _pair_popcount_kernel(rois_ref, flags_ref, a_ref, b_ref, out_ref, *,
                          bh: int, nw: int, nb: int):
    i, row_tile = pl.program_id(0), pl.program_id(1)

    @pl.when(row_tile == 0)
    def _init():
        for k in range(3):
            out_ref[k * nb + i] = 0

    ea = _effective_word(a_ref[0], flags_ref[0], flags_ref[1])
    eb = _effective_word(b_ref[0], flags_ref[2], flags_ref[3])
    valid = _tile_valid(rois_ref, 4 * i, bh, nw, row_tile)
    out_ref[i] += jnp.sum(_popcount32(ea & eb & valid))
    out_ref[nb + i] += jnp.sum(_popcount32((ea | eb) & valid))
    out_ref[2 * nb + i] += jnp.sum(_popcount32(ea & ~eb & valid))


def pair_counts_packed_pallas(packed_a: jax.Array, packed_b: jax.Array,
                              rois: jax.Array, ta, tb, *,
                              interpret: bool = False):
    """(B,H,words)×2, (B,4) → (inter, union, diff) each (B,) int32."""
    b, h, nw = packed_a.shape
    bh = _pick_bh(h, nw, packed_a.dtype.itemsize)
    flags = jnp.stack(_thresh_flags(ta) + _thresh_flags(tb))
    rois = rois.astype(jnp.int32)

    def call(s, e):
        nb = e - s
        kernel = functools.partial(_pair_popcount_kernel, bh=bh, nw=nw, nb=nb)
        out = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(nb, h // bh),
                in_specs=[_tile_spec(bh, nw), _tile_spec(bh, nw)],
                out_specs=SMEM_OUT),
            out_shape=jax.ShapeDtypeStruct((3 * nb,), jnp.int32),
            interpret=interpret,
        )(rois[s:e].reshape(-1), flags, packed_a[s:e], packed_b[s:e])
        return tuple(out.reshape(3, nb))

    return _over_rows(call, b, 7)


def _fused_verify_popcount_kernel(rois_ref, flags_ref, dec_ref, lb_ref,
                                  mask_ref, out_ref, *,
                                  bh: int, nw: int, q: int, nb: int):
    i, row_tile = pl.program_id(0), pl.program_id(1)

    @pl.when(row_tile == 0)
    def _init():
        # CHI-decided (descriptor, mask) entries pass their exact bound
        # straight through; undecided entries start at 0 and accumulate.
        for qi in range(q):
            k = qi * nb + i
            out_ref[k] = dec_ref[k] * lb_ref[k]

    m = mask_ref[0]                                   # (bh, nw) — loaded ONCE
    for qi in range(q):                               # static unroll over Q
        k = qi * nb + i
        valid = _tile_valid(rois_ref, 4 * k, bh, nw, row_tile)
        ones = jnp.sum(_popcount32(m & valid))
        area = jnp.sum(_popcount32(valid))
        count = flags_ref[qi] * ones + flags_ref[q + qi] * (area - ones)
        out_ref[k] += (1 - dec_ref[k]) * count


def fused_verify_packed_pallas(packed: jax.Array, rois: jax.Array,
                               lvs: jax.Array, uvs: jax.Array,
                               decided: jax.Array, lb: jax.Array, *,
                               interpret: bool = False) -> jax.Array:
    """The bounds+verify megakernel: one launch per verification batch.

    (B,H,words), (Q,B,4), (Q,), (Q,), decided (Q,B) int32 0/1, lb (Q,B)
    int32 → (Q,B) int32 exact counts.  Where ``decided`` the CHI bound is
    already exact (lb == ub) and is emitted as-is; everywhere else the
    packed words are counted — all Q descriptors answered from a single
    pass over the batch's bits.
    """
    b, h, nw = packed.shape
    q = rois.shape[0]
    bh = _pick_bh(h, nw, packed.dtype.itemsize)
    flags = jnp.concatenate(_range_flags(lvs, uvs))   # [f1s; f0s]
    rois = rois.astype(jnp.int32)
    decided = decided.astype(jnp.int32)
    lb = lb.astype(jnp.int32)

    def call(s, e):
        nb = e - s
        kernel = functools.partial(_fused_verify_popcount_kernel, bh=bh,
                                   nw=nw, q=q, nb=nb)
        out = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4, grid=(nb, h // bh),
                in_specs=[pl.BlockSpec((1, bh, nw),
                                       lambda i, j, r, f, d, lo: (i, j, 0))],
                out_specs=SMEM_OUT),
            out_shape=jax.ShapeDtypeStruct((q * nb,), jnp.int32),
            interpret=interpret,
        )(rois[:, s:e].reshape(-1), flags, decided[:, s:e].reshape(-1),
          lb[:, s:e].reshape(-1), packed[s:e])
        return out.reshape(q, nb)

    return _over_rows(call, b, 8 * q)


# ---------------------------------------------------------------------------
# jnp references (portable fallbacks; ops.py dispatches here off-TPU)
# ---------------------------------------------------------------------------


def _pc(x):
    return jax.lax.population_count(x.astype(jnp.uint32)).astype(jnp.int32)


def _valid_words(rois, h, nw):
    """(B, 4) int32 → (B, h, nw) uint32 per-word ROI coverage masks."""
    b = rois.shape[0]
    rr = jax.lax.broadcasted_iota(jnp.int32, (b, h, nw), 1)
    base = jax.lax.broadcasted_iota(jnp.int32, (b, h, nw), 2) * _WORD
    r0 = rois[:, 0][:, None, None]
    c0 = rois[:, 1][:, None, None]
    r1 = rois[:, 2][:, None, None]
    c1 = rois[:, 3][:, None, None]
    span = _span_mask(c0 - base, c1 - base)
    return jnp.where((rr >= r0) & (rr < r1), span, jnp.uint32(0))


def cp_count_packed_ref(packed, rois, lv, uv):
    _, h, nw = packed.shape
    valid = _valid_words(rois.astype(jnp.int32), h, nw)
    ones = jnp.sum(_pc(packed & valid), axis=(1, 2))
    area = jnp.sum(_pc(valid), axis=(1, 2))
    f1, f0 = _range_flags(lv, uv)
    return (f1 * ones + f0 * (area - ones)).astype(jnp.int32)


def cp_count_multi_packed_ref(packed, rois, lvs, uvs):
    return jax.vmap(cp_count_packed_ref, in_axes=(None, 0, 0, 0))(
        packed, rois.astype(jnp.int32), lvs, uvs)


def mask_agg_counts_packed_ref(group_packed, rois, thresh):
    _, s, h, nw = group_packed.shape
    f1, f0 = _thresh_flags(thresh)
    inter_w = _effective_word(group_packed[:, 0], f1, f0)
    union_w = inter_w
    for si in range(1, s):
        eff = _effective_word(group_packed[:, si], f1, f0)
        inter_w = inter_w & eff
        union_w = union_w | eff
    valid = _valid_words(rois.astype(jnp.int32), h, nw)
    inter = jnp.sum(_pc(inter_w & valid), axis=(1, 2))
    union = jnp.sum(_pc(union_w & valid), axis=(1, 2))
    return inter, union


def pair_counts_packed_ref(packed_a, packed_b, rois, ta, tb):
    _, h, nw = packed_a.shape
    fa1, fa0 = _thresh_flags(ta)
    fb1, fb0 = _thresh_flags(tb)
    ea = _effective_word(packed_a, fa1, fa0)
    eb = _effective_word(packed_b, fb1, fb0)
    valid = _valid_words(rois.astype(jnp.int32), h, nw)
    inter = jnp.sum(_pc(ea & eb & valid), axis=(1, 2))
    union = jnp.sum(_pc((ea | eb) & valid), axis=(1, 2))
    diff = jnp.sum(_pc(ea & ~eb & valid), axis=(1, 2))
    return inter, union, diff


def fused_verify_packed_ref(packed, rois, lvs, uvs, decided, lb):
    counts = cp_count_multi_packed_ref(packed, rois, lvs, uvs)
    return jnp.where(decided.astype(jnp.int32) > 0,
                     lb.astype(jnp.int32), counts)
