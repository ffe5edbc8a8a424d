"""Jit'd public wrappers for the Pallas kernels, with portable fallbacks.

Dispatch policy: by default the compiled Pallas path runs on TPU backends
and every other backend gets the pure-jnp reference, which is semantically
identical.  Interpret mode runs only when a caller asks for it
(``interpret=True``); ``use_pallas=True`` off the TPU without
``interpret=True`` raises instead of silently interpreting.  Shape
contracts that the kernels can't serve (ragged CHI grids) fall back to the
reference.

Setting ``REPRO_FORCE_PALLAS_INTERPRET=1`` in the environment asks for
interpret mode in every default-dispatched wrapper — CI uses this to
exercise the actual kernel bodies on CPU machines instead of only the jnp
references.  It is a test knob: ``chip_smoke.py`` refuses to run with it.

These wrappers are what core/ and the distributed engine call — nothing else
imports the kernel modules directly.
"""

from __future__ import annotations

import functools
import os
import time

import jax
import jax.numpy as jnp

from ..obs.metrics import REGISTRY as _REG
from . import popcount, ref
from .chi_build import chi_cell_hist_pallas
from .cp_count import cp_count_multi_pallas, cp_count_pallas
from .mask_agg import mask_agg_counts_pallas
from .pair_count import pair_counts_pallas

_FORCE_INTERPRET = os.environ.get("REPRO_FORCE_PALLAS_INTERPRET", "") == "1"

_KERNEL_LAUNCHES = _REG.counter(
    "masksearch_kernel_launches_total",
    "Dispatches through each public kernel wrapper", ("kernel",))
_KERNEL_SECONDS = _REG.histogram(
    "masksearch_kernel_dispatch_seconds",
    "Wall time per kernel wrapper dispatch (first call includes trace+jit "
    "compile; steady-state is the launch itself)", ("kernel",))
_JIT_COMPILES = _REG.counter(
    "masksearch_jit_compiles_total",
    "jit cache-entry growth observed per wrapper — a steadily rising count "
    "means shape/static-arg churn is defeating the jit cache", ("kernel",))


def _instrument(name: str, fn):
    """Wrap a jitted kernel entry point with launch counting, dispatch
    timing, and recompile detection (via the jit cache-size delta)."""
    launches = _KERNEL_LAUNCHES.labels(kernel=name)
    seconds = _KERNEL_SECONDS.labels(kernel=name)
    compiles = _JIT_COMPILES.labels(kernel=name)

    @functools.wraps(fn)
    def wrapper(*args, **kw):
        before = fn._cache_size()
        t0 = time.perf_counter()
        try:
            return fn(*args, **kw)
        finally:
            seconds.observe(time.perf_counter() - t0)
            launches.inc()
            after = fn._cache_size()
            if before < after:
                compiles.inc(after - before)

    return wrapper


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _dispatch(use_pallas: bool | None, interpret: bool) -> tuple[bool, bool]:
    """Resolve the (pallas, interpret) pair for one wrapper call.

    ``interpret=True`` selects the Pallas path in interpret mode unless the
    caller explicitly asked for the jnp reference (``use_pallas=False``).
    The force flag only overrides the *default* dispatch, so
    reference-vs-Pallas comparison tests stay meaningful under the
    forced-interpret CI leg."""
    if use_pallas is False:
        return False, False
    if interpret or (_FORCE_INTERPRET and use_pallas is None):
        return True, True
    if use_pallas and not _on_tpu():
        raise ValueError(
            f"use_pallas=True needs a TPU backend (found "
            f"{jax.default_backend()!r}); pass interpret=True to run the "
            f"kernel in interpret mode")
    return _on_tpu(), False


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def cp_count(masks, rois, lv, uv, *, use_pallas: bool | None = None,
             interpret: bool = False):
    """Batched exact CP — (B,H,W), (B,4) → (B,) int32."""
    pallas, interpret = _dispatch(use_pallas, interpret)
    if pallas:
        return cp_count_pallas(masks, rois, lv, uv,
                               interpret=interpret)
    return ref.cp_count_ref(masks, rois, lv, uv)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def cp_count_multi(masks, rois, lvs, uvs, *, use_pallas: bool | None = None,
                   interpret: bool = False):
    """Multi-query CP — (B,H,W), (Q,B,4), (Q,), (Q,) → (Q,B) int32."""
    pallas, interpret = _dispatch(use_pallas, interpret)
    if pallas:
        return cp_count_multi_pallas(masks, rois, lvs, uvs,
                                     interpret=interpret)
    return ref.cp_count_multi_ref(masks, rois, lvs, uvs)


@functools.partial(jax.jit, static_argnames=("grid", "use_pallas", "interpret"))
def chi_cell_hist(masks, interior_edges, grid: int, *,
                  use_pallas: bool | None = None, interpret: bool = False):
    """CHI ingest histograms — (B,H,W) → (B,G,G,NB) int32."""
    _, h, w = masks.shape
    divisible = (h % grid == 0) and (w % grid == 0)
    pallas, interpret = _dispatch(use_pallas, interpret)
    if pallas and divisible:
        return chi_cell_hist_pallas(masks, interior_edges, grid,
                                    interpret=interpret)
    return ref.chi_cell_hist_ref(masks, interior_edges, grid)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def mask_agg_counts(group_masks, rois, thresh, *,
                    use_pallas: bool | None = None, interpret: bool = False):
    """Fused MASK_AGG counts — (N,S,H,W), (N,4) → (inter, union) int32."""
    pallas, interpret = _dispatch(use_pallas, interpret)
    if pallas:
        return mask_agg_counts_pallas(group_masks, rois, thresh,
                                      interpret=interpret)
    return ref.mask_agg_counts_ref(group_masks, rois, thresh)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def pair_counts(masks_a, masks_b, rois, ta, tb, *,
                use_pallas: bool | None = None, interpret: bool = False):
    """Fused dual-mask counts — (B,H,W)×2, (B,4) → (inter, union, diff),
    each (B,) int32, in one pass over both masks (DESIGN.md §9)."""
    pallas, interpret = _dispatch(use_pallas, interpret)
    if pallas:
        return pair_counts_pallas(masks_a, masks_b, rois, ta, tb,
                                  interpret=interpret)
    return ref.pair_counts_ref(masks_a, masks_b, rois, ta, tb)


# -- bitpacked binary-mask tier (DESIGN.md §12) -----------------------------


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def cp_count_packed(packed, rois, lv, uv, *, use_pallas: bool | None = None,
                    interpret: bool = False):
    """Batched exact CP on packed words — (B,H,words) uint32, (B,4) →
    (B,) int32, bit-identical to ``cp_count`` on the same binary masks."""
    pallas, interpret = _dispatch(use_pallas, interpret)
    if pallas:
        return popcount.cp_count_packed_pallas(
            packed, rois, lv, uv, interpret=interpret)
    return popcount.cp_count_packed_ref(packed, rois, lv, uv)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def cp_count_multi_packed(packed, rois, lvs, uvs, *,
                          use_pallas: bool | None = None,
                          interpret: bool = False):
    """Multi-query CP on packed words — (B,H,words), (Q,B,4) → (Q,B)."""
    pallas, interpret = _dispatch(use_pallas, interpret)
    if pallas:
        return popcount.cp_count_multi_packed_pallas(
            packed, rois, lvs, uvs, interpret=interpret)
    return popcount.cp_count_multi_packed_ref(packed, rois, lvs, uvs)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def mask_agg_counts_packed(group_packed, rois, thresh, *,
                           use_pallas: bool | None = None,
                           interpret: bool = False):
    """Fused MASK_AGG counts on packed words — (N,S,H,words), (N,4) →
    (inter, union) int32."""
    pallas, interpret = _dispatch(use_pallas, interpret)
    if pallas:
        return popcount.mask_agg_counts_packed_pallas(
            group_packed, rois, thresh, interpret=interpret)
    return popcount.mask_agg_counts_packed_ref(group_packed, rois, thresh)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def pair_counts_packed(packed_a, packed_b, rois, ta, tb, *,
                       use_pallas: bool | None = None,
                       interpret: bool = False):
    """Fused dual-mask counts on packed words — (B,H,words)×2, (B,4) →
    (inter, union, diff), each (B,) int32."""
    pallas, interpret = _dispatch(use_pallas, interpret)
    if pallas:
        return popcount.pair_counts_packed_pallas(
            packed_a, packed_b, rois, ta, tb,
            interpret=interpret)
    return popcount.pair_counts_packed_ref(packed_a, packed_b, rois, ta, tb)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def fused_bounds_verify(packed, rois, lvs, uvs, decided, lb, *,
                        use_pallas: bool | None = None,
                        interpret: bool = False):
    """Bounds+verify megakernel — one launch answers every CP descriptor
    of a verification batch, passing CHI-decided entries through and
    counting the undecided remainder from the packed words.  (B,H,words),
    (Q,B,4), (Q,), (Q,), (Q,B), (Q,B) → (Q,B) int32."""
    pallas, interpret = _dispatch(use_pallas, interpret)
    if pallas:
        return popcount.fused_verify_packed_pallas(
            packed, rois, lvs, uvs, decided, lb,
            interpret=interpret)
    return popcount.fused_verify_packed_ref(packed, rois, lvs, uvs,
                                            decided, lb)


cp_count = _instrument("cp_count", cp_count)
cp_count_multi = _instrument("cp_count_multi", cp_count_multi)
chi_cell_hist = _instrument("chi_cell_hist", chi_cell_hist)
mask_agg_counts = _instrument("mask_agg_counts", mask_agg_counts)
pair_counts = _instrument("pair_counts", pair_counts)
cp_count_packed = _instrument("cp_count_packed", cp_count_packed)
cp_count_multi_packed = _instrument("cp_count_multi_packed",
                                    cp_count_multi_packed)
mask_agg_counts_packed = _instrument("mask_agg_counts_packed",
                                     mask_agg_counts_packed)
pair_counts_packed = _instrument("pair_counts_packed", pair_counts_packed)
fused_bounds_verify = _instrument("fused_bounds_verify", fused_bounds_verify)


def mask_agg_iou(group_masks, rois, thresh, **kw):
    """IoU per group from the fused counts."""
    inter, union = mask_agg_counts(group_masks, rois, thresh, **kw)
    return jnp.where(union > 0,
                     inter.astype(jnp.float32) / jnp.maximum(union, 1),
                     0.0)
