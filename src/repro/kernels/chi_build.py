"""Pallas TPU kernel: CHI construction (per-cell per-bin histograms).

Index ingest is a one-pass streaming histogram: for each mask, for each of
the G×G spatial cells, count pixels per value bin.  The kernel processes one
**whole mask** per grid step — a ``(1, H, W)`` VMEM tile, which satisfies
the TPU tiling rule at any cell height (a ``(1, H/G, W)`` cell-row tile
does not when H/G is not a multiple of 8) — and turns the per-cell
segmentation into two MXU matmuls instead of a scatter:

    for bin k:   inr   = (m >= e_k) & (m < e_{k+1})          # (H, W) VPU
                 rows  = RSEL @ inr                          # (G, W) MXU
                 cells = rows @ CSEL                         # (G, G) MXU

where ``RSEL[g, h] = [h // ch == g]`` and ``CSEL[w, g] = [w // cw == g]``
are iota-built block selectors living entirely in VMEM.  TPUs have no fast
scatter; the selector matmul is the TPU-native segment-sum (DESIGN.md §3,
"hardware adaptation").  Every operand is a 0/1 value or a count of at most
``ch`` (exact in bfloat16 up to 256), accumulated in float32, so the counts
are exact.  The bin edges are SMEM scalars (scalar prefetch).

The kernel writes ``(B, NB, G, G)``; the wrapper transposes to the
``(B, G, G, NB)`` contract.  The cheap prefix sums that turn cell
histograms into the CHI table stay in XLA
(``core.chi.histograms_to_table``) where they fuse freely.

Contract: G | H and G | W (production mask stores are padded to this); the
ragged path falls back to the jnp reference in ops.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _chi_kernel(edges_ref, mask_ref, out_ref, *, h: int, w: int, g: int,
                nb: int):
    m = mask_ref[0].astype(jnp.float32)                   # (H, W)
    ch, cw = h // g, w // g
    # Block selectors, built from iota (never touch HBM).
    rsel = (jax.lax.broadcasted_iota(jnp.int32, (g, h), 1) // ch
            == jax.lax.broadcasted_iota(jnp.int32, (g, h), 0)
            ).astype(jnp.float32)                         # (G, H)
    csel = (jax.lax.broadcasted_iota(jnp.int32, (w, g), 0) // cw
            == jax.lax.broadcasted_iota(jnp.int32, (w, g), 1)
            ).astype(jnp.float32)                         # (W, G)
    for k in range(nb):                                    # static unroll
        inr = ((m >= edges_ref[k]) & (m < edges_ref[k + 1])
               ).astype(jnp.float32)                      # (H, W)
        rows = jnp.dot(rsel, inr, preferred_element_type=jnp.float32)
        cells = jnp.dot(rows, csel, preferred_element_type=jnp.float32)
        out_ref[0, k] = cells.astype(jnp.int32)           # (G, G)


def chi_cell_hist_pallas(masks: jax.Array, interior_edges: jax.Array,
                         grid: int, *, interpret: bool = False) -> jax.Array:
    """(B, H, W), interior edges (NB-1,) → (B, G, G, NB) int32.

    ``interior_edges`` are the finite thresholds; ±max sentinels are added
    here so the kernel's bin ranges cover the whole real line (matching
    core.chi semantics for out-of-[0,1) pixel values).
    """
    b, h, w = masks.shape
    g = grid
    if h % g or w % g:
        raise ValueError(f"chi_build kernel needs G|H and G|W, got {h}x{w}, G={g}")
    nb = interior_edges.shape[0] + 1
    big = jnp.finfo(masks.dtype).max
    edges = jnp.concatenate([
        jnp.asarray([-big], masks.dtype),
        interior_edges.astype(masks.dtype),
        jnp.asarray([big], masks.dtype),
    ]).astype(jnp.float32)
    kernel = functools.partial(_chi_kernel, h=h, w=w, g=g, nb=nb)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b,),
            in_specs=[pl.BlockSpec((1, h, w), lambda i, e: (i, 0, 0))],
            out_specs=pl.BlockSpec((1, nb, g, g),
                                   lambda i, e: (i, 0, 0, 0))),
        out_shape=jax.ShapeDtypeStruct((b, nb, g, g), jnp.int32),
        interpret=interpret,
    )(edges, masks)
    return jnp.transpose(out, (0, 2, 3, 1))
