"""Distributed MaskSearch — the query engine sharded over a TPU mesh.

The paper's prototype is single-node; this module is the beyond-paper
scale-out.  The mask DB (mask bytes + CHI tables + ROI table) is sharded
row-wise over every mesh axis (a DB of N masks becomes N/num_devices rows per
chip).  Each *step* function below is one physical primitive of
:class:`repro.core.backend.MeshBackend`, which drives them from the public
query path (``run_plan(plan, backend="mesh")``); each is jit-compiled with
explicit shardings, and steps that launch a Pallas kernel run it per shard
under ``jax.shard_map``:

  * ``chi_bounds_step``   — the CP leaf of every mesh bounds pass (CHI
    tables in, ``(lb, ub)`` out; collective-free).
  * ``verify_step`` / ``cp_multi_step`` — exact CP over a dense batch of
    survivor masks, one descriptor or several in one pass.
  * ``topk_select_step``  — the ranking frontier's collective: the global
    k-th best pessimistic bound from one ``all_gather`` per shard.
  * ``mask_agg_step``, ``pair_counts_step``, ``pair_cells_step`` — MASK_AGG
    group verification, pair verification and pair bounds.
  * ``*_packed_step`` and ``fused_verify_step`` — the same over the packed
    binary tier's words, and its bounds+verify megakernel.

Device placement convention: rows are sharded over the flattened mesh
(``("pod","data","model")`` or ``("data","model")``); nothing is replicated
except the query descriptor scalars.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..kernels import ops as kops
from . import chi as chi_lib
from .exprs import cell_counts_jnp, pair_cell_bounds_jnp


def db_axes(mesh: Mesh) -> tuple[str, ...]:
    """All mesh axes — DB rows shard over the full device set."""
    return tuple(mesh.axis_names)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def _per_shard(mesh: Mesh, fn, in_specs, out_specs):
    """``jit(shard_map(fn))``: every device runs ``fn`` on its own rows.
    A Mosaic kernel cannot be partitioned automatically, so each kernel
    step goes through here rather than a sharded ``jax.jit``."""
    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))


# ---------------------------------------------------------------------------
# Step functions (device-side hot paths)
# ---------------------------------------------------------------------------


def _bounds_from_corners(table, corners, area, kl_in, ku_in, kl_out, ku_out,
                         pos=None):
    """Same 8-corner math as chi._bounds_device, but with corner indices as
    device arrays (computed on device from boundary tables) so the whole
    bounds pass stays on-chip.  ``table`` holds the candidates' rows, or,
    with ``pos``, is the whole resident table read at those rows
    (:func:`repro.core.chi._lookup`)."""
    il, ih, jl, jh, ol, oh, pl, ph = [corners[:, i] for i in range(8)]
    inner_ok = (ih > il) & (jh > jl) & (ku_in > kl_in)
    lb = jnp.where(inner_ok,
                   chi_lib._lookup(table, il, ih, jl, jh,
                                   jnp.minimum(kl_in, ku_in), ku_in, pos), 0)
    outer_ok = (oh > ol) & (ph > pl) & (ku_out > kl_out)
    ub = jnp.where(outer_ok,
                   chi_lib._lookup(table, ol, oh, pl, ph,
                                   jnp.minimum(kl_out, ku_out), ku_out, pos),
                   0)
    ub = jnp.minimum(ub, area.astype(ub.dtype))
    lb = jnp.minimum(lb, ub)
    return lb.astype(jnp.int32), ub.astype(jnp.int32)


def device_resolve(rois, row_bounds, col_bounds):
    """Device-side resolve_query: map pixel ROIs onto grid corners.

    rois (N, 4) int32; boundary tables (G+1,) int32 (replicated — tiny).
    Returns corners (N, 8) int32 + area (N,).
    """
    r0, c0, r1, c1 = rois[:, 0], rois[:, 1], rois[:, 2], rois[:, 3]
    il = jnp.searchsorted(row_bounds, r0, side="left")
    ih = jnp.searchsorted(row_bounds, r1, side="right") - 1
    jl = jnp.searchsorted(col_bounds, c0, side="left")
    jh = jnp.searchsorted(col_bounds, c1, side="right") - 1
    ol = jnp.searchsorted(row_bounds, r0, side="right") - 1
    oh = jnp.searchsorted(row_bounds, r1, side="left")
    pl = jnp.searchsorted(col_bounds, c0, side="right") - 1
    ph = jnp.searchsorted(col_bounds, c1, side="left")
    g = row_bounds.shape[0] - 1
    corners = jnp.stack([il, ih, jl, jh, ol, oh, pl, ph], axis=1)
    corners = jnp.clip(corners, 0, g).astype(jnp.int32)
    area = (jnp.maximum(r1 - r0, 0) * jnp.maximum(c1 - c0, 0)).astype(jnp.int32)
    return corners, area


def make_verify_step(mesh: Mesh):
    """Exact CP over a dense survivor batch, rows sharded over all devices.

    Signature: (masks (V,H,W), rois (V,4), lv (), uv ()) → counts (V,) int32.
    On TPU this dispatches to the Pallas ``cp_count`` kernel; the jnp path is
    the portable fallback (identical semantics — see kernels/ops.py).
    """
    axes = db_axes(mesh)
    return _per_shard(mesh, kops.cp_count,
                      (P(axes, None, None), P(axes, None), P(), P()),
                      P(axes))


def value_ks(cfg: chi_lib.CHIConfig, lv: float, uv: float) -> np.ndarray:
    """Resolve a value range onto CHI bin edges as the 4-vector
    ``[kl_in, ku_in, kl_out, ku_out]`` (inner/outer threshold-prefix
    indices) — the host-side half of a device bounds pass.  Matches
    :func:`repro.core.chi.resolve_query`'s value resolution exactly."""
    edges = cfg.edges
    kl_in = np.searchsorted(edges, lv, side="left")
    ku_in = np.searchsorted(edges, uv, side="right") - 1
    kl_out = np.searchsorted(edges, lv, side="right") - 1
    ku_out = np.searchsorted(edges, uv, side="left")
    return np.clip(np.array([kl_in, ku_in, kl_out, ku_out], dtype=np.int32),
                   0, cfg.num_bins)


def make_chi_bounds_step(mesh: Mesh):
    """The CP-leaf bounds pass, sharded: CHI tables in, (lb, ub) out.

    Collective-free (each row's 8-corner gather is local); this is what the
    mesh backend runs once per distinct CP term of a plan.

    Signature: (chi_tables (N,G+1,G+1,NB+1), rois (N,4), row_bounds,
                col_bounds, value_ks (4,) int32) → lb (N,), ub (N,) int32.
    """
    axes = db_axes(mesh)

    def step(tables, rois, row_bounds, col_bounds, ks):
        corners, area = device_resolve(rois, row_bounds, col_bounds)
        return _bounds_from_corners(tables, corners, area,
                                    ks[0], ks[1], ks[2], ks[3])

    row = NamedSharding(mesh, P(axes))
    rep = replicated(mesh)
    return jax.jit(
        step,
        in_shardings=(NamedSharding(mesh, P(axes, None, None, None)),
                      NamedSharding(mesh, P(axes, None)), rep, rep, rep),
        out_shardings=(row, row),
    )


def make_topk_select_step(mesh: Mesh, k: int):
    """Distributed selection of the global k-th best pessimistic score.

    The collective runs over *precomputed* bounds scores rather than
    re-deriving them from CHI tables — so any ranking expression the plan
    IR can express (ratios, sums of CPs) shards.  Per device: mask
    non-definite rows to −inf, local top-k, one ``all_gather`` of
    (value, row-id) pairs, global top-k.  Returns the
    *row id* of the k-th best so the caller can read the threshold τ back
    at full host precision rather than float32.

    Signature: (pes (N,) f32, definite (N,) bool, base_ids (N,) int32)
      → () int32 row id of the global k-th best definite pessimistic score.
    """
    axes = db_axes(mesh)

    def local(pes, definite, base_ids):
        masked = jnp.where(definite, pes, -jnp.inf)
        kk = min(k, masked.shape[0])
        vals, idx = jax.lax.top_k(masked, kk)
        g_vals = jax.lax.all_gather(vals, axes, tiled=True)
        g_ids = jax.lax.all_gather(base_ids[idx], axes, tiled=True)
        order = jax.lax.top_k(g_vals, k)[1]
        return g_ids[order[k - 1]]

    mapped = jax.shard_map(local, mesh=mesh,
                           in_specs=(P(axes), P(axes), P(axes)),
                           out_specs=P(), check_vma=False)
    return jax.jit(mapped)


def make_mask_agg_step(mesh: Mesh):
    """Fused thresholded intersection/union *counts* for MASK_AGG group
    verification, group rows sharded over all devices (on TPU dispatches
    to the Pallas ``mask_agg`` kernel).

    Signature: (group_masks (G,S,H,W), rois (G,4), thresh ())
      → (inter (G,), union (G,)) int32.
    """
    axes = db_axes(mesh)
    return _per_shard(mesh, kops.mask_agg_counts,
                      (P(axes, None, None, None), P(axes, None), P()),
                      (P(axes), P(axes)))


def make_cp_multi_step(mesh: Mesh):
    """Fused multi-descriptor CP over one sharded mask batch — the service
    scheduler's cross-query verification pass on the mesh (Q descriptors
    answered from one pass over the sharded bytes).

    Signature: (masks (B,H,W), rois (Q,B,4), lvs (Q,), uvs (Q,))
      → counts (Q,B) int32.
    """
    axes = db_axes(mesh)
    return _per_shard(mesh, kops.cp_count_multi,
                      (P(axes, None, None), P(None, axes, None), P(), P()),
                      P(None, axes))


def make_pair_counts_step(mesh: Mesh):
    """Fused dual-mask pair counts, pair rows sharded over all devices —
    the mesh backend's verification pass for the discrepancy (pair) query
    class (DESIGN.md §9).  The i-th rows of ``masks_a`` and ``masks_b``
    are one image's role pair and shard to the same device, so the kernel
    runs collective-free; on TPU it dispatches to the Pallas
    ``pair_count`` kernel.

    Signature: (masks_a (B,H,W), masks_b (B,H,W), rois (B,4), ta (), tb ())
      → (inter (B,), union (B,), diff (B,)) int32.
    """
    axes = db_axes(mesh)
    return _per_shard(mesh, kops.pair_counts,
                      (P(axes, None, None), P(axes, None, None),
                       P(axes, None), P(), P()),
                      (P(axes), P(axes), P(axes)))


def make_pair_cells_step(mesh: Mesh, stat: str):
    """The pair-term *bounds* pass on the mesh (DESIGN.md §13): the
    cell-decomposed sound combination of both roles' CHI rows
    (:func:`repro.core.exprs.pair_cell_bounds_jnp`), pair rows sharded
    over all devices.  Collective-free — each pair's cell math reads only
    its own two CHI rows — so, like the CP-leaf bounds step, the pair
    filter phase leaves the host entirely.  Padded rows (zero tables +
    zero ROIs) yield lb = ub = 0 and are sliced off by the caller.

    Signature: (tables_a (B,G+1,G+1,NB+1), tables_b (B,G+1,G+1,NB+1),
                rois (B,4), ks (4,) int32 [ka_in, ka_out, kb_in, kb_out],
                row_bounds (G+1,), col_bounds (G+1,))
      → (lb (B,), ub (B,)) int32.
    """
    axes = db_axes(mesh)

    def step(tables_a, tables_b, rois, ks, row_bounds, col_bounds):
        lo_a = cell_counts_jnp(tables_a, ks[0])
        hi_a = cell_counts_jnp(tables_a, ks[1])
        lo_b = cell_counts_jnp(tables_b, ks[2])
        hi_b = cell_counts_jnp(tables_b, ks[3])
        return pair_cell_bounds_jnp(stat, lo_a, hi_a, lo_b, hi_b,
                                    rois, row_bounds, col_bounds)

    row = NamedSharding(mesh, P(axes))
    rep = replicated(mesh)
    return jax.jit(
        step,
        in_shardings=(NamedSharding(mesh, P(axes, None, None, None)),
                      NamedSharding(mesh, P(axes, None, None, None)),
                      NamedSharding(mesh, P(axes, None)), rep, rep, rep),
        out_shardings=(row, row),
    )


# -- bitpacked binary-mask tier (DESIGN.md §12) -----------------------------
# Packed variants of the verification steps: identical partition specs (the
# word axis replaces the pixel-column axis, rank for rank), kernel dispatch
# swapped for the popcount family.  Pair/agg thresholds are float32 — the
# packed wrappers derive integer flags from them; the uint32 words never
# meet a float lane.


def make_verify_packed_step(mesh: Mesh):
    """``make_verify_step`` over packed words.

    Signature: (packed (V,H,words) uint32, rois (V,4), lv (), uv ())
      → counts (V,) int32.
    """
    axes = db_axes(mesh)
    return _per_shard(mesh, kops.cp_count_packed,
                      (P(axes, None, None), P(axes, None), P(), P()),
                      P(axes))


def make_cp_multi_packed_step(mesh: Mesh):
    """``make_cp_multi_step`` over packed words.

    Signature: (packed (B,H,words), rois (Q,B,4), lvs (Q,), uvs (Q,))
      → counts (Q,B) int32.
    """
    axes = db_axes(mesh)
    return _per_shard(mesh, kops.cp_count_multi_packed,
                      (P(axes, None, None), P(None, axes, None), P(), P()),
                      P(None, axes))


def make_mask_agg_packed_step(mesh: Mesh):
    """``make_mask_agg_step`` over packed words.

    Signature: (group_packed (G,S,H,words), rois (G,4), thresh () f32)
      → (inter (G,), union (G,)) int32.
    """
    axes = db_axes(mesh)
    return _per_shard(mesh, kops.mask_agg_counts_packed,
                      (P(axes, None, None, None), P(axes, None), P()),
                      (P(axes), P(axes)))


def make_pair_counts_packed_step(mesh: Mesh):
    """``make_pair_counts_step`` over packed words.

    Signature: (packed_a (B,H,words), packed_b (B,H,words), rois (B,4),
                ta () f32, tb () f32)
      → (inter (B,), union (B,), diff (B,)) int32.
    """
    axes = db_axes(mesh)
    return _per_shard(mesh, kops.pair_counts_packed,
                      (P(axes, None, None), P(axes, None, None),
                       P(axes, None), P(), P()),
                      (P(axes), P(axes), P(axes)))


def make_fused_verify_step(mesh: Mesh):
    """The bounds+verify megakernel on the mesh: batch rows shard over all
    devices, the Q descriptor axis (rois/decided/lb) shards with them on
    the batch dimension, and every shard answers its rows collective-free
    in one launch.

    Signature: (packed (B,H,words), rois (Q,B,4), lvs (Q,), uvs (Q,),
                decided (Q,B) int32, lb (Q,B) int32)
      → counts (Q,B) int32.
    """
    axes = db_axes(mesh)
    qb = P(None, axes)
    return _per_shard(mesh, kops.fused_bounds_verify,
                      (P(axes, None, None), P(None, axes, None), P(), P(),
                       qb, qb),
                      qb)
