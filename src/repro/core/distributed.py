"""Distributed MaskSearch — the query engine sharded over a TPU mesh.

The paper's prototype is single-node; this module is the beyond-paper
scale-out.  The mask DB (mask bytes + CHI tables + ROI table) is sharded
row-wise over every mesh axis (a DB of N masks becomes N/num_devices rows per
chip).  Four device-side *step* functions cover the engine's hot paths; each
is jit-compiled with explicit shardings (steps that launch a Pallas kernel
run it per shard under ``jax.shard_map``) and is what the multi-pod dry-run
lowers for the "masksearch" cells:

  * ``filter_bounds_step`` — CHI bounds + predicate verdicts for every local
    row.  Collective-free (embarrassingly parallel); one ``psum`` reports
    global accept/undecided counts.
  * ``verify_step``        — exact CP over a dense batch of survivor masks
    (the verification round; Pallas kernel on TPU).
  * ``topk_step``          — bound-driven distributed top-k: per-shard
    ``lax.top_k`` over upper bounds, ``all_gather`` of k candidates per
    shard, global threshold τ = k-th best lower bound, survivor flags.
  * ``iou_agg_step``       — fused thresholded intersection/union counts for
    group (MASK_AGG) queries.

Since the backend refactor these step functions are no longer a parallel
universe: :class:`repro.core.backend.MeshBackend` drives them from the
public query path (``run_plan(plan, backend="mesh")``) — the bounds step is
the CP leaf of every mesh bounds pass, ``verify_step`` answers verification
batches, ``topk_select_step`` is the ranking frontier's collective, and
``mask_agg_step`` serves MASK_AGG group verification.  The original
fused-verdict steps (``filter_bounds_step``/``topk_step``/``iou_agg_step``)
remain for the dry-run's lowered cells and the multi-device tests.

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
from . import cp as cp_lib
from .exprs import cell_counts_jnp, pair_cell_bounds_jnp

def db_axes(mesh: Mesh) -> tuple[str, ...]:
    """All mesh axes — DB rows shard over the full device set."""
    return tuple(mesh.axis_names)


def row_sharding(mesh: Mesh, ndim: int) -> NamedSharding:
    return NamedSharding(mesh, P(db_axes(mesh), *([None] * (ndim - 1))))


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


def _bounds_from_corners(table, corners, area, kl_in, ku_in, kl_out, ku_out):
    """Same 8-corner math as chi._bounds_device, but with corner indices as
    device arrays (computed on device from boundary tables) so the whole
    bounds pass stays on-chip."""
    il, ih, jl, jh, ol, oh, pl, ph = [corners[:, i] for i in range(8)]
    inner_ok = (ih > il) & (jh > jl) & (ku_in > kl_in)
    lb = jnp.where(inner_ok,
                   chi_lib._lookup(table, il, ih, jl, jh,
                                   jnp.minimum(kl_in, ku_in), ku_in), 0)
    outer_ok = (oh > ol) & (ph > pl) & (ku_out > kl_out)
    ub = jnp.where(outer_ok,
                   chi_lib._lookup(table, ol, oh, pl, ph,
                                   jnp.minimum(kl_out, ku_out), ku_out), 0)
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


def make_filter_bounds_step(mesh: Mesh, op: str = "<"):
    """Build the jitted distributed bounds+verdict pass.

    Signature: (chi_tables (N,G+1,G+1,NB+1), rois (N,4), row_bounds, col_bounds,
                value_ks (4,) int32 [kl_in,ku_in,kl_out,ku_out], threshold ())
      → accept (N,) bool, undecided (N,) bool, counts (2,) int32 global.
    """
    axes = db_axes(mesh)

    def step(tables, rois, row_bounds, col_bounds, value_ks, threshold):
        corners, area = device_resolve(rois, row_bounds, col_bounds)
        kl_in, ku_in, kl_out, ku_out = (value_ks[0], value_ks[1],
                                        value_ks[2], value_ks[3])
        lb, ub = _bounds_from_corners(tables, corners, area,
                                      kl_in, ku_in, kl_out, ku_out)
        if op in ("<", "<="):
            accept = (ub < threshold) if op == "<" else (ub <= threshold)
            reject = (lb >= threshold) if op == "<" else (lb > threshold)
        else:
            accept = (lb > threshold) if op == ">" else (lb >= threshold)
            reject = (ub <= threshold) if op == ">" else (ub < threshold)
        undecided = ~(accept | reject)
        counts = jnp.stack([jnp.sum(accept.astype(jnp.int32)),
                            jnp.sum(undecided.astype(jnp.int32))])
        return accept, undecided, counts

    row = NamedSharding(mesh, P(axes))
    row2 = NamedSharding(mesh, P(axes, None))
    rep = replicated(mesh)
    return jax.jit(
        step,
        in_shardings=(NamedSharding(mesh, P(axes, None, None, None)),
                      row2, rep, rep, rep, rep),
        out_shardings=(row, row, rep),
    )


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


def make_topk_step(mesh: Mesh, k: int, desc: bool = True):
    """Bound-driven distributed top-k candidate selection (one shard_map).

    Per device: bounds → local top-k upper bounds (optimistic candidates) and
    local top-k lower bounds (pessimistic threshold contributors).  One
    ``all_gather`` each merges them; τ = k-th best gathered lower bound; every
    local row with ub ≥ τ survives to verification.

    Signature: (chi_tables, rois, row_bounds, col_bounds, value_ks)
      → (cand_vals (D*k,), cand_ids (D*k,), tau (), survivors (N,) bool)
    """
    axes = db_axes(mesh)
    n_dev = int(np.prod([mesh.shape[a] for a in axes]))

    def local(tables, rois, row_bounds, col_bounds, value_ks, base_ids):
        corners, area = device_resolve(rois, row_bounds, col_bounds)
        lb, ub = _bounds_from_corners(
            tables, corners, area,
            value_ks[0], value_ks[1], value_ks[2], value_ks[3])
        score_opt = ub if desc else -lb
        score_pes = lb if desc else -ub
        top_opt, idx_opt = jax.lax.top_k(score_opt, k)
        top_pes, _ = jax.lax.top_k(score_pes, k)
        gathered_opt = jax.lax.all_gather(top_opt, axes, tiled=True)
        gathered_ids = jax.lax.all_gather(base_ids[idx_opt], axes, tiled=True)
        gathered_pes = jax.lax.all_gather(top_pes, axes, tiled=True)
        # τ: k-th best pessimistic score globally
        tau = jax.lax.top_k(gathered_pes, k)[0][-1]
        survivors = score_opt >= tau
        return gathered_opt, gathered_ids, tau, survivors

    mapped = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axes, None, None, None), P(axes, None), P(), P(), P(),
                  P(axes)),
        out_specs=(P(), P(), P(), P(axes)),
        check_vma=False,
    )
    return jax.jit(mapped), n_dev * k


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
    mesh backend runs once per distinct CP term of a plan — the generic
    analogue of ``filter_bounds_step``, which additionally folds in one
    comparison verdict.

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

    The collective at the heart of ``topk_step``, but over *precomputed*
    bounds scores instead of re-deriving them from CHI tables — so any
    ranking expression the plan IR can express (ratios, sums of CPs)
    shards.  Per device: mask non-definite rows to −inf, local top-k, one
    ``all_gather`` of (value, row-id) pairs, global top-k.  Returns the
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
    verification, group rows sharded over all devices (the counts-level
    sibling of ``iou_agg_step``; on TPU dispatches to the Pallas
    ``mask_agg`` kernel).

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


def make_iou_agg_step(mesh: Mesh):
    """Fused group IoU: masks (Ngroups, n_types, H, W) → IoU scores.

    Signature: (group_masks, rois (Ngroups,4), thresh ()) → iou (Ngroups,) f32.
    On TPU dispatches to the Pallas ``mask_agg_iou`` kernel.
    """
    axes = db_axes(mesh)

    def step(group_masks, rois, thresh):
        binary = group_masks > thresh
        inter = jnp.all(binary, axis=1)
        union = jnp.any(binary, axis=1)
        h, w = group_masks.shape[-2:]
        inside = cp_lib._roi_mask(rois, h, w)
        inter_ct = jnp.sum(inter & inside, axis=(1, 2)).astype(jnp.float32)
        union_ct = jnp.sum(union & inside, axis=(1, 2)).astype(jnp.float32)
        return jnp.where(union_ct > 0, inter_ct / jnp.maximum(union_ct, 1), 0.0)

    return jax.jit(
        step,
        in_shardings=(NamedSharding(mesh, P(axes, None, None, None)),
                      NamedSharding(mesh, P(axes, None)),
                      replicated(mesh)),
        out_shardings=NamedSharding(mesh, P(axes)),
    )


# ---------------------------------------------------------------------------
# Host-side distributed query driver (runs the steps; used on real meshes and
# in the multi-device CPU tests)
# ---------------------------------------------------------------------------


class DistributedEngine:
    """Thin host orchestrator over the step functions for a sharded DB."""

    def __init__(self, mesh: Mesh, cfg: chi_lib.CHIConfig):
        self.mesh = mesh
        self.cfg = cfg
        self._filter_steps: dict[str, object] = {}
        self._verify = make_verify_step(mesh)
        self._topk_steps: dict[tuple, object] = {}

    def _value_ks(self, lv: float, uv: float) -> np.ndarray:
        return value_ks(self.cfg, lv, uv)

    def filter_bounds(self, tables, rois, lv, uv, op, threshold):
        if op not in self._filter_steps:
            self._filter_steps[op] = make_filter_bounds_step(self.mesh, op)
        rb = jnp.asarray(self.cfg.row_bounds, jnp.int32)
        cb = jnp.asarray(self.cfg.col_bounds, jnp.int32)
        return self._filter_steps[op](
            tables, jnp.asarray(rois, jnp.int32), rb, cb,
            jnp.asarray(self._value_ks(lv, uv)),
            jnp.asarray(threshold, jnp.int32))

    def verify(self, masks, rois, lv, uv):
        return self._verify(masks, jnp.asarray(rois, jnp.int32),
                            jnp.float32(lv), jnp.float32(uv))

    def topk_candidates(self, tables, rois, lv, uv, k, desc=True, ids=None):
        key = (k, desc)
        if key not in self._topk_steps:
            self._topk_steps[key] = make_topk_step(self.mesh, k, desc)[0]
        n = tables.shape[0]
        if ids is None:
            ids = jnp.arange(n, dtype=jnp.int32)
        rb = jnp.asarray(self.cfg.row_bounds, jnp.int32)
        cb = jnp.asarray(self.cfg.col_bounds, jnp.int32)
        return self._topk_steps[key](
            tables, jnp.asarray(rois, jnp.int32), rb, cb,
            jnp.asarray(self._value_ks(lv, uv)), ids)
