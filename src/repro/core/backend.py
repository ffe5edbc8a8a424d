"""Pluggable execution backends — one physical filter–verification layer.

The engine's run objects (:mod:`.engine`) are *drivers*: they own the
frontier bookkeeping (what is decided, what is pending, when a ranking is
final) but delegate every physical operation to an :class:`ExecBackend`,
the way SeeSaw routes one interactive query API over interchangeable
vector backends.  Four primitives cover every plan the IR can express:

* ``bounds(ctx, expr)``            — CHI-derived (lb, ub) for every
                                     candidate of a value expression (the
                                     filter phase; no mask bytes touched).
* ``verify_counts(ctx, batch, terms)`` — exact per-CP-term pixel counts for
                                     one verification batch (the
                                     verification phase).
* ``topk_candidates(lb, ub, k, …)`` — the ranking frontier: which
                                     candidates can still reach the top-k.
* ``mask_agg_counts(gctx, node, gidx)`` — fused thresholded
                                     intersection/union counts for MASK_AGG
                                     group verification.

plus ``fused_counts`` — the service scheduler's cross-query
``cp_count_multi`` pass, run on whichever backend owns the store — and
the dual-mask pair primitives (DESIGN.md §9): ``fused_pair_counts``
(Q pair descriptors over a batch of per-image mask pairs → (Q, 3, B)
inter/union/diff counts) with the shared driver ``pair_verify_counts``
(pair bounds stay host-side: the cell decomposition needs per-cell CHI
counts, and sharing that code path keeps pruning bit-identical).

Three implementations:

* :class:`HostBackend`   — the NumPy/``MaskEvalContext`` paths extracted
                           from the engine, behavior-preserving (partial
                           ROI-row loads, shared-load cache, I/O metering).
* :class:`DeviceBackend` — the store's mask bytes and CHI table pinned
                           resident in device memory; bounds *and*
                           verification are jit-compiled over the Pallas
                           kernels, so the filter phase leaves the host.
* :class:`MeshBackend`   — :mod:`.distributed`'s step functions over
                           ``shard_map``: rows shard over every mesh axis,
                           the top-k frontier is one ``all_gather``
                           collective, and verification/MASK_AGG batches
                           run sharded.

Equivalence contract (property-tested in
``tests/test_backend_equivalence.py``): all three backends return
identical ids/scores and identical ``n_verified`` accounting for any plan.
Bounds interval arithmetic stays on the host in float64 for every backend
(only the CP leaf differs, and it is integral), and the device/mesh top-k
collectives return the τ *row id* rather than a float32 τ value, so the
frontier comparison happens at full host precision everywhere.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops as kops
from ..obs import trace as _trace
from ..obs.metrics import REGISTRY as _REG
from .chi import gather_rows
from .distributed import (_bounds_from_corners, device_resolve,
                          make_chi_bounds_step, make_cp_multi_packed_step,
                          make_cp_multi_step, make_fused_verify_step,
                          make_mask_agg_packed_step, make_mask_agg_step,
                          make_pair_cells_step,
                          make_pair_counts_packed_step,
                          make_pair_counts_step, make_topk_select_step,
                          make_verify_packed_step, make_verify_step,
                          value_ks)
from .exprs import _threshold_ks, cell_counts_jnp, pair_cell_bounds_jnp

F32_MAX = 3.4e38  # finite stand-in for +inf in float32 kernel compares
_F32_MAX = F32_MAX

_BACKEND_RESOLUTIONS = _REG.counter(
    "masksearch_backend_resolutions_total",
    "get_backend() resolutions by resolved backend", ("backend",))
_BACKEND_BUILDS = _REG.counter(
    "masksearch_backend_constructions_total",
    "Named backend instances constructed (the resident mask/CHI upload "
    "happens here)", ("backend",))
_BACKEND_SYNCS = _REG.counter(
    "masksearch_backend_syncs_total",
    "Epoch re-pins of resident backend state after store mutations",
    ("backend",))


def spec_arrays(specs, dtype=np.float32):
    """Stack fused-pass descriptors ``(rois, lv, uv)`` into kernel inputs,
    clamping +inf upper values to the float32-safe ceiling — the one
    canonical layout shared by every backend and the service scheduler."""
    rois_q = np.stack([s[0] for s in specs]).astype(np.int32)
    lvs = np.asarray([s[1] for s in specs], dtype)
    uvs = np.asarray([min(s[2], F32_MAX) for s in specs], dtype)
    return rois_q, lvs, uvs


def is_packed(store) -> bool:
    """Whether a store serves the bitpacked binary-mask tier (DESIGN.md §12).

    ``getattr`` so snapshots, stores predating the tier, and test doubles
    all read as float."""
    return bool(getattr(store, "packed", False))


def chi_verdicts(terms, batch: np.ndarray, bounds_of):
    """Assemble the megakernel's CHI-verdict inputs from memoized bounds.

    ``bounds_of(term) -> (lb, ub) | None`` is a *memo-only* getter: a term
    whose filter-phase bounds were never computed returns None and is simply
    treated as undecided everywhere — always correct, never an extra bounds
    pass.  Returns ``decided`` (Q, B) int32 0/1 and ``lb`` (Q, B) int32
    aligned with ``terms`` × ``batch``."""
    q, b = len(terms), len(batch)
    decided = np.zeros((q, b), np.int32)
    lb_out = np.zeros((q, b), np.int32)
    for i, t in enumerate(terms):
        bnd = bounds_of(t) if bounds_of is not None else None
        if bnd is None:
            continue
        tlb = np.asarray(bnd[0])[batch]
        tub = np.asarray(bnd[1])[batch]
        eq = tlb == tub
        decided[i] = eq
        lb_out[i] = np.where(eq, tlb, 0)
    return decided, lb_out


class ExecBackend:
    """Protocol for the physical layer under the engine's run drivers."""

    name = "abstract"

    def sync(self) -> None:
        """Refresh any store-resident state (pinned masks, CHI tables) to
        the store's current epoch.  Called by :func:`get_backend` on every
        resolution, so a backend instance cached across mutations never
        serves pre-epoch residency.  Host is stateless — no-op."""

    def bounds(self, ctx, expr):
        """(lb, ub) float64 arrays over ``ctx``'s candidates for ``expr``."""
        raise NotImplementedError

    def verify_counts(self, ctx, batch: np.ndarray, terms) -> dict:
        """Exact counts for one verification batch: CP term → float64
        array aligned with ``batch`` (candidate indices into ``ctx``)."""
        raise NotImplementedError

    def topk_candidates(self, lb, ub, k: int, desc: bool,
                        definite: np.ndarray,
                        possible: np.ndarray) -> np.ndarray:
        """The static pruning frontier: candidates whose optimistic bound
        beats the k-th best pessimistic bound among ``definite``
        (definitely-qualifying) candidates.  Returns an ``alive`` bool
        array ⊆ ``possible``; when fewer than k are definite nothing can
        be pruned and ``possible`` is returned unchanged."""
        raise NotImplementedError

    def mask_agg_counts(self, gctx, node, gidx: np.ndarray) -> np.ndarray:
        """Exact MASK_AGG counts (thresholded intersect/union inside the
        ROI) for group indices ``gidx`` of a :class:`GroupEvalContext`."""
        raise NotImplementedError

    def fused_verify_counts(self, ctx, batch: np.ndarray, terms,
                            bounds_of=None) -> dict:
        """The bounds+verify megakernel route (packed stores, DESIGN.md
        §12): one launch answers *every* CP descriptor of a verification
        batch — CHI-decided (term, mask) entries (memoized lb == ub) pass
        their bound straight through, the undecided remainder is counted
        from the packed words.  ``bounds_of(term) -> (lb, ub) | None`` is a
        memo-only getter over the run's filter-phase bounds; None →
        undecided (always correct).  Float stores fall back to the classic
        per-term :meth:`verify_counts` path, so drivers can call this
        unconditionally."""
        terms = list(terms)
        if not is_packed(getattr(ctx, "store", None)):
            return self.verify_counts(ctx, batch, terms)
        batch = np.asarray(batch)
        pos = ctx.positions[batch]
        rois_q, lvs, uvs = spec_arrays(
            [(ctx.resolve_rois(t.roi, pos), t.lv, t.uv) for t in terms])
        decided, lb = chi_verdicts(terms, batch, bounds_of)
        counts = self._fused_verify_batch(ctx, batch, pos, rois_q, lvs, uvs,
                                          decided, lb)
        return {t: np.asarray(counts[i], np.float64)
                for i, t in enumerate(terms)}

    def _fused_verify_batch(self, ctx, batch, pos, rois_q, lvs, uvs,
                            decided, lb) -> np.ndarray:
        """Physical megakernel dispatch: packed batch rows + assembled
        descriptors/verdicts → (Q, B) int32 exact counts."""
        raise NotImplementedError

    @property
    def gathered_bytes(self) -> int:
        """Bytes of resident mask rows this backend's device steps have
        gathered (monotonic).  Backends that hold no rows on a device
        gather none."""
        return 0

    @property
    def chi_lookup_bytes(self) -> int:
        """Bytes of CHI entries this backend's bounds steps have looked up
        in place (monotonic).  Backends that read whole CHI rows report
        none, and the engine counts the rows instead."""
        return 0

    def fused_counts(self, store, positions: np.ndarray,
                     specs) -> np.ndarray:
        """The scheduler's fused pass: Q ``(rois, lv, uv)`` descriptors
        over the masks at ``positions`` → (Q, B) counts from one pass
        over the bytes."""
        raise NotImplementedError

    PAIR_STAT_ROW = {"inter": 0, "union": 1, "diff": 2}

    def fused_pair_counts(self, store, pos_a: np.ndarray, pos_b: np.ndarray,
                          specs) -> np.ndarray:
        """Dual-mask pass: Q ``(rois, ta, tb)`` descriptors over the
        per-image mask pairs ``(pos_a[i], pos_b[i])`` → (Q, 3, B) counts —
        rows indexed by :attr:`PAIR_STAT_ROW` (inter / union / diff=|A∖B|).
        Each pair's bytes are touched once per descriptor batch; all three
        stats come from that one pass (DESIGN.md §9)."""
        raise NotImplementedError

    def pair_verify_counts(self, pctx, batch: np.ndarray, terms) -> dict:
        """Exact pair-term counts for one verification batch: pair term →
        float64 array aligned with ``batch`` (candidate indices into
        ``pctx``).  Terms sharing a (ta, tb, roi) pair spec — e.g. IoU's
        intersection and union — are answered by a single fused kernel
        pass.  Shared driver; the physical pass is
        :meth:`fused_pair_counts`."""
        terms = list(terms)
        batch = np.asarray(batch)
        spec_ix: dict = {}
        specs: list = []
        for t in terms:
            key = (t.ta, t.tb, t.roi)
            if key not in spec_ix:
                spec_ix[key] = len(specs)
                specs.append((pctx.pair_rois(t.roi, batch), t.ta, t.tb))
        counts = self.fused_pair_counts(pctx.store, pctx.pos_a[batch],
                                        pctx.pos_b[batch], specs)
        return {t: np.asarray(counts[spec_ix[(t.ta, t.tb, t.roi)],
                                     self.PAIR_STAT_ROW[t.stat]], np.float64)
                for t in terms}


# ---------------------------------------------------------------------------
# Host — the extracted NumPy / MaskEvalContext physical layer
# ---------------------------------------------------------------------------


class HostBackend(ExecBackend):
    """The original physical layer: bounds through the store's CHI gather,
    verification through metered ``store.load`` (partial ROI-row loads,
    shared-load cache) + the ``cp_count`` kernel, frontiers in NumPy."""

    name = "host"

    def bounds(self, ctx, expr):
        return ctx.bounds(expr)

    def verify_counts(self, ctx, batch, terms):
        # One ctx.exact per *distinct* term: masks_for caches the load, so
        # a predicate and a ranking sharing an expression share its bytes.
        return {t: ctx.exact(t, batch) for t in terms}

    def topk_candidates(self, lb, ub, k, desc, definite, possible):
        if desc:
            dvals = lb[definite]
            if len(dvals) >= k:
                tau = np.partition(dvals, -k)[-k]
                return possible & (ub >= tau)
            return possible.copy()
        dvals = ub[definite]
        if len(dvals) >= k:
            tau = np.partition(dvals, k - 1)[k - 1]
            return possible & (lb <= tau)
        return possible.copy()

    def mask_agg_counts(self, gctx, node, gidx):
        gidx = np.asarray(gidx)
        s = gctx.groups.shape[1]
        flat_idx = (gidx[:, None] * s + np.arange(s)[None, :]).reshape(-1)
        masks = gctx._ctx.masks_for(flat_idx)
        # row shape is (H, W) float or (H, words) packed — keep it as-is
        masks = masks.reshape((len(gidx), s) + masks.shape[1:])
        rois = gctx.resolve_group_rois(node.roi, gidx)
        # fused threshold+agg+count → Pallas mask_agg kernel on TPU
        if is_packed(gctx._ctx.store):
            inter, union = kops.mask_agg_counts_packed(
                jnp.asarray(masks), jnp.asarray(rois),
                jnp.asarray(node.thresh, jnp.float32))
        else:
            inter, union = kops.mask_agg_counts(
                jnp.asarray(masks), jnp.asarray(rois),
                jnp.asarray(node.thresh, masks.dtype))
        counts = inter if node.agg == "intersect" else union
        return np.asarray(counts, np.float64)

    def fused_counts(self, store, positions, specs):
        masks = store.load(positions)
        if is_packed(store):
            rois_q, lvs, uvs = spec_arrays(specs)
            return np.asarray(kops.cp_count_multi_packed(
                jnp.asarray(masks), jnp.asarray(rois_q),
                jnp.asarray(lvs), jnp.asarray(uvs)))
        rois_q, lvs, uvs = spec_arrays(specs, masks.dtype)
        return np.asarray(kops.cp_count_multi(
            jnp.asarray(masks), jnp.asarray(rois_q),
            jnp.asarray(lvs), jnp.asarray(uvs)))

    def fused_pair_counts(self, store, pos_a, pos_b, specs):
        # One metered load of the *union* of both roles' rows — a mask
        # shared by several pairs (or both roles) pays its bytes once.
        pos_a, pos_b = np.asarray(pos_a), np.asarray(pos_b)
        upos = np.unique(np.concatenate([pos_a, pos_b]))
        loaded = store.load(upos)
        a = jnp.asarray(loaded[np.searchsorted(upos, pos_a)])
        b = jnp.asarray(loaded[np.searchsorted(upos, pos_b)])
        packed = is_packed(store)
        kernel = kops.pair_counts_packed if packed else kops.pair_counts
        tdt = jnp.float32 if packed else a.dtype
        out = np.empty((len(specs), 3, len(pos_a)), np.int64)
        for qi, (rois, ta, tb) in enumerate(specs):
            trio = kernel(a, b, jnp.asarray(rois, jnp.int32),
                          jnp.asarray(ta, tdt), jnp.asarray(tb, tdt))
            for row, counts in enumerate(trio):
                out[qi, row] = np.asarray(counts)
        return out

    def _fused_verify_batch(self, ctx, batch, pos, rois_q, lvs, uvs,
                            decided, lb):
        # masks_for meters the load (in packed bytes) and shares rows with
        # any other term touching the same candidates.
        masks = ctx.masks_for(batch)
        return np.asarray(kops.fused_bounds_verify(
            jnp.asarray(masks), jnp.asarray(rois_q), jnp.asarray(lvs),
            jnp.asarray(uvs), jnp.asarray(decided), jnp.asarray(lb)))


# ---------------------------------------------------------------------------
# Device — single device, masks + CHI pinned resident in HBM
# ---------------------------------------------------------------------------


# CHI entries one CP-leaf bounds step reads per candidate: 8 corners for
# the lower bound and 8 for the upper.
CHI_LOOKUPS = 16


@jax.jit
def _device_cp_bounds(tables, pos, rois, rb, cb, ks):
    """CP-leaf bounds with corner resolution and the 8-corner lookups all
    on device (the filter phase leaving the host).  The lookups read the
    16 entries each candidate needs at ``(pos, i, j, k)`` of the resident
    table as it lies; no row is gathered, copied or relaid out.  The tier
    is implicit in the operands — ``device_resolve`` derives the grid
    from ``rb``'s length — so one compilation serves each tier shape."""
    corners, area = device_resolve(rois, rb, cb)
    return _bounds_from_corners(tables, corners, area,
                                ks[0], ks[1], ks[2], ks[3], pos=pos)


@functools.partial(jax.jit, static_argnames=("stat",))
def _device_pair_cells(tables, pos_a, pos_b, ks, rois, rb, cb, stat):
    """Pair-term cell-combine with both role gathers, the per-cell
    thresholded counts and the cell algebra all on device — the pair
    filter phase leaving the host like the CP leaf (DESIGN.md §13).
    ``ks`` holds [ka_in, ka_out, kb_in, kb_out] value-edge indices."""
    tab_a = gather_rows(tables, pos_a)
    tab_b = gather_rows(tables, pos_b)
    lo_a = cell_counts_jnp(tab_a, ks[0])
    hi_a = cell_counts_jnp(tab_a, ks[1])
    lo_b = cell_counts_jnp(tab_b, ks[2])
    hi_b = cell_counts_jnp(tab_b, ks[3])
    return pair_cell_bounds_jnp(stat, lo_a, hi_a, lo_b, hi_b, rois, rb, cb)


def _batch(rows, pos, row_shape):
    """The resident rows at ``pos`` as a ``(len(pos),) + row_shape`` batch.

    ``rows`` is the store's resident array (``MaskStore.device_masks``,
    one mask per leading index: packed words as 2-D rows, float pixels in
    lanes of 128): whole masks gather as they lie, and only the batch is
    reshaped.  The same gather from a 3-D ``(n, H, W')`` array would first
    relayout the whole store, and from 2-D float rows it passes the whole
    store through column slabs (DESIGN.md §7).  The float verification
    steps over lane rows do not come here: their kernels read the rows in
    place (``_in_lanes``); the packed steps, the pair pass's ``gather`` and
    float 2-D rows do.

    The barrier keeps XLA from fusing the batch's reshape into the Pallas
    call's operand: fused, a batch of 104 rows took the TPU compiler 7 s
    instead of 1 s, and every new batch size of a warm-up paid it."""
    return jax.lax.optimization_barrier(
        rows[pos].reshape(pos.shape + row_shape))


def _in_lanes(rows) -> bool:
    """Whether resident float rows lie in lanes of 128, ``(n, H·W/128,
    128)``, so a verification kernel can read each row where it lies."""
    return rows.ndim == 3 and rows.shape[-1] == 128


@functools.partial(jax.jit, static_argnames=("row_shape",))
def _device_multi_counts(masks, pos, rois_q, lvs, uvs, row_shape):
    """Answer Q CP descriptors over the resident float rows at ``pos`` in
    one fused kernel pass: read in place from lane rows, else over a
    gathered batch."""
    if _in_lanes(masks):
        return kops.cp_count_multi_inplace(masks, pos, rois_q, lvs, uvs,
                                           row_shape=row_shape)
    return kops.cp_count_multi(_batch(masks, pos, row_shape), rois_q, lvs,
                               uvs)


@functools.partial(jax.jit, static_argnames=("k",))
def _device_kth_index(pes, definite, k):
    masked = jnp.where(definite, pes, -jnp.inf)
    return jax.lax.top_k(masked, k)[1][k - 1]


@functools.partial(jax.jit, static_argnames=("s", "row_shape"))
def _device_group_counts(masks, flat_pos, rois, thresh, s, row_shape):
    if _in_lanes(masks):
        return kops.mask_agg_counts_inplace(masks, flat_pos, rois, thresh,
                                            s=s, row_shape=row_shape)
    grp = _batch(masks, flat_pos, row_shape)
    return kops.mask_agg_counts(grp.reshape((-1, s) + row_shape), rois,
                                thresh)


@functools.partial(jax.jit, static_argnames=("row_shape",))
def _device_multi_counts_packed(packed, pos, rois_q, lvs, uvs, row_shape):
    """Packed-tier sibling of :func:`_device_multi_counts`."""
    return kops.cp_count_multi_packed(_batch(packed, pos, row_shape), rois_q,
                                      lvs, uvs)


@functools.partial(jax.jit, static_argnames=("s", "row_shape"))
def _device_group_counts_packed(packed, flat_pos, rois, thresh, s, row_shape):
    grp = _batch(packed, flat_pos, row_shape)
    return kops.mask_agg_counts_packed(grp.reshape((-1, s) + row_shape), rois,
                                       thresh)


@functools.partial(jax.jit, static_argnames=("row_shape",))
def _device_fused_verify(packed, pos, rois_q, lvs, uvs, decided, lb,
                         row_shape):
    """Gather a verification batch from the resident packed words and run
    the bounds+verify megakernel — one launch for the whole batch."""
    return kops.fused_bounds_verify(_batch(packed, pos, row_shape), rois_q,
                                    lvs, uvs, decided, lb)


@functools.partial(jax.jit, static_argnames=("row_shape",))
def gather(masks, pos, row_shape):
    """One role of the fused pair pass: the resident rows at ``pos`` as a
    ``(B,) + row_shape`` batch, left on the device.  Its program is named
    ``gather`` in the device trace, where the verification roofline
    counts it among the verification steps."""
    return _batch(masks, pos, row_shape)


class _KthValueMixin:
    """Shared τ finalization: the device/mesh collectives select over
    *float32* scores and return the k-th best row's id; τ itself is then
    re-derived on the host in float64, so the frontier is bit-identical to
    HostBackend's ``np.partition`` path.

    The float32 cast is order-preserving but not injective: scores closer
    than one f32 ulp collapse into a tie class, and the collective's pick
    within that class is arbitrary — reading its float64 value directly
    could yield a τ *larger* than the true k-th value and over-prune.  So
    when the selected row's f32 score is shared, the exact τ is resolved
    from the (tiny) tie class at float64: it is the m-th largest member,
    where m = k − (#definite scores strictly above the class)."""

    def _alive_from_index(self, lb, ub, k, desc, definite, possible,
                          pes32, tau_idx):
        pes64 = lb if desc else -ub
        if tau_idx >= len(pes64):   # τ fell on a padded −inf row: no pruning
            return possible.copy()
        # Read τ's class through the same masked view the collective ranked
        # (non-definite rows are −inf there), not the raw score array.
        tau32 = pes32[tau_idx] if definite[tau_idx] else np.float32(-np.inf)
        tie = definite & (pes32 == tau32)
        n_tie = int(np.count_nonzero(tie))
        if n_tie == 0:              # masked −inf pick outside definite
            return possible.copy()
        if n_tie == 1:
            tau = pes64[np.nonzero(tie)[0][0]]
        else:
            m = k - int(np.count_nonzero(definite & (pes32 > tau32)))
            vals = pes64[tie]
            tau = np.partition(vals, len(vals) - m)[len(vals) - m]
        if desc:
            return possible & (ub >= tau)
        return possible & (lb <= -tau)


@dataclasses.dataclass
class BackendStats:
    """Host↔device traffic of the device backend (monotonic, always on;
    ``/metrics`` as ``masksearch_backend_*``): bytes of step arguments
    converted host→device, bytes fetched device→host, calls into jitted
    steps or eager device ops, output arrays fetched, bytes of resident
    mask rows the verification steps read (rows × one stored row,
    ``gathered_bytes``), of those rows the ones a kernel read in place
    from the resident store, with no gathered batch (``inplace_rows``),
    and the candidates whose CP-leaf bounds were looked up in place in
    the resident CHI table (``chi_inplace_rows``, ``CHI_LOOKUPS`` entries
    each)."""
    h2d_bytes: int = 0
    d2h_bytes: int = 0
    device_calls: int = 0
    fetches: int = 0
    gathered_bytes: int = 0
    inplace_rows: int = 0
    chi_inplace_rows: int = 0


class DeviceBackend(_KthValueMixin, ExecBackend):
    """Mask bytes + CHI table pinned in device memory; bounds and
    verification jit-compiled over the Pallas kernels.

    Every call into the device goes through :meth:`_step`, which counts
    the host↔device traffic (:class:`BackendStats`) and, with tracing on,
    splits the call into ``device.call`` / ``device.wait`` /
    ``device.fetch`` spans."""

    name = "device"

    def __init__(self, store):
        self.store = store
        self.cfg = store.cfg
        self.stats = BackendStats()
        self._packed = is_packed(store)   # resident array is uint32 words
        self._masks = store.device_masks()          # one mask per index
        self._row_shape = store.row_shape           # what a kernel sees
        self._row_nbytes = store.row_nbytes
        # float lane rows: the CP and grouped kernels read them in place
        self._inplace = not self._packed and _in_lanes(self._masks)
        self._tables = store.chi_table
        self._epoch = getattr(store, "epoch", 0)
        self._rb = jnp.asarray(self.cfg.row_bounds, jnp.int32)
        self._cb = jnp.asarray(self.cfg.col_bounds, jnp.int32)
        self._tier_bnds: dict = {}   # tier grid → (row_bounds, col_bounds)

    def sync(self):
        """Re-pin the resident mask/CHI arrays after a store mutation.  The
        store maintains its device caches incrementally (appends
        ``device_put`` only the new chunk, updates scatter, deletes
        gather), so this is a reference refresh, not a re-upload."""
        if self._epoch == getattr(self.store, "epoch", 0):
            return
        self._masks = self.store.device_masks()
        self._tables = self.store.chi_table
        self._epoch = self.store.epoch
        _BACKEND_SYNCS.labels(backend=self.name).inc()

    # -- the one door to the device -----------------------------------------
    def _put(self, a):
        """A step argument on the device: resident arrays pass through,
        host values are converted (their device bytes counted)."""
        if isinstance(a, jax.Array):
            return a
        a = jnp.asarray(a)
        self.stats.h2d_bytes += a.nbytes
        return a

    def _fetch(self, out):
        """Device outputs (one array or a tuple) → numpy, counted."""
        if isinstance(out, tuple):
            return tuple(self._fetch(o) for o in out)
        host = np.asarray(out)
        self.stats.fetches += 1
        self.stats.d2h_bytes += host.nbytes
        return host

    @property
    def gathered_bytes(self) -> int:
        return self.stats.gathered_bytes

    @property
    def chi_lookup_bytes(self) -> int:
        return (self.stats.chi_inplace_rows * CHI_LOOKUPS *
                self._tables.dtype.itemsize)

    def _step(self, step: str, fn, *args, pick: int | None = None,
              fetch: bool = True, rows: int = 0, inplace: bool = False,
              **static):
        """One call into the device: host ``args`` converted, ``fn``
        dispatched (``static`` keywords passed through), output ``pick``
        of a tuple chosen on the device, and the result fetched to the
        host.  ``fetch=False`` returns the outputs on the device.
        ``rows`` counts the resident mask rows the step reads, and
        ``inplace`` says that its kernel reads them where they lie.

        Traced, the call is three spans with attr ``step``:
        ``device.call`` (conversion and the asynchronous dispatch),
        ``device.wait`` (``block_until_ready``) and ``device.fetch`` (the
        copy to the host).  Untraced, nothing waits but the fetch."""
        self.stats.device_calls += 1
        self.stats.gathered_bytes += rows * self._row_nbytes
        if inplace:
            self.stats.inplace_rows += rows
        traced = _trace.current_tracer().enabled
        with _trace.span("device.call") as sp:
            sp.set(step=step)
            out = fn(*[self._put(a) for a in args], **static)
            if pick is not None:
                out = out[pick]
            if traced and fetch:
                # Queue the copy to the host behind the step now, as a bare
                # fetch does: queued after the wait, it would start only
                # once the host had seen the step finish.
                for o in out if isinstance(out, tuple) else (out,):
                    o.copy_to_host_async()
        if not fetch:
            return out
        if traced:
            with _trace.span("device.wait") as sp:
                sp.set(step=step)
                jax.block_until_ready(out)
        with _trace.span("device.fetch") as sp:
            sp.set(step=step)
            return self._fetch(out)

    # -- primitives ------------------------------------------------------------
    def bounds(self, ctx, expr):
        if hasattr(ctx, "pair_rois"):
            return ctx.bounds(expr, pair_leaf=self._pair_cells)
        return ctx.bounds(expr, cp_leaf=self._cp_bounds)

    def _tier_bounds(self, g: int):
        pair = self._tier_bnds.get(g)
        if pair is None:
            tcfg = self.cfg.for_grid(g)
            pair = (jnp.asarray(tcfg.row_bounds, jnp.int32),
                    jnp.asarray(tcfg.col_bounds, jnp.int32))
            self._tier_bnds[g] = pair
        return pair

    def _cp_bounds(self, mctx, node):
        rois = mctx.resolve_rois(node.roi, mctx.positions)
        g = getattr(mctx, "tier", None)
        if g is None or g == self.cfg.grid:
            cfg, tables, rb, cb = self.cfg, self._tables, self._rb, self._cb
        else:
            # coarse ladder rung: the store's device-resident tier table
            # (maintained incrementally across mutations) + tier boundaries
            cfg = self.cfg.for_grid(g)
            tables = self.store.chi_tier_table(g)
            rb, cb = self._tier_bounds(g)
        ks = value_ks(cfg, node.lv, node.uv)
        lb, ub = self._step(
            "_device_cp_bounds", _device_cp_bounds, tables,
            np.asarray(mctx.positions), np.asarray(rois, np.int32), rb, cb,
            np.asarray(ks))
        self.stats.chi_inplace_rows += len(mctx.positions)
        return lb.astype(np.float64), ub.astype(np.float64)

    def _pair_cells(self, pctx, node):
        rois = pctx.pair_rois(node.roi)
        ka = _threshold_ks(self.cfg, node.ta)
        kb = _threshold_ks(self.cfg, node.tb)
        lb, ub = self._step(
            "_device_pair_cells", _device_pair_cells, self._tables,
            np.asarray(pctx.pos_a), np.asarray(pctx.pos_b),
            np.array([ka[0], ka[1], kb[0], kb[1]], np.int32),
            np.asarray(rois, np.int32), self._rb, self._cb, stat=node.stat)
        return lb.astype(np.float64), ub.astype(np.float64)

    def _multi(self):
        """The fused CP-count step for the resident representation."""
        if self._packed:
            return "_device_multi_counts_packed", _device_multi_counts_packed
        return "_device_multi_counts", _device_multi_counts

    def verify_counts(self, ctx, batch, terms):
        terms = list(terms)
        pos = ctx.positions[batch]
        rois_q, lvs, uvs = spec_arrays(
            [(ctx.resolve_rois(t.roi, pos), t.lv, t.uv) for t in terms])
        counts = self._step(*self._multi(), self._masks, np.asarray(pos),
                            rois_q, lvs, uvs, rows=len(pos),
                            inplace=self._inplace,
                            row_shape=self._row_shape)
        return {t: counts[i].astype(np.float64)
                for i, t in enumerate(terms)}

    def _fused_verify_batch(self, ctx, batch, pos, rois_q, lvs, uvs,
                            decided, lb):
        return self._step("_device_fused_verify", _device_fused_verify,
                          self._masks, np.asarray(pos), rois_q, lvs, uvs,
                          decided, lb, rows=len(pos),
                          row_shape=self._row_shape)

    def topk_candidates(self, lb, ub, k, desc, definite, possible):
        if k <= 0 or int(np.count_nonzero(definite)) < k:
            return possible.copy()
        pes32 = (lb if desc else -ub).astype(np.float32)
        tau_idx = int(self._step("_device_kth_index", _device_kth_index,
                                 pes32, np.asarray(definite), k=k))
        return self._alive_from_index(lb, ub, k, desc, definite, possible,
                                      pes32, tau_idx)

    def mask_agg_counts(self, gctx, node, gidx):
        gidx = np.asarray(gidx)
        s = gctx.groups.shape[1]
        flat = gctx.groups[gidx].reshape(-1)
        rois = np.asarray(gctx.resolve_group_rois(node.roi, gidx), np.int32)
        pick = 0 if node.agg == "intersect" else 1     # (inter, union)
        if self._packed:
            counts = self._step(
                "_device_group_counts_packed", _device_group_counts_packed,
                self._masks, flat, rois, np.asarray(node.thresh, np.float32),
                pick=pick, rows=len(flat), s=int(s),
                row_shape=self._row_shape)
        else:
            counts = self._step(
                "_device_group_counts", _device_group_counts, self._masks,
                flat, rois, np.asarray(node.thresh, self._masks.dtype),
                pick=pick, rows=len(flat), inplace=self._inplace, s=int(s),
                row_shape=self._row_shape)
        return counts.astype(np.float64)

    def fused_counts(self, store, positions, specs):
        rois_q, lvs, uvs = spec_arrays(specs)
        return self._step(*self._multi(), self._masks,
                          np.asarray(positions), rois_q, lvs, uvs,
                          rows=len(positions), inplace=self._inplace,
                          row_shape=self._row_shape)

    def fused_pair_counts(self, store, pos_a, pos_b, specs):
        # Both roles are resident (the store's one HBM mask array); gather
        # each role ONCE and answer every descriptor against the gathered
        # batch — zero metered bytes, 2 gathers regardless of Q.
        a = self._step("gather", gather, self._masks, np.asarray(pos_a),
                       fetch=False, rows=len(pos_a), row_shape=self._row_shape)
        b = self._step("gather", gather, self._masks, np.asarray(pos_b),
                       fetch=False, rows=len(pos_b), row_shape=self._row_shape)
        if self._packed:
            step, kernel, tdt = ("pair_counts_packed",
                                 kops.pair_counts_packed, np.float32)
        else:
            step, kernel, tdt = "pair_counts", kops.pair_counts, a.dtype
        out = np.empty((len(specs), 3, len(pos_a)), np.int64)
        for qi, (rois, ta, tb) in enumerate(specs):
            trio = self._step(step, kernel, a, b, np.asarray(rois, np.int32),
                              np.asarray(ta, tdt), np.asarray(tb, tdt))
            for row, counts in enumerate(trio):
                out[qi, row] = counts
        return out


# ---------------------------------------------------------------------------
# Mesh — distributed.py's step functions over shard_map
# ---------------------------------------------------------------------------


class MeshBackend(_KthValueMixin, ExecBackend):
    """The query engine sharded over a device mesh: every physical
    primitive is one of :mod:`.distributed`'s step functions, rows sharded
    over the flattened mesh.  Candidate sets are padded to a device-count
    multiple (padded rows carry −inf/False sentinels and are sliced off)."""

    name = "mesh"

    def __init__(self, store, mesh=None):
        self.store = store
        self.cfg = store.cfg
        if mesh is None:
            mesh = jax.make_mesh((len(jax.devices()),), ("data",),
                                 axis_types=(jax.sharding.AxisType.Auto,))
        self.mesh = mesh
        self.n_dev = int(mesh.devices.size)
        self._masks = store.resident_masks()
        self._tables_np = (store.chi_host() if hasattr(store, "chi_host")
                           else np.asarray(store.chi_table))
        self._epoch = getattr(store, "epoch", 0)
        self._rb = jnp.asarray(self.cfg.row_bounds, jnp.int32)
        self._cb = jnp.asarray(self.cfg.col_bounds, jnp.int32)
        self._bounds_step = make_chi_bounds_step(mesh)
        self._packed = is_packed(store)
        # Packed steps share the float steps' call signatures and shardings
        # (words axis for pixel-column axis), so every call site below is
        # representation-agnostic once the right step is pinned here.
        if self._packed:
            self._verify_step = make_verify_packed_step(mesh)
            self._agg_step = make_mask_agg_packed_step(mesh)
            self._multi_step = make_cp_multi_packed_step(mesh)
            self._pair_step = make_pair_counts_packed_step(mesh)
            self._fused_verify_step = make_fused_verify_step(mesh)
        else:
            self._verify_step = make_verify_step(mesh)
            self._agg_step = make_mask_agg_step(mesh)
            self._multi_step = make_cp_multi_step(mesh)
            self._pair_step = make_pair_counts_step(mesh)
            self._fused_verify_step = None
        self._select_steps: dict = {}
        self._pair_cells_steps: dict = {}   # pair stat → sharded cells step
        self._tier_bnds: dict = {}          # tier grid → (row_b, col_b)

    def sync(self):
        """Re-pin the host-resident mask/CHI arrays after a store mutation.
        The store maintains ``resident_masks`` incrementally, so memory-tier
        refreshes are a view swap; shards are re-padded lazily per step
        (the mesh has no persistent sharded residency to patch)."""
        if self._epoch == getattr(self.store, "epoch", 0):
            return
        self._masks = self.store.resident_masks()
        self._tables_np = self.store.chi_host()
        self._epoch = self.store.epoch
        _BACKEND_SYNCS.labels(backend=self.name).inc()

    def _pad(self, arr, fill=0):
        """Pad the leading dim to a positive device-count multiple."""
        n = len(arr)
        r = (-n) % self.n_dev if n else self.n_dev
        if r == 0:
            return arr, n
        pad = np.full((r,) + arr.shape[1:], fill, arr.dtype)
        return np.concatenate([arr, pad]), n

    def bounds(self, ctx, expr):
        if hasattr(ctx, "pair_rois"):
            return ctx.bounds(expr, pair_leaf=self._pair_cells)
        return ctx.bounds(expr, cp_leaf=self._cp_bounds)

    def _tier_bounds(self, g: int):
        pair = self._tier_bnds.get(g)
        if pair is None:
            tcfg = self.cfg.for_grid(g)
            pair = (jnp.asarray(tcfg.row_bounds, jnp.int32),
                    jnp.asarray(tcfg.col_bounds, jnp.int32))
            self._tier_bnds[g] = pair
        return pair

    def _cp_bounds(self, mctx, node):
        pos = np.asarray(mctx.positions)
        rois = mctx.resolve_rois(node.roi, pos).astype(np.int32)
        g = getattr(mctx, "tier", None)
        if g is None or g == self.cfg.grid:
            cfg, tables, rb, cb = self.cfg, self._tables_np, self._rb, self._cb
        else:
            # coarse ladder rung: the store's host tier cache (maintained
            # incrementally across mutations) + the tier's grid boundaries
            cfg = self.cfg.for_grid(g)
            tables = self.store.chi_tier_host(g)
            rb, cb = self._tier_bounds(g)
        tab_p, n = self._pad(tables[pos])
        rois_p, _ = self._pad(rois)
        ks = value_ks(cfg, node.lv, node.uv)
        lb, ub = self._bounds_step(tab_p, rois_p, rb, cb,
                                   jnp.asarray(ks))
        return (np.asarray(lb)[:n].astype(np.float64),
                np.asarray(ub)[:n].astype(np.float64))

    def _pair_cells(self, pctx, node):
        step = self._pair_cells_steps.get(node.stat)
        if step is None:
            step = make_pair_cells_step(self.mesh, node.stat)
            self._pair_cells_steps[node.stat] = step
        pos_a = np.asarray(pctx.pos_a)
        pos_b = np.asarray(pctx.pos_b)
        rois = np.asarray(pctx.pair_rois(node.roi), np.int32)
        tab_a_p, n = self._pad(self._tables_np[pos_a])
        tab_b_p, _ = self._pad(self._tables_np[pos_b])
        rois_p, _ = self._pad(rois)
        ka = _threshold_ks(self.cfg, node.ta)
        kb = _threshold_ks(self.cfg, node.tb)
        ks = jnp.asarray(np.array([ka[0], ka[1], kb[0], kb[1]], np.int32))
        lb, ub = step(tab_a_p, tab_b_p, rois_p, ks, self._rb, self._cb)
        return (np.asarray(lb)[:n].astype(np.float64),
                np.asarray(ub)[:n].astype(np.float64))

    def verify_counts(self, ctx, batch, terms):
        terms = list(terms)
        pos = ctx.positions[batch]
        masks_p, n = self._pad(self._masks[pos])
        if len(terms) == 1:
            # single descriptor → the plain sharded verify step
            t = terms[0]
            rois_p, _ = self._pad(
                ctx.resolve_rois(t.roi, pos).astype(np.int32))
            counts = self._verify_step(masks_p, rois_p,
                                       jnp.float32(t.lv),
                                       jnp.float32(min(t.uv, _F32_MAX)))
            return {t: np.asarray(counts)[:n].astype(np.float64)}
        # several distinct terms (predicate + ranking) → one fused pass
        # over the sharded batch, exactly like the scheduler's route
        rois_q, lvs, uvs = spec_arrays(
            [(self._pad(ctx.resolve_rois(t.roi, pos).astype(np.int32))[0],
              t.lv, t.uv) for t in terms])
        counts = np.asarray(self._multi_step(masks_p, rois_q, lvs, uvs))
        return {t: counts[i, :n].astype(np.float64)
                for i, t in enumerate(terms)}

    def _fused_verify_batch(self, ctx, batch, pos, rois_q, lvs, uvs,
                            decided, lb):
        masks_p, n = self._pad(self._masks[pos])
        pad = len(masks_p) - n
        if pad:
            # padded rows: empty ROI (zero area) + undecided → count 0
            rois_q = np.pad(rois_q, ((0, 0), (0, pad), (0, 0)))
            decided = np.pad(decided, ((0, 0), (0, pad)))
            lb = np.pad(lb, ((0, 0), (0, pad)))
        counts = self._fused_verify_step(masks_p, rois_q, lvs, uvs,
                                         decided, lb)
        return np.asarray(counts)[:, :n]

    def topk_candidates(self, lb, ub, k, desc, definite, possible):
        if k <= 0 or int(np.count_nonzero(definite)) < k:
            return possible.copy()
        pes32 = (lb if desc else -ub).astype(np.float32)
        pes_p, n = self._pad(pes32, fill=np.float32(-np.inf))
        def_p, _ = self._pad(np.asarray(definite, bool), fill=False)
        step = self._select_steps.get(k)
        if step is None:
            step = self._select_steps[k] = make_topk_select_step(self.mesh, k)
        ids = np.arange(len(pes_p), dtype=np.int32)
        tau_idx = int(step(pes_p, def_p, ids))
        return self._alive_from_index(lb, ub, k, desc, definite, possible,
                                      pes32, tau_idx)

    def mask_agg_counts(self, gctx, node, gidx):
        gidx = np.asarray(gidx)
        s = gctx.groups.shape[1]
        grp = self._masks[gctx.groups[gidx].reshape(-1)]
        # row shape is (H, W) float or (H, words) packed
        grp = grp.reshape((len(gidx), s) + self._masks.shape[1:])
        rois = gctx.resolve_group_rois(node.roi, gidx).astype(np.int32)
        grp_p, n = self._pad(grp)
        rois_p, _ = self._pad(rois)
        tdt = jnp.float32 if self._packed else grp.dtype
        inter, union = self._agg_step(grp_p, rois_p,
                                      jnp.asarray(node.thresh, tdt))
        counts = inter if node.agg == "intersect" else union
        return np.asarray(counts)[:n].astype(np.float64)

    def fused_counts(self, store, positions, specs):
        masks_p, n = self._pad(self._masks[np.asarray(positions)])
        rois_q, lvs, uvs = spec_arrays(
            [(self._pad(np.asarray(sp[0], np.int32))[0], sp[1], sp[2])
             for sp in specs])
        counts = self._multi_step(masks_p, rois_q, lvs, uvs)
        return np.asarray(counts)[:, :n]

    def fused_pair_counts(self, store, pos_a, pos_b, specs):
        # Pair rows shard together: the i-th pair's A and B tiles land on
        # the same device, so the fused kernel needs no collective.
        a_p, n = self._pad(self._masks[np.asarray(pos_a)])
        b_p, _ = self._pad(self._masks[np.asarray(pos_b)])
        out = np.empty((len(specs), 3, n), np.int64)
        for qi, (rois, ta, tb) in enumerate(specs):
            rois_p, _ = self._pad(np.asarray(rois, np.int32))
            trio = self._pair_step(a_p, b_p, rois_p, jnp.float32(ta),
                                   jnp.float32(tb))
            for row, counts in enumerate(trio):
                out[qi, row] = np.asarray(counts)[:n]
        return out


# ---------------------------------------------------------------------------
# Resolution
# ---------------------------------------------------------------------------

_HOST = HostBackend()
_NAMED = {"device": DeviceBackend, "mesh": MeshBackend}


def host_backend() -> HostBackend:
    """The stateless host backend singleton (the default everywhere)."""
    return _HOST


def get_backend(store, backend=None) -> ExecBackend:
    """Resolve a backend spec against a store.

    ``backend`` is ``None``/``"host"`` (default), a backend *name*
    (``"device"``/``"mesh"`` — instances are cached per store, so the
    resident mask/CHI upload happens once), or an :class:`ExecBackend`
    instance (e.g. a :class:`MeshBackend` built over an explicit mesh).
    """
    if backend is None or backend == "host":
        _BACKEND_RESOLUTIONS.labels(backend="host").inc()
        return _HOST
    if isinstance(backend, ExecBackend):
        backend.sync()
        _BACKEND_RESOLUTIONS.labels(backend=backend.name).inc()
        return backend
    cls = _NAMED.get(backend)
    if cls is None:
        raise ValueError(f"unknown backend {backend!r}; expected one of "
                         f"{['host'] + sorted(_NAMED)} or an ExecBackend")
    cache = store.backend_cache
    if backend not in cache:
        cache[backend] = cls(store)
        _BACKEND_BUILDS.labels(backend=backend).inc()
    else:
        cache[backend].sync()
    _BACKEND_RESOLUTIONS.labels(backend=backend).inc()
    return cache[backend]


__all__ = ["ExecBackend", "HostBackend", "DeviceBackend", "MeshBackend",
           "BackendStats", "CHI_LOOKUPS", "F32_MAX", "chi_verdicts",
           "get_backend", "host_backend", "is_packed", "spec_arrays"]
