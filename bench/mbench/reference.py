"""The plain reference: what every query of the mixes must answer, computed
by a full scan of the seed's masks, with nothing taken from the program.

The semantics are MaskSearch's (arXiv:2305.02375) as the query language
states them:

* ``CP(mask, roi, (lv, uv))`` counts the pixels inside the half-open box
  ``roi`` (or the whole mask for ``full_img``) whose value ``v`` has
  ``lv <= v < uv``, compared in the masks' float32; ``/ AREA(roi)``
  divides the count by the box's pixel area in float64.
* ``IOU(saliency, attention, ta, tb)`` is, per image, the count of pixels
  with ``saliency > ta`` and ``attention > tb`` over the count with either,
  over the whole mask (0 when the union is empty); the grouped MASK_AGG of
  the two masks of an image thresholded at ``t`` is the same ratio with
  ``ta = tb = t``.
* A ranking orders by the score, descending or ascending, ties by
  ascending id; a filter returns the qualifying ids in ascending order.

Masks are re-rendered from the seed chunk by chunk on the device, counted
there, and only the per-mask counts come back to the host.  A packed
configuration's masks are the float masks thresholded at ``> 0.5``.
``precision="bfloat16"`` computes the same scan with every mask value and
constant rounded to bfloat16 first and the pixel counts summed in
bfloat16: the control, which a sound comparison must fail.  (Rounding the
values alone changed no binary mask on the chip, so the counts carry the
lower precision too.)
"""

from __future__ import annotations

import functools

import numpy as np

from . import data

QBLOCK = 8            # CP descriptors per device call


@functools.lru_cache(maxsize=None)
def _kernels(h: int, w: int, binary: bool, precision: str):
    import jax
    import jax.numpy as jnp
    dt = jnp.bfloat16 if precision == "bfloat16" else jnp.float32

    def values(masks):
        m = masks.astype(dt)
        if binary:
            m = (m > dt(0.5)).astype(dt)
        return m

    def count(hit):
        """Pixels set per mask: exact in int32, or summed in bfloat16."""
        acc = jnp.int32 if precision == "float32" else dt
        return jnp.sum(hit, axis=(-2, -1), dtype=acc).astype(jnp.int32)

    @jax.jit
    def cp_counts(masks, rois, lvs, uvs):
        """(C, H, W), (Q, C, 4), (Q,), (Q,) → (Q, C) int32."""
        m = values(masks)
        rr = jnp.arange(h, dtype=jnp.int32)[None, :, None]
        cc = jnp.arange(w, dtype=jnp.int32)[None, None, :]

        def one(roi, lv, uv):
            inside = ((rr >= roi[:, 0, None, None]) & (rr < roi[:, 2, None, None])
                      & (cc >= roi[:, 1, None, None]) & (cc < roi[:, 3, None, None]))
            hit = inside & (m >= lv.astype(dt)) & (m < uv.astype(dt))
            return count(hit)

        return jax.vmap(one)(rois, lvs, uvs)

    @jax.jit
    def pair_counts(masks, ta, tb):
        """Saliency = even rows, attention = odd rows → (inter, union)."""
        m = values(masks)
        a = m[0::2] > ta.astype(dt)
        b = m[1::2] > tb.astype(dt)
        return count(a & b), count(a | b)

    return cp_counts, pair_counts


def _terms(spec: dict) -> list:
    return [spec[k] for k in ("pred", "term") if k in spec]


def _term_key(term: dict) -> tuple:
    return (term["roi"], float(term["lv"]), float(term["uv"]))


def _pair_key(spec: dict):
    if spec["kind"] == "iou_topk":
        return (spec["ta"], spec["tb"])
    if spec["kind"] == "mask_agg":
        return (spec["t"], spec["t"])
    return None


class Scan:
    """Per-mask CP counts and per-image pair counts for a set of specs."""

    def __init__(self, params: dict, h: int, w: int, *, binary: bool,
                 precision: str = "float32", chunk: int | None = None):
        self.params, self.h, self.w = params, h, w
        self.binary, self.precision = binary, precision
        self.chunk = chunk or data.chunk_for(h, w)
        self.n = len(params["bg"])
        self.boxes = params["boxes"]

    def run(self, specs) -> tuple[dict, dict]:
        import jax.numpy as jnp
        cp_keys = sorted({_term_key(t) for s in specs for t in _terms(s)})
        pair_keys = sorted({k for s in specs if (k := _pair_key(s))})
        cp_counts, pair_counts = _kernels(self.h, self.w, self.binary,
                                          self.precision)
        cp = {k: np.zeros(self.n, np.int64) for k in cp_keys}
        pairs = {k: (np.zeros(self.n // 2, np.int64),
                     np.zeros(self.n // 2, np.int64)) for k in pair_keys}
        full = np.array([0, 0, self.h, self.w], np.int32)
        pad = (-len(cp_keys)) % QBLOCK
        blocks = cp_keys + cp_keys[:1] * pad if cp_keys else []
        for s, e, masks in data.render_chunks(self.params, self.h, self.w,
                                              self.chunk):
            idx = np.minimum(np.arange(s, s + self.chunk), e - 1)
            boxes = self.boxes[idx]
            for b in range(0, len(blocks), QBLOCK):
                keys = blocks[b:b + QBLOCK]
                rois = np.stack([boxes if k[0] == "roi" else
                                 np.broadcast_to(full, boxes.shape)
                                 for k in keys])
                out = np.asarray(cp_counts(
                    masks, jnp.asarray(rois),
                    jnp.asarray([k[1] for k in keys], jnp.float32),
                    jnp.asarray([k[2] for k in keys], jnp.float32)))
                for i, k in enumerate(keys):
                    if b + i < len(cp_keys):       # the padding repeats a key
                        cp[k][s:e] = out[i, :e - s]
            for k in pair_keys:
                inter, union = pair_counts(masks, jnp.float32(k[0]),
                                           jnp.float32(k[1]))
                g0, g1 = s // 2, e // 2
                pairs[k][0][g0:g1] = np.asarray(inter)[:g1 - g0]
                pairs[k][1][g0:g1] = np.asarray(union)[:g1 - g0]
        return cp, pairs


def _divide(num, den):
    num = np.asarray(num, np.float64)
    den = np.asarray(den, np.float64)
    with np.errstate(divide="ignore", invalid="ignore"):
        return np.where(den != 0, num / np.where(den == 0, 1, den), 0.0)


class Answers:
    """Answers of query specs from one :class:`Scan`."""

    def __init__(self, scan: Scan, specs):
        self.scan = scan
        self.cp, self.pairs = scan.run(specs)
        b = scan.boxes.astype(np.int64)
        self.area = {"roi": ((b[:, 2] - b[:, 0]) * (b[:, 3] - b[:, 1])
                             ).astype(np.float64),
                     "full": np.full(scan.n, float(scan.h * scan.w))}

    def value(self, term: dict) -> np.ndarray:
        counts = self.cp[_term_key(term)].astype(np.float64)
        return _divide(counts, self.area[term["roi"]]) if term["norm"] \
            else counts

    def ranking(self, spec: dict, k: int) -> tuple[list, list]:
        """(ids, scores) of the first ``k`` of the spec's ranking."""
        kind = spec["kind"]
        if kind in ("iou_topk", "mask_agg"):
            inter, union = self.pairs[_pair_key(spec)]
            v = _divide(inter, union)
            cand = np.arange(len(v))
        else:
            v = self.value(spec["term"])
            cand = np.arange(len(v))
            if kind == "filtered_topk":
                cand = np.nonzero(self.value(spec["pred"]) > spec["f"])[0]
                v = v[cand]
        key = -v if spec["desc"] else v
        order = np.lexsort((np.arange(len(v)), key))[:k]
        return ([int(x) for x in cand[order]],
                [float(x) for x in v[order]])

    def answer(self, spec: dict) -> dict:
        """The one-shot answer: ``{"ids", "scores"}`` or ``{"ids"}``."""
        if spec["kind"] == "filter":
            ids = np.nonzero(self.value(spec["pred"]) > spec["f"])[0]
            return {"ids": [int(x) for x in ids]}
        ids, scores = self.ranking(spec, spec["k"])
        return {"ids": ids, "scores": scores}
