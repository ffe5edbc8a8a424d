"""Traffic: one general generator over the mix files in ``bench/traffic``.

A mix file names a loop kind and a list of weighted query templates.  The
template kinds and the SQL they fill live here; a mix chooses among them
and gives each parameter a distribution:

* a list: uniform choice among its values;
* ``{"grid": [lo, hi, step]}``: uniform choice on that grid;
* ``{"uniform": [lo, hi], "round": r}``: continuous, rounded to ``r``;
* ranges: ``{"bins": n}`` draws ``lv < uv`` on the edges ``j / n``;
  ``{"width": [a, b], "within": [lo, hi], "round": r}`` a continuous range
  of that width; a list of ``[lv, uv]`` pairs is a choice.

Every seed sends the same work over its own data: a window's pool holds
each template as often as its weight says (largest remainders), every
parameter is drawn from a stream that does not depend on ``--seed``, and
the pool keeps the order that stream gives it.  The seed makes the masks
(``mbench.data``) and, in an open loop, orders the gaps between arrivals:
the quantiles of an exponential distribution (Poisson arrivals at the
mix's rate), permuted by the seed and scaled so that the arrivals span
exactly the window.  (Seeds that drew the constants and ordered the pool
changed the work: which queries repeat within the result cache's reach
follows the order.)
"""

from __future__ import annotations

import json
import os

import numpy as np

from .data import rng_for

KINDS = ("topk", "filter", "filtered_topk", "iou_topk", "mask_agg")
ROI_SQL = {"roi": "roi", "full": "full_img"}
POOL_SEED = 0          # the pool's stream: the same for every seed


def load_mix(bench_dir: str, name: str) -> dict:
    with open(os.path.join(bench_dir, "traffic", f"{name}.json")) as f:
        mix = json.load(f)
    mix["name"] = name
    return mix


# -- parameter distributions ------------------------------------------------

def _round(x: float, r) -> float:
    return float(round(round(x / r) * r, 10)) if r else float(x)


def draw_value(rng, spec):
    if isinstance(spec, list):
        v = spec[int(rng.integers(len(spec)))]
        return tuple(v) if isinstance(v, list) else v
    if "grid" in spec:
        lo, hi, step = spec["grid"]
        n = int(round((hi - lo) / step)) + 1
        return _round(lo + step * int(rng.integers(n)), step)
    if "uniform" in spec:
        lo, hi = spec["uniform"]
        return _round(rng.uniform(lo, hi), spec.get("round"))
    raise ValueError(f"bad value spec {spec!r}")


def draw_range(rng, spec) -> tuple:
    if isinstance(spec, list):
        return tuple(float(x) for x in spec[int(rng.integers(len(spec)))])
    if "bins" in spec:
        n = int(spec["bins"])
        a, b = sorted(rng.choice(n + 1, 2, replace=False))
        return _round(a / n, 1 / 2**20), _round(b / n, 1 / 2**20)
    if "width" in spec:
        lo, hi = spec.get("within", [0.0, 1.0])
        width = rng.uniform(*spec["width"])
        start = rng.uniform(lo, hi - width)
        r = spec.get("round")
        lv = _round(start, r)
        return lv, _round(lv + width, r)
    raise ValueError(f"bad range spec {spec!r}")


# -- templates --------------------------------------------------------------

def _term(rng, t: dict, prefix: str = "") -> dict:
    lv, uv = draw_range(rng, t[prefix + "range"])
    return {"roi": draw_value(rng, t[prefix + "roi"]),
            "norm": bool(draw_value(rng, t[prefix + "norm"])),
            "lv": lv, "uv": uv}


def term_sql(term: dict) -> str:
    roi = ROI_SQL[term["roi"]]
    s = f"CP(mask, {roi}, ({term['lv']!r}, {term['uv']!r}))"
    return s + (f" / AREA({roi})" if term["norm"] else "")


def draw_spec(rng, t: dict) -> dict:
    """One query's parameters from template ``t``."""
    kind = t["kind"]
    spec = {"kind": kind}
    if kind in ("topk", "filtered_topk", "iou_topk", "mask_agg"):
        spec["desc"] = draw_value(rng, t["order"]) == "DESC"
        spec["k"] = int(draw_value(rng, t["k"]))
    if kind in ("topk", "filtered_topk"):
        spec["term"] = _term(rng, t)
    if kind in ("filter", "filtered_topk"):
        pre = "" if kind == "filter" else "pred_"
        spec["pred"] = _term(rng, t, pre)
        spec["f"] = float(draw_value(rng, t["f"]))
    if kind == "iou_topk":
        spec["ta"] = float(draw_value(rng, t["ta"]))
        spec["tb"] = float(draw_value(rng, t["tb"]))
    if kind == "mask_agg":
        spec["t"] = float(draw_value(rng, t["t"]))
    return spec


def spec_sql(spec: dict) -> str:
    kind = spec["kind"]
    order = "DESC" if spec.get("desc") else "ASC"
    view = "FROM MasksDatabaseView"
    if kind == "topk":
        return (f"SELECT mask_id {view} ORDER BY {term_sql(spec['term'])} "
                f"{order} LIMIT {spec['k']};")
    if kind == "filter":
        return (f"SELECT mask_id {view} WHERE {term_sql(spec['pred'])} > "
                f"{spec['f']!r};")
    if kind == "filtered_topk":
        return (f"SELECT mask_id {view} WHERE {term_sql(spec['pred'])} > "
                f"{spec['f']!r} ORDER BY {term_sql(spec['term'])} {order} "
                f"LIMIT {spec['k']};")
    if kind == "iou_topk":
        return (f"SELECT image_id {view} ORDER BY IOU(saliency, attention, "
                f"{spec['ta']!r}, {spec['tb']!r}) {order} LIMIT {spec['k']};")
    if kind == "mask_agg":
        t = repr(spec["t"])
        return (f"SELECT image_id, CP(intersect(mask > {t}), full_img, "
                f"(0.5, 2.0)) / CP(union(mask > {t}), full_img, (0.5, 2.0)) "
                f"AS iou {view} WHERE mask_type IN (1, 2) GROUP BY image_id "
                f"ORDER BY iou {order} LIMIT {spec['k']};")
    raise ValueError(f"unknown template kind {kind!r}")


def template_counts(weights, n: int) -> list:
    """How many of ``n`` requests each template gets: its share of the
    weights, rounded by largest remainders, so the counts sum to ``n``."""
    w = np.asarray(weights, np.float64)
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(int)
    rest = np.argsort(-(exact - counts), kind="stable")[:n - counts.sum()]
    counts[rest] += 1
    return [int(c) for c in counts]


def draw_requests(mix: dict, n: int, stream: str) -> list:
    """``n`` requests of the mix from the fixed stream ``stream``, each
    template as often as its weight says, in an order the stream shuffles:
    dicts with ``spec``, ``sql``, ``tenant`` and ``session`` (opens a
    session)."""
    rng = rng_for(POOL_SEED, f"{mix['name']}/{stream}")
    tpls = mix["templates"]
    sess = mix.get("sessions", {})
    out = []
    for t, count in zip(tpls, template_counts([t["weight"] for t in tpls],
                                              n)):
        for _ in range(count):
            spec = draw_spec(rng, t)
            opens = bool(t.get("session")) and rng.random() < sess.get(
                "share", 0)
            out.append({"spec": spec, "sql": spec_sql(spec), "session": opens,
                        "tenant": f"t{int(rng.integers(mix['tenants']))}"})
    return [out[i] for i in rng.permutation(n)]


# -- schedules ---------------------------------------------------------------

def open_schedule(mix: dict, seed: int, seconds: float):
    """→ (requests, due offsets in seconds): ``round(rate × seconds)``
    requests of the pool, Poisson gaps permuted by the seed."""
    n = max(int(round(mix["rate"] * seconds)), 1)
    reqs = draw_requests(mix, n, "window")
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)[rng_for(seed, "gaps").permutation(n)]
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return reqs, due * (seconds / gaps.sum())


def closed_lists(mix: dict) -> list:
    """Per-client request lists for a closed loop: a pool of ``clients ×
    per_client`` requests dealt round-robin.  A client that reaches its
    list's end starts it again.  The lists are the same for every seed."""
    n = int(mix["clients"]) * int(mix["per_client"])
    reqs = draw_requests(mix, n, "window")
    c = mix["clients"]
    return [reqs[i::c] for i in range(c)]

