"""CHI pyramid + cost-based filter ordering benchmark (DESIGN.md §13).

Prints ``name,us_per_call,derived`` CSV rows (harness contract) and, with
``--json PATH``, writes a machine-readable record (``BENCH_optimizer.json``).

Workload: a skewed-selectivity conjunction over full-image ROIs at
grid=16 — one conjunct rejects almost nothing, the other rejects almost
everything.  The cost-based optimizer evaluates the selective conjunct
first and decides nearly every candidate at the 4x4 pyramid tier, touching
a fraction of the index bytes the classic single-grid pass reads.

Measured:
  * optimizer.bytes_per_decided_ratio — index bytes per bounds-decided
    candidate, classic single-grid vs pyramid ladder.  Headline; the
    acceptance bar is >= 3x and CI gates it (seed-deterministic).
  * optimizer.reorder.latency_ratio   — filter-phase latency without vs
    with conjunct reordering (pyramid on for both).  Reported, not gated.

Bit-identity of (ids, decided counts) between classic plan-order
evaluation and the optimized ladder is asserted in-bench on the host and
device backends.

    PYTHONPATH=src python benchmarks/bench_optimizer.py \
        --json BENCH_optimizer.json
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np


def _row(name: str, seconds: float, derived: str = ""):
    print(f"{name},{seconds * 1e6:.1f},{derived}", flush=True)


def _setup(n_masks: int, size: int):
    from repro.core import CHIConfig, MaskStore
    from repro.core.store import MASK_META_DTYPE

    rng = np.random.default_rng(17)
    masks = rng.random((n_masks, size, size), dtype=np.float32)
    n_low = n_masks // 2
    n_hot = max(n_masks // 20, 1)
    masks[:n_low] *= 0.3                        # half the store: low-valued
    masks[n_low:n_low + n_hot] = (              # 5%: clearly hot
        0.5 + 0.5 * masks[n_low:n_low + n_hot])
    meta = np.zeros(n_masks, MASK_META_DTYPE)
    meta["mask_id"] = np.arange(n_masks)
    meta["image_id"] = np.arange(n_masks)
    meta["mask_type"] = np.arange(n_masks) % 3 + 1
    # 0.2 and 0.8 (the query thresholds) sit on CHI value edges, so the
    # aligned full-image ROI is answered exactly at every pyramid tier
    cfg = CHIConfig(grid=16, num_bins=16, height=size, width=size,
                    thresholds=tuple(round(0.1 + 0.05 * i, 2)
                                     for i in range(15)))
    return MaskStore.create_memory(masks, meta, cfg)


def _skewed_plan(size: int):
    from repro.core.exprs import CP, And, Cmp
    from repro.core.plan import LogicalPlan

    area = size * size
    full = (0, 0, size, size)
    inf = float("inf")
    # plan order puts the weak conjunct first; the optimizer must flip it.
    # weak accepts ~everything; strong rejects all but the hot 5% (uniform
    # masks have ~0.2*area above 0.8 — a clear margin below 0.25*area).
    # CHI value edges are float32-quantized, so query at the float32 edge
    # value for exact (lb == ub) aligned bounds.
    lo, hi = float(np.float32(0.2)), float(np.float32(0.8))
    weak = Cmp(CP(full, lo, inf), ">", 0.01 * area)
    strong = Cmp(CP(full, hi, inf), ">", 0.25 * area)
    return LogicalPlan(predicate=And(weak, strong))


def _run(store, plan, repeats, backend=None, pyramid=True, reorder=True):
    from repro.core import opt
    from repro.core.plan import run_plan

    with opt.configure(pyramid=pyramid, reorder=reorder):
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            ids, stats = run_plan(store, plan, backend=backend)
            best = min(best, time.perf_counter() - t0)
    return ids, stats, best


def bench_optimizer(store, size, repeats, record):
    plan = _skewed_plan(size)
    legs = {}
    for name, kw in (
        ("classic", dict(pyramid=False, reorder=False)),
        ("ladder", dict(pyramid=True, reorder=True)),
        ("ladder_unordered", dict(pyramid=True, reorder=False)),
    ):
        ids, stats, t = _run(store, plan, repeats, **kw)
        decided = max(int(stats.n_decided_by_bounds), 1)
        legs[name] = {"ids": list(map(int, ids)),
                      "chi_bytes": int(stats.chi_bytes),
                      "n_decided_by_bounds": int(stats.n_decided_by_bounds),
                      "n_verified": int(stats.n_verified),
                      "bytes_per_decided": stats.chi_bytes / decided,
                      "filter_latency_s": t}
        _row(f"optimizer_{name}", t,
             f"chi_bytes={stats.chi_bytes};decided="
             f"{stats.n_decided_by_bounds};hits={len(ids)}")
    # the optimized ladder must be bit-identical to plan-order evaluation
    assert legs["ladder"]["ids"] == legs["classic"]["ids"]
    assert legs["ladder_unordered"]["ids"] == legs["classic"]["ids"]
    assert (legs["ladder"]["n_decided_by_bounds"]
            == legs["classic"]["n_decided_by_bounds"])
    ids_dev, stats_dev, t_dev = _run(store, plan, 1, backend="device")
    assert list(map(int, ids_dev)) == legs["classic"]["ids"], \
        "device ladder diverged from host plan-order evaluation"
    _row("optimizer_ladder_device", t_dev,
         f"chi_bytes={stats_dev.chi_bytes}")

    ratio = (legs["classic"]["bytes_per_decided"]
             / max(legs["ladder"]["bytes_per_decided"], 1e-9))
    reorder_ratio = (legs["ladder_unordered"]["filter_latency_s"]
                     / max(legs["ladder"]["filter_latency_s"], 1e-9))
    _row("optimizer_summary", legs["ladder"]["filter_latency_s"],
         f"bytes_per_decided_ratio={ratio:.2f}x;"
         f"reorder_latency_ratio={reorder_ratio:.2f}x")
    record["optimizer"] = {
        "workload": "skewed-selectivity conjunction, full-image ROIs, "
                    "grid=16",
        "classic": {k: v for k, v in legs["classic"].items() if k != "ids"},
        "ladder": {k: v for k, v in legs["ladder"].items() if k != "ids"},
        "ladder_unordered": {k: v for k, v in legs["ladder_unordered"].items()
                             if k != "ids"},
        "bytes_per_decided_ratio": ratio,
        "reorder": {
            "with_s": legs["ladder"]["filter_latency_s"],
            "without_s": legs["ladder_unordered"]["filter_latency_s"],
            "latency_ratio": reorder_ratio,
        },
        "device": {"filter_latency_s": t_dev,
                   "chi_bytes": int(stats_dev.chi_bytes)},
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-masks", type=int, default=2000)
    ap.add_argument("--size", type=int, default=128)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--json", default=None,
                    help="also write a JSON record to this path")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    import jax

    print("name,us_per_call,derived")
    record = {"config": {"n_masks": args.n_masks, "size": args.size,
                         "repeats": args.repeats,
                         "jax_backend": jax.default_backend(),
                         "device_count": jax.device_count()}}
    t0 = time.perf_counter()
    store = _setup(args.n_masks, args.size)
    _row("db_ingest_total", time.perf_counter() - t0,
         f"n_masks={args.n_masks};size={args.size}")
    bench_optimizer(store, args.size, args.repeats, record)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(record, f, indent=2)
        print(f"# wrote {args.json}", flush=True)


if __name__ == "__main__":
    main()
