"""Ingest-path benchmark — incremental CHI maintenance vs full rebuild.

The paper's motivating workflows regenerate masks between queries (models
retrain, saliency maps refresh), so the index must absorb deltas without
re-indexing the database.  This benchmark appends a fixed-size delta to
databases of growing size and compares:

  * ``ingest_incr_bN``  — ``MaskStore.append``: CHI tables built for the
                          delta only, attached as a new chunk (O(delta)).
  * ``ingest_full_bN``  — the frozen-store alternative: rebuild the whole
                          CHI with ``build_chi_np`` over base+delta (O(N)).

Prints ``name,us_per_call,derived`` CSV rows (harness contract) and, with
``--json PATH``, writes a machine-readable record (``BENCH_ingest.json``).
The headline: incremental append cost is proportional to the delta, so its
speedup over the full rebuild *grows with database size*.

    PYTHONPATH=src python benchmarks/bench_ingest.py --json BENCH_ingest.json
    PYTHONPATH=src python benchmarks/bench_ingest.py \
        --sizes 96,192 --delta 16 --size 32        # tiny CI smoke mode
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np


def _row(name: str, seconds: float, derived: str = ""):
    print(f"{name},{seconds * 1e6:.1f},{derived}", flush=True)


def _make_db(n: int, size: int, seed: int):
    from repro.core.store import MASK_META_DTYPE
    from repro.data.masks import object_boxes, saliency_masks

    boxes = object_boxes(n, size, size, seed=seed + 1)
    masks, _ = saliency_masks(n, size, size, seed=seed,
                              attacked_fraction=0.2, boxes=boxes)
    meta = np.zeros(n, MASK_META_DTYPE)
    meta["mask_id"] = np.arange(n)
    meta["image_id"] = np.arange(n) // 2
    meta["mask_type"] = np.arange(n) % 2 + 1
    return np.asarray(masks, np.float32), meta


def bench_size(n_base: int, delta: int, size: int, repeats: int, record: list):
    from repro.core import CHIConfig, MaskStore, build_chi_np

    cfg = CHIConfig(grid=16, num_bins=16, height=size, width=size)
    base_masks, base_meta = _make_db(n_base + delta, size, seed=n_base % 97)
    new_masks = base_masks[n_base:]
    store = MaskStore.create_memory(base_masks[:n_base],
                                    base_meta[:n_base], cfg)

    def fresh_meta(k):
        m = base_meta[n_base:].copy()
        m["mask_id"] += 10_000_000 * (k + 1)  # fresh ids per delta
        return m

    # warmup append absorbs the one-time amortized buffer growth, then
    # measure steady-state appends (the model-iteration loop's cost)
    store.append(new_masks, fresh_meta(0))
    t_incr = []
    for i in range(repeats):
        t0 = time.perf_counter()
        store.append(new_masks, fresh_meta(i + 1))
        t_incr.append(time.perf_counter() - t0)
    t_incr_s = float(np.median(t_incr))

    t_full = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        full = build_chi_np(base_masks, cfg)
        t_full.append(time.perf_counter() - t0)
    t_full_s = float(np.median(t_full))

    # the incremental chunks must equal a from-scratch rebuild
    chi_equal = bool(np.array_equal(store.chi_host()[:n_base + delta], full))
    assert chi_equal, "incremental CHI diverged from full rebuild"

    speedup = t_full_s / max(t_incr_s, 1e-12)
    _row(f"ingest_incr_b{n_base}", t_incr_s,
         f"delta={delta};chunks={len(store.chi_chunks)}")
    _row(f"ingest_full_b{n_base}", t_full_s,
         f"n={n_base + delta};speedup={speedup:.1f}x")
    record.append({
        "n_base": n_base, "delta": delta, "mask_size": size,
        "t_incremental_s": t_incr_s, "t_full_rebuild_s": t_full_s,
        "speedup": speedup, "chi_equal": chi_equal,
    })


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", default="512,2048",
                    help="comma-separated base database sizes")
    ap.add_argument("--delta", type=int, default=64,
                    help="masks appended per ingest")
    ap.add_argument("--size", type=int, default=128, help="mask side length")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--json", default=None,
                    help="also write a JSON record to this path")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    import jax

    sizes = [int(s) for s in args.sizes.split(",")]
    print("name,us_per_call,derived")
    results: list = []
    for n_base in sizes:
        bench_size(n_base, args.delta, args.size, args.repeats, results)

    speedups = [r["speedup"] for r in results]
    growing = all(b >= a for a, b in zip(speedups, speedups[1:]))
    _row("ingest_speedup_trend", 0.0,
         f"speedups={'/'.join(f'{s:.1f}x' for s in speedups)};"
         f"growing={growing}")
    record = {
        "config": {"sizes": sizes, "delta": args.delta,
                   "mask_size": args.size, "repeats": args.repeats,
                   "jax_backend": jax.default_backend(),
                   "device_count": jax.device_count()},
        "results": results,
        "speedup_growing_with_size": growing,
    }
    if args.json:
        with open(args.json, "w") as f:
            json.dump(record, f, indent=2)
        print(f"# wrote {args.json}", flush=True)


if __name__ == "__main__":
    main()
