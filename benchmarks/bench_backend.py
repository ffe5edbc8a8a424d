"""Execution-backend benchmark — host vs device (vs mesh) physical layers.

Times the two phases the ExecBackend protocol splits out, on the standard
serving workload (2000 masks, 128×128):

  * backend_bounds_*   — the filter phase: CHI bounds for a CP and for a
                         ratio expression over every candidate.
  * backend_verify_*   — the verification phase: exact per-term counts for
                         256-mask batches covering the whole store (the
                         device backend gathers from the HBM-resident tier;
                         the host loads through the store).
  * backend_e2e_*      — one filtered top-k plan end to end per backend.

Prints ``name,us_per_call,derived`` CSV rows (harness contract) and, with
``--json PATH``, writes ``BENCH_backend.json`` with jax backend + device
count metadata.

    PYTHONPATH=src python benchmarks/bench_backend.py --json BENCH_backend.json
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
    from repro.data.masks import object_boxes, saliency_masks

    rois = object_boxes(n_masks, size, size, seed=1)
    masks, _ = saliency_masks(n_masks, size, size, seed=7,
                              attacked_fraction=0.2, boxes=rois,
                              in_box_fraction=0.9)
    meta = np.zeros(n_masks, MASK_META_DTYPE)
    meta["mask_id"] = np.arange(n_masks)
    meta["image_id"] = np.arange(n_masks) // 2
    meta["mask_type"] = np.arange(n_masks) % 2 + 1
    cfg = CHIConfig(grid=16, num_bins=16, height=size, width=size)
    return MaskStore.create_memory(masks, meta, cfg), rois


def _time(fn, repeat: int = 5) -> float:
    fn()                                   # warmup / compile
    times = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def bench_bounds(store, rois, backends, record):
    from repro.core.exprs import BinOp, CP, MaskEvalContext, RoiArea

    exprs = {"cp": CP(None, 0.2, 0.6),
             "ratio": BinOp("/", CP("provided", 0.8, 1.0),
                            RoiArea("provided"))}
    record["bounds"] = {}
    for ename, expr in exprs.items():
        per_backend = {}
        for name, be in backends.items():
            ctx = MaskEvalContext(store, np.arange(len(store)), rois)
            t = _time(lambda be=be, ctx=ctx, expr=expr:
                      be.bounds(ctx, expr))
            per_backend[name] = t
            _row(f"backend_bounds_{ename}_{name}", t,
                 f"masks_per_s={len(store) / max(t, 1e-9):.0f}")
        base = per_backend["host"]
        record["bounds"][ename] = {
            **{n: {"latency_s": t} for n, t in per_backend.items()},
            "device_speedup_vs_host":
                base / max(per_backend.get("device", base), 1e-9),
        }


def bench_verify(store, rois, backends, record):
    from repro.core.exprs import CP, MaskEvalContext

    terms = {CP(None, 0.2, 0.6), CP("provided", 0.8, 1.0)}
    batch_size = 256
    batches = [np.arange(i, min(i + batch_size, len(store)))
               for i in range(0, len(store), batch_size)]
    n_counts = len(store) * len(terms)
    record["verify"] = {}
    for name, be in backends.items():
        def sweep(be=be):
            # fresh context each sweep: no cross-iteration load caching
            ctx = MaskEvalContext(store, np.arange(len(store)), rois,
                                  partial_rows=False)
            for b in batches:
                be.verify_counts(ctx, b, terms)
        t = _time(sweep, repeat=3)
        _row(f"backend_verify_{name}", t,
             f"counts_per_s={n_counts / max(t, 1e-9):.0f};"
             f"batches={len(batches)}")
        record["verify"][name] = {"latency_s": t,
                                  "counts_per_s": n_counts / max(t, 1e-9)}
    base = record["verify"]["host"]["latency_s"]
    if "device" in record["verify"]:
        record["verify"]["device_speedup_vs_host"] = (
            base / max(record["verify"]["device"]["latency_s"], 1e-9))


def bench_e2e(store, rois, backends, record):
    from repro.core.exprs import Cmp, CP
    from repro.core.plan import LogicalPlan, run_plan

    plan = LogicalPlan(predicate=Cmp(CP("provided", 0.8, 1.0), ">", 200.0),
                       order_by=CP(None, 0.2, 0.6), k=25)
    record["e2e_filtered_topk"] = {}
    ref = None
    for name, be in backends.items():
        payload = {}

        def once(be=be, payload=payload):
            payload["out"] = run_plan(store, plan, provided_rois=rois,
                                      verify_batch=256, backend=be)
        t = _time(once, repeat=3)
        (ids, _), stats = payload["out"]
        if ref is None:
            ref = list(ids)
        assert list(ids) == ref, f"backend {name} diverged"
        _row(f"backend_e2e_{name}", t,
             f"verified={stats.n_verified}/{stats.n_candidates}")
        record["e2e_filtered_topk"][name] = {
            "latency_s": t, "n_verified": int(stats.n_verified)}


def bench_packed(n_masks, size, record):
    """Bitpacked binary tier vs float tier on the same binary masks — the
    ISSUE 8 acceptance numbers: ids bit-identical, ``bytes_ratio`` ≥ 8
    (words are 1/32 the float bytes; both stores verify the same residue),
    and exactly one fused bounds+verify megakernel launch per round."""
    from repro.core import CHIConfig, MaskStore
    from repro.core.exprs import CP
    from repro.core.plan import LogicalPlan, run_plan
    from repro.core.store import MASK_META_DTYPE
    from repro.data.masks import object_boxes, saliency_masks
    from repro.obs import REGISTRY

    boxes = object_boxes(n_masks, size, size, seed=1)
    m, _ = saliency_masks(n_masks, size, size, seed=7,
                          attacked_fraction=0.2, boxes=boxes,
                          in_box_fraction=0.9)
    masks = (m > 0.5).astype(np.float32)
    meta = np.zeros(n_masks, MASK_META_DTYPE)
    meta["mask_id"] = np.arange(n_masks)
    meta["image_id"] = np.arange(n_masks) // 2
    meta["mask_type"] = np.arange(n_masks) % 2 + 1
    cfg = CHIConfig(grid=16, num_bins=16, height=size, width=size)
    stores = {
        "float": MaskStore.create_memory(masks, meta, cfg),
        "packed": MaskStore.create_memory(masks, meta.copy(), cfg,
                                          packed=True),
    }
    # grid-misaligned ROI so CHI bounds leave a residue to verify
    roi = (3, 5, size - 3, size - 1)
    plan = LogicalPlan(order_by=CP(roi, 0.5, 1.5), k=25)

    def launches():
        snap = REGISTRY.snapshot().get(
            "masksearch_kernel_launches_total", {})
        return snap.get("kernel=fused_bounds_verify", 0.0)

    out = {}
    ref_ids = None
    for name, store in stores.items():
        payload = {}

        def once(store=store, payload=payload):
            payload["out"] = run_plan(store, plan, verify_batch=256)

        n0 = launches()
        t = _time(once, repeat=3)
        n_launch = launches() - n0
        (ids, _), stats = payload["out"]
        if ref_ids is None:
            ref_ids = list(ids)
        assert list(ids) == ref_ids, "packed tier diverged from float"
        out[name] = {"latency_s": t,
                     "bytes_loaded": int(stats.bytes_loaded),
                     "n_verified": int(stats.n_verified),
                     "n_rounds": int(stats.n_rounds)}
        derived = (f"bytes={stats.bytes_loaded};"
                   f"verified={stats.n_verified}/{stats.n_candidates}")
        if name == "packed":
            # 4 timed runs (warmup + 3): launches divide evenly per round
            out[name]["megakernel_launches_per_round"] = (
                n_launch / max(4 * stats.n_rounds, 1))
            derived += f";megakernel_per_round=" \
                       f"{out[name]['megakernel_launches_per_round']:.2f}"
        _row(f"backend_packed_{name}", t, derived)
    out["bytes_ratio"] = (out["float"]["bytes_loaded"]
                          / max(out["packed"]["bytes_loaded"], 1))
    out["latency_ratio"] = (out["float"]["latency_s"]
                            / max(out["packed"]["latency_s"], 1e-9))
    record["packed"] = {"e2e_topk": out}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-masks", type=int, default=2000)
    ap.add_argument("--size", type=int, default=128)
    ap.add_argument("--skip-mesh", action="store_true",
                    help="benchmark host/device only")
    ap.add_argument("--json", default=None)
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    import jax
    from repro.core.backend import get_backend

    print("name,us_per_call,derived")
    store, rois = _setup(args.n_masks, args.size)
    names = ["host", "device"] + ([] if args.skip_mesh else ["mesh"])
    backends = {n: get_backend(store, n) for n in names}
    record = {"config": {"n_masks": args.n_masks, "size": args.size,
                         "jax_backend": jax.default_backend(),
                         "device_count": jax.device_count(),
                         "backends": names}}
    bench_bounds(store, rois, backends, record)
    bench_verify(store, rois, backends, record)
    bench_e2e(store, rois, backends, record)
    bench_packed(args.n_masks, args.size, record)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(record, f, indent=2)
        print(f"# wrote {args.json}", flush=True)


if __name__ == "__main__":
    main()
