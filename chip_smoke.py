#!/usr/bin/env python3
"""Run the served MaskSearch query path once on the chip and check it.

    python chip_smoke.py              # one chip: the device backend
    python chip_smoke.py --chips 4    # four chips: the mesh backend only

One process; nothing is forked.  The deployment is the MaskSearch paper's
workload: 22,275 float32 masks of 224×224 (the ImageNet classifier input;
the resolution is assumed, not sourced), two mask types per image
(saliency and attention), ``CHIConfig(grid=16, num_bins=16)``, made from
``--seed``; plus a packed copy of the same masks thresholded at 0.5.  The
CHI index is built on the chip by the ``chi_build`` kernel.  Each store is
served by the async tier (``repro.service.asyncserver``) on an event-loop
thread and driven over HTTP ``/v1`` with ``ServiceClient``.

Every answer is compared with the host backend on a second store object
that holds the same arrays (so the served store's I/O counters see only
the server): the host reference applies the same ingest and answers the
same queries in process.  The run fails on any mismatch, error envelope
or non-2xx status; if ``/v1/stats`` does not report the chip backend with
``store_io.bytes_read == 0``; without a TPU, or with
``REPRO_FORCE_PALLAS_INTERPRET`` set; and, on one chip, if a kernel
wrapper the path used lowered without a Mosaic ``tpu_custom_call``, if a
kernel differs from its jnp reference at the deployment's shapes, or if
the chip-built CHI differs from the NumPy oracle.

Earlier lines report the device, set-up and compile seconds, per-query
latency (host clock around the HTTP request; the server reads every
result back to the host before it answers, so the time ends after the
device finished) and ``peak_bytes_in_use``.  The last line is one JSON
object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
PAPER_MASKS = 22275     # the MaskSearch paper's iWildCam workload
SIZE = 224
INGEST_MASKS = 256
INGEST_REQUEST = 64     # masks per /v1/ingest body: stays under the tier's 64 MiB cap
CHECK_ROWS = 256        # rows in the kernel and CHI bit-identity checks
SESSION_PAGES = 3

# Two passes over each query family on one chip; the second perturbs a
# constant so the result cache cannot answer it, and shows latency once
# shapes are warm.
FLOAT_QUERIES = {
    "cp_filter": "SELECT mask_id FROM MasksDatabaseView "
                 "WHERE CP(mask, roi, (0.8, 1.0)) > {0};",
    "cp_topk_desc": "SELECT mask_id FROM MasksDatabaseView "
                    "ORDER BY CP(mask, full_img, (0.2, {1})) DESC LIMIT 25;",
    "filtered_topk": "SELECT mask_id FROM MasksDatabaseView "
                     "WHERE CP(mask, roi, (0.8, 1.0)) > {2} "
                     "ORDER BY CP(mask, full_img, (0.2, 0.6)) DESC LIMIT 25;",
    "iou_topk": "SELECT image_id FROM MasksDatabaseView "
                "ORDER BY IOU(saliency, attention, {3}, 0.6) ASC LIMIT 25;",
    "mask_agg": "SELECT image_id, CP(intersect(mask > {4}), full_img, "
                "(0.5, 2.0)) / CP(union(mask > {4}), full_img, (0.5, 2.0)) "
                "AS iou FROM MasksDatabaseView WHERE mask_type IN (1, 2) "
                "GROUP BY image_id ORDER BY iou ASC LIMIT 25;",
}
PASS_CONSTANTS = [("400", "0.6", "500", "0.6", "0.8"),
                  ("401", "0.61", "501", "0.61", "0.81")]
SESSION_SQL = ("SELECT mask_id FROM MasksDatabaseView "
               "ORDER BY CP(mask, roi, (0.8, 1.0)) / AREA(roi) ASC LIMIT 25;")
REQUERIES = ("cp_topk_desc", "iou_topk")   # full-image ROIs cover new masks
PACKED_QUERIES = {
    "packed_topk": "SELECT mask_id FROM MasksDatabaseView "
                   "ORDER BY CP(mask, roi, (0.5, 1.5)) DESC LIMIT 25;",
    "packed_pair": "SELECT image_id FROM MasksDatabaseView "
                   "ORDER BY IOU(saliency, attention, 0.5, 0.5) ASC LIMIT 25;",
}


class SmokeFailure(Exception):
    """A check failed: the run exits non-zero and prints no result."""


def fail(msg: str):
    raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def _short(v, n: int = 8):
    return v[:n] + ["..."] if isinstance(v, list) and len(v) > n else v


def log_memory(at: str) -> None:
    """Device 0's HBM now and at its peak so far, so the phase that sets
    the peak can be read off the log."""
    import jax
    st = jax.devices()[0].memory_stats() or {}
    log(f"memory at={at} bytes_in_use={st.get('bytes_in_use')} "
        f"peak_bytes_in_use={st.get('peak_bytes_in_use')} "
        f"bytes_limit={st.get('bytes_limit')}")


def device_info(chips: int) -> dict:
    """The device as JAX reports it; fails unless it is a TPU with at
    least (one chip) or exactly (``--chips 4``) the chips asked for."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        fail(f"no TPU: JAX found {d.platform!r} devices")
    if len(devs) < chips or (chips > 1 and len(devs) != chips):
        fail(f"--chips {chips} but JAX found {len(devs)} devices")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


class CompileClock:
    """Seconds JAX spent in backend compiles (persistent-cache reads
    included), in total and per compiled function, from JAX's own
    monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.by_fun: dict = {}
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, fun_name="?", **_):
        if event == self.EVENT:
            self.seconds += duration
            s, n = self.by_fun.get(fun_name, (0.0, 0))
            self.by_fun[fun_name] = (s + duration, n + 1)

    def report(self, top: int = 10) -> None:
        ranked = sorted(self.by_fun.items(), key=lambda kv: -kv[1][0])
        for fun, (s, n) in ranked[:top]:
            log(f"compile fun={fun} seconds={s:.3f} compiles={n}")


# ---------------------------------------------------------------------------
# Set-up
# ---------------------------------------------------------------------------


def build_stores(n: int, seed: int):
    """→ (float store, packed store, reference twins of both, rois, cfg).

    The reference twins are separate store objects over the same arrays:
    the host reference's metered loads never touch the served stores'
    ``store_io``."""
    import numpy as np
    from repro.core import MaskStore
    from repro.core.chi import build_chi_delta, build_chi_np
    from repro.service.server import synthetic_db

    t = time.perf_counter()
    masks, meta, rois, cfg = synthetic_db(n, SIZE, seed=seed)
    log(f"setup synthesize_s={time.perf_counter() - t:.3f} masks={n} "
        f"shape={SIZE}x{SIZE} mask_bytes={masks.nbytes}")
    t = time.perf_counter()
    chi = build_chi_delta(masks, cfg)
    log(f"setup chi_build_s={time.perf_counter() - t:.3f} "
        f"chi_bytes={chi.nbytes}")
    want = build_chi_np(masks[:CHECK_ROWS], cfg)
    if not np.array_equal(chi[:CHECK_ROWS], want):
        fail("CHI built on the chip differs from the NumPy oracle")
    log(f"check chi_build rows={CHECK_ROWS} equal_to_numpy_oracle=True")
    store = MaskStore.create_memory(masks, meta, cfg, chi_table=chi)
    ref = MaskStore(cfg, meta.copy(), tier="memory", masks=masks,
                    chi_table=chi)

    t = time.perf_counter()
    binary = (masks > 0.5).astype(np.float32)
    pchi = build_chi_delta(binary, cfg)
    pstore = MaskStore.create_memory(binary, meta.copy(), cfg,
                                     chi_table=pchi, packed=True)
    del binary
    pref = MaskStore(cfg, meta.copy(), tier="memory",
                     masks=pstore.resident_masks(), chi_table=pchi,
                     packed=True)
    log(f"setup packed_s={time.perf_counter() - t:.3f} "
        f"packed_bytes={pstore.resident_masks().nbytes}")
    return store, pstore, ref, pref, rois, cfg


# ---------------------------------------------------------------------------
# Kernel checks (one chip)
# ---------------------------------------------------------------------------


def kernel_cases(store, pstore, rois):
    """Each kernel wrapper's arguments at the deployment's shapes (B = 256
    rows, Q = 4 descriptors, S = 2 mask types), from the stores' data."""
    import jax.numpy as jnp
    import numpy as np

    b, q = CHECK_ROWS, 4
    # the resident tiers hold 2-D rows; the kernels take (B, H, W') masks
    m = store.device_masks()[:b].reshape((b,) + store.row_shape)
    p = pstore.device_masks()[:b].reshape((b,) + pstore.row_shape)
    r = jnp.asarray(rois[:b], jnp.int32)
    rq = jnp.asarray(np.stack([np.roll(rois[:b], i, axis=0)
                               for i in range(q)]), jnp.int32)
    lvs = jnp.asarray([0.1, 0.2, 0.5, 0.8], jnp.float32)
    uvs = jnp.asarray([0.5, 0.6, 0.9, 1.0], jnp.float32)
    grp = m.reshape(b // 2, 2, SIZE, SIZE)
    pgrp = p.reshape(b // 2, 2, SIZE, p.shape[-1])
    gr = r[: b // 2]
    edges = jnp.asarray(store.cfg.interior_edges, jnp.float32)
    decided = jnp.asarray(np.arange(q * b).reshape(q, b) % 3 == 0, jnp.int32)
    lb = jnp.asarray(np.arange(q * b).reshape(q, b) % 97, jnp.int32)
    f32 = jnp.float32
    return {
        "cp_count": ((m, r, f32(0.2), f32(0.6)), {}),
        "cp_count_multi": ((m, rq, lvs, uvs), {}),
        "chi_cell_hist": ((m, edges), {"grid": store.cfg.grid}),
        "mask_agg_counts": ((grp, gr, f32(0.6)), {}),
        "pair_counts": ((m[0::2], m[1::2], gr, f32(0.6), f32(0.5)), {}),
        "cp_count_packed": ((p, r, f32(0.5), f32(1.5)), {}),
        "cp_count_multi_packed": ((p, rq, lvs, uvs), {}),
        "mask_agg_counts_packed": ((pgrp, gr, f32(0.5)), {}),
        "pair_counts_packed": ((p[0::2], p[1::2], gr, f32(0.5), f32(0.5)),
                               {}),
        "fused_bounds_verify": ((p, rq, lvs, uvs, decided, lb), {}),
    }


def lowers_to_mosaic(wrapper, args, kw) -> bool:
    """Whether a kernel wrapper, lowered for the default backend at these
    arguments' shapes, contains a Mosaic ``tpu_custom_call``."""
    return "tpu_custom_call" in wrapper.__wrapped__.lower(*args, **kw).as_text()


def check_kernels(cases) -> None:
    """Every kernel bit-identical to its jnp reference on the chip."""
    import jax
    import numpy as np
    from repro.kernels import ops as kops

    for name, (args, kw) in cases.items():
        fn = getattr(kops, name)
        got = jax.block_until_ready(fn(*args, **kw))
        want = fn(*args, use_pallas=False, **kw)
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            if not np.array_equal(np.asarray(g), np.asarray(w)):
                fail(f"kernel {name} differs from its jnp reference")
        log(f"check kernel={name} equal_to_jnp_reference=True")


def kernel_dispatches() -> dict:
    """Dispatch counts per kernel wrapper so far (a wrapper called inside a
    jitted step counts when that step is traced)."""
    from repro.kernels import ops as kops
    return {lab["kernel"]: child.get()
            for lab, child in kops._KERNEL_LAUNCHES.samples()}


def check_mosaic(cases, names) -> None:
    from repro.kernels import ops as kops
    for name in names:
        args, kw = cases[name]
        ok = lowers_to_mosaic(getattr(kops, name), args, kw)
        log(f"mosaic kernel={name} tpu_custom_call={ok}")
        if not ok:
            fail(f"kernel wrapper {name} lowered without a Mosaic call")


# ---------------------------------------------------------------------------
# Served path vs host reference
# ---------------------------------------------------------------------------


class Served:
    """One store behind an async tier on an event-loop thread, driven over
    HTTP, next to an in-process host-backend service over its twin."""

    def __init__(self, store, ref_store, rois, backend: str, clock):
        from repro.service import MaskSearchService, ServiceClient
        from repro.service.asyncserver import serve_in_thread
        self.service = MaskSearchService(store, provided_rois=rois,
                                         backend=backend)
        self.ref = MaskSearchService(ref_store, provided_rois=rois,
                                     backend="host")
        self.handle = serve_in_thread(self.service)
        self.client = ServiceClient(self.handle.base_url, timeout=1200.0)
        self.backend = backend
        self.clock = clock

    def close(self) -> None:
        self.handle.stop()
        self.service.close()
        self.ref.close()

    def timed(self, label: str, call, *args, **kw):
        c0, t0 = self.clock.seconds, time.perf_counter()
        try:
            out = call(*args, **kw)
        except Exception as e:          # noqa: BLE001 — any error fails the run
            fail(f"{label}: request failed: {type(e).__name__}: {e}")
        dt = time.perf_counter() - t0
        log(f"query {label} latency_s={dt:.4f} "
            f"compile_s={self.clock.seconds - c0:.4f}")
        if isinstance(out, dict) and "error" in out:
            fail(f"{label}: error envelope {out['error']}")
        return out

    def compare(self, label: str, sql: str) -> None:
        got = self.timed(label, self.client.query, sql)
        t = time.perf_counter()
        want = self.ref.query(sql)
        ref_s = time.perf_counter() - t
        for key in ("kind", "ids", "scores", "value"):
            if got.get(key) != want.get(key):
                fail(f"{label}: {key} differs from the host backend: served "
                     f"{_short(got.get(key))} vs host {_short(want.get(key))}")
        n = len(want.get("ids", []))
        log(f"check query={label} rows={n} equal_to_host=True "
            f"host_s={ref_s:.4f}")

    def compare_session(self, label: str, sql: str) -> None:
        got = [self.timed(f"{label}_page1", self.client.query, sql,
                          session=True, page_size=25)]
        want = [self.ref.query(sql, session=True, page_size=25)]
        for i in range(2, SESSION_PAGES + 1):
            got.append(self.timed(f"{label}_page{i}", self.client.next_page,
                                  got[-1]["session"]))
            want.append(self.ref.next_page(want[0]["session"]))
        for i, (g, w) in enumerate(zip(got, want), 1):
            for key in ("ids", "scores"):
                if g["page"][key] != w["page"][key]:
                    fail(f"{label} page {i}: {key} differs from the host "
                         f"backend: served {_short(g['page'][key])} vs host "
                         f"{_short(w['page'][key])}")
        log(f"check query={label} pages={SESSION_PAGES} equal_to_host=True")

    def ingest(self, masks, image_ids, mask_types) -> None:
        for s in range(0, len(masks), INGEST_REQUEST):
            e = s + INGEST_REQUEST
            out = self.timed(f"ingest_{s}_{e}", self.client.ingest,
                             masks[s:e], image_ids=image_ids[s:e],
                             mask_types=mask_types[s:e])
            ref = self.ref.ingest(masks[s:e], image_ids=image_ids[s:e],
                                  mask_types=mask_types[s:e])
            if (out["appended"], out["n_masks"], out["mask_ids"]) != \
                    (ref["appended"], ref["n_masks"], ref["mask_ids"]):
                fail(f"ingest {s}:{e} differs from the host reference: "
                     f"{out['appended']}/{out['n_masks']} vs "
                     f"{ref['appended']}/{ref['n_masks']}")
        log(f"check ingest masks={len(masks)} n_masks={out['n_masks']} "
            f"equal_to_host=True")

    def check_stats(self, label: str) -> None:
        try:
            st = self.client.stats()
        except Exception as e:          # noqa: BLE001
            fail(f"{label}: /v1/stats failed: {e}")
        io = st["store_io"]
        log(f"stats store={label} backend={st['backend']} "
            f"bytes_read={io['bytes_read']} epoch={st['epoch']} "
            f"n_masks={st['n_masks']}")
        log_memory(f"{label}_epoch{st['epoch']}")
        if st["backend"] != self.backend or io["bytes_read"] != 0:
            fail(f"{label}: /v1/stats reports backend={st['backend']} "
                 f"bytes_read={io['bytes_read']}; want {self.backend} and 0")


def new_masks(n_existing_images: int, seed: int):
    """The ingest batch: INGEST_MASKS new masks forming saliency/attention
    pairs of new images."""
    import numpy as np
    from repro.data.masks import saliency_masks
    masks, _ = saliency_masks(INGEST_MASKS, SIZE, SIZE, seed=seed + 2)
    image_ids = n_existing_images + np.arange(INGEST_MASKS) // 2
    mask_types = np.arange(INGEST_MASKS) % 2 + 1
    return masks, image_ids, mask_types


def serve_float(store, ref, rois, backend, clock, seed) -> None:
    srv = Served(store, ref, rois, backend, clock)
    # The second pass shows warm latency on one chip; the mesh phase only
    # checks answers, and each second of it holds four chips.
    passes = PASS_CONSTANTS if backend == "device" else PASS_CONSTANTS[:1]
    try:
        for p, consts in enumerate(passes):
            for name, sql in FLOAT_QUERIES.items():
                srv.compare(f"{name}_pass{p}", sql.format(*consts))
        srv.compare_session("session", SESSION_SQL)
        srv.check_stats("float")
        n_images = int(store.meta["image_id"].max()) + 1
        srv.ingest(*new_masks(n_images, seed))
        log_memory("float_after_ingest")
        for name in REQUERIES:
            srv.compare(f"{name}_after_ingest",
                        FLOAT_QUERIES[name].format(*PASS_CONSTANTS[0]))
        srv.check_stats("float")
    finally:
        srv.close()


def serve_packed(pstore, pref, rois, backend, clock) -> None:
    srv = Served(pstore, pref, rois, backend, clock)
    try:
        for name, sql in PACKED_QUERIES.items():
            srv.compare(name, sql)
        srv.check_stats("packed")
    finally:
        srv.close()


# ---------------------------------------------------------------------------


def run(args) -> dict:
    if os.environ.get("REPRO_FORCE_PALLAS_INTERPRET"):
        fail("REPRO_FORCE_PALLAS_INTERPRET is set: kernels would run in "
             "interpret mode")
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        fail(f"the repro package is not at {src}")
    sys.path.insert(0, src)
    import jax
    from repro.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    device = device_info(args.chips)
    clock = CompileClock()
    backend = "device" if args.chips == 1 else "mesh"
    log(f"device platform={device['platform']} kind={device['kind']} "
        f"count={device['count']} backend={backend} jax={jax.__version__} "
        f"compile_cache={cache_dir}")
    n = args.n_masks
    if n != PAPER_MASKS:
        log(f"cut masks={n} of {PAPER_MASKS}")

    t0 = time.perf_counter()
    store, pstore, ref, pref, rois, _ = build_stores(n, args.seed)
    if args.chips == 1:
        # the device backend's resident copies, uploaded once
        jax.block_until_ready((store.device_masks(), pstore.device_masks()))
    log(f"setup total_s={time.perf_counter() - t0:.3f} "
        f"compile_s={clock.seconds:.3f}")
    log_memory("setup")

    if args.chips == 1:
        cases = kernel_cases(store, pstore, rois)
        c0 = clock.seconds
        check_kernels(cases)
        log(f"kernels compile_s={clock.seconds - c0:.3f}")
        log_memory("kernel_checks")
    before = kernel_dispatches()
    t = time.perf_counter()
    serve_float(store, ref, rois, backend, clock, args.seed)
    del store, ref                      # release their HBM and host buffers
    gc.collect()
    serve_packed(pstore, pref, rois, backend, clock)
    log(f"served total_s={time.perf_counter() - t:.3f}")
    used = sorted(k for k, v in kernel_dispatches().items()
                  if v > before.get(k, 0))
    log(f"served kernel_wrappers={','.join(used)}")
    if args.chips == 1:
        check_mosaic(cases, used)
    log_memory("end")
    log(f"compile total_s={clock.seconds:.3f}")
    clock.report()
    return device


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: device backend on one chip; 4: mesh backend "
                         "over four chips and no other phase")
    ap.add_argument("--n-masks", type=int, default=PAPER_MASKS,
                    help="store size (a cut is printed)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        device = run(args)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
