"""Set-up: the configuration's store, built through the program's ingest
path from the seed's masks, behind the async tier.

The masks are rendered on the device in chunks.  The program's CHI
kernel path (``chi_cell_hist`` and the prefix sums of
``histograms_to_table``, what ``build_chi_delta`` runs on the chip)
indexes each chunk where it lies.  The rows are copied to the host,
because ``MaskStore`` takes host arrays, and the device backend uploads
the resident copy once when the service starts.  A packed configuration
thresholds and packs each chunk on the device and hands the store its
words; a sample of them is checked against the program's own host
packing.
"""

from __future__ import annotations

import time

import numpy as np

from . import data

PACK_CHECK_ROWS = 64


def log(msg: str) -> None:
    import sys
    print(msg, file=sys.stderr, flush=True)


def chi_config(cfg: dict):
    from repro.core import CHIConfig
    return CHIConfig(grid=cfg["chi_grid"], num_bins=cfg["chi_bins"],
                     height=cfg["height"], width=cfg["width"])


def meta_for(n: int):
    from repro.core.store import MASK_META_DTYPE
    meta = np.zeros(n, MASK_META_DTYPE)
    meta["mask_id"] = np.arange(n)
    meta["image_id"] = np.arange(n) // 2      # saliency, attention per image
    meta["mask_type"] = np.arange(n) % 2 + 1
    return meta


def params_for(cfg: dict, seed: int) -> dict:
    return data.mask_params(seed, cfg["n_masks"], cfg["height"], cfg["width"],
                            attacked_fraction=cfg["attacked_fraction"],
                            in_box_fraction=cfg["in_box_fraction"])


def chi_rows(masks, ccfg) -> np.ndarray:
    """CHI table rows of a device chunk through the program's kernel path,
    without copying the chunk to the host."""
    import jax.numpy as jnp
    from repro.core.chi import histograms_to_table
    from repro.kernels import ops as kops
    hist = kops.chi_cell_hist(masks, jnp.asarray(ccfg.interior_edges),
                              ccfg.grid)
    return np.asarray(histograms_to_table(hist), np.int32)


def build_store(cfg: dict, params: dict, phases: dict):
    """→ the program's ``MaskStore`` of the configuration's masks."""
    from repro.core import MaskStore
    from repro.core import packing

    n, h, w = cfg["n_masks"], cfg["height"], cfg["width"]
    ccfg = chi_config(cfg)
    packed = cfg["tier"] == "packed"
    rows = (np.empty((n, h, data.words_for(w)), np.uint32) if packed
            else np.empty((n, h, w), np.float32))
    chi_parts = []
    t_render = t_chi = 0.0
    for s, e, masks in data.render_chunks(params, h, w):
        t = time.perf_counter()
        if packed:
            masks, words = data.threshold_and_pack(masks)
            rows[s:e] = np.asarray(words)[:e - s]
            if s == 0:
                k = min(PACK_CHECK_ROWS, e)
                binary = np.asarray(masks[:k])
                if not np.array_equal(packing.pack_masks(binary), rows[:k]):
                    raise RuntimeError("device packing differs from the "
                                       "program's word layout")
        else:
            rows[s:e] = np.asarray(masks)[:e - s]
        t_render += time.perf_counter() - t
        t = time.perf_counter()
        chi_parts.append(chi_rows(masks, ccfg)[:e - s])
        t_chi += time.perf_counter() - t
    phases["render_s"] = t_render
    phases["chi_build_s"] = t_chi
    chi = np.concatenate(chi_parts)
    store = MaskStore(ccfg, meta_for(n), tier="memory", masks=rows,
                      chi_table=chi, packed=packed)
    return store


def serve(cfg: dict, store, boxes, phases: dict):
    """→ (service, tier handle): the device backend (its resident upload
    happens here) behind the async tier on a loop thread."""
    import jax
    from repro.service import MaskSearchService
    from repro.service.asyncserver import serve_in_thread
    svc = cfg["service"]
    t = time.perf_counter()
    service = MaskSearchService(
        store, provided_rois=boxes, backend="device",
        verify_batch=svc["verify_batch"],
        result_cache_size=svc["result_cache_size"],
        bounds_cache_size=svc["bounds_cache_size"])
    jax.block_until_ready((store.device_masks(), store.chi_table))
    phases["upload_s"] = time.perf_counter() - t
    handle = serve_in_thread(service, **cfg["tier_settings"])
    return service, handle
