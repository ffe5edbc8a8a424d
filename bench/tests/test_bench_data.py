"""The seed's masks: device packing in the program's word layout, and the
generator's statistics."""

import _bench_path  # noqa: F401
import numpy as np

from mbench import data


def test_device_packing_equals_program_packing():
    from repro.core import packing
    params = data.mask_params(2**35 + 9, 40, 48, 70, attacked_fraction=0.15,
                              in_box_fraction=0.9)
    (s, e, masks), = list(data.render_chunks(params, 48, 70, chunk=40))
    binary, words = data.threshold_and_pack(masks)
    binary = np.asarray(binary)
    assert set(np.unique(binary)) <= {0.0, 1.0}
    assert np.array_equal(binary, (np.asarray(masks) > 0.5).astype(np.float32))
    assert np.array_equal(np.asarray(words), packing.pack_masks(binary))
    assert np.asarray(words).shape == (40, 48, packing.words_for(70))


def test_masks_follow_the_generator_statistics():
    n, h, w = 400, 32, 32
    params = data.mask_params(7, n, h, w, attacked_fraction=0.15,
                              in_box_fraction=0.9)
    chunks = list(data.render_chunks(params, h, w, chunk=128))
    masks = np.concatenate([np.asarray(m)[:e - s] for s, e, m in chunks])
    assert masks.shape == (n, h, w) and masks.dtype == np.float32
    assert masks.min() >= 0.0 and masks.max() < 1.0
    assert 0.08 < params["attacked"].mean() < 0.22
    b = params["boxes"]
    assert np.all(b[:, 2] - b[:, 0] >= h // 4) and np.all(b[:, 2] <= h)


def test_same_seed_same_masks_and_seeds_differ():
    a = data.mask_params(2**40, 16, 16, 16, attacked_fraction=0.15,
                         in_box_fraction=0.9)
    b = data.mask_params(2**40, 16, 16, 16, attacked_fraction=0.15,
                         in_box_fraction=0.9)
    c = data.mask_params(0, 16, 16, 16, attacked_fraction=0.15,
                         in_box_fraction=0.9)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["blobs"], c["blobs"])


def test_device_chi_rows_equal_the_programs_build():
    from repro.core import CHIConfig
    from repro.core.chi import build_chi_delta

    from mbench import deploy
    params = data.mask_params(2**36 + 3, 24, 32, 32, attacked_fraction=0.15,
                              in_box_fraction=0.9)
    (s, e, masks), = list(data.render_chunks(params, 32, 32, chunk=32))
    ccfg = CHIConfig(grid=8, num_bins=16, height=32, width=32)
    rows = deploy.chi_rows(masks, ccfg)[:e - s]
    assert np.array_equal(rows, build_chi_delta(np.asarray(masks)[:e - s],
                                                ccfg))


def test_chunks_keep_their_pixels_at_any_width():
    assert data.chunk_for(224, 224) == data.CHUNK
    assert data.chunk_for(448, 448) == data.CHUNK // 4
    assert data.chunk_for(48, 48) == data.CHUNK
    assert data.chunk_for(4096, 4096) >= 1
