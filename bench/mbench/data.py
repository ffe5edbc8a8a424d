"""The deployment's data, made from the seed.

Masks follow the statistics of the program's synthetic saliency generator
(smooth Gaussian blobs over a low background, the dominant blob inside the
object box for 90% of masks, 15% "attacked" masks with diffuse mid-value
noise, normalised to [0, 1)).  Every per-mask parameter is drawn on the
host with NumPy from the seed (small: a few dozen numbers per mask); the
pixels are rendered on the device in fixed-size chunks by one jitted
function, so the same seed gives bit-identical masks in set-up and in the
reference, whatever the chunk count.

Packed configurations threshold the rendered float masks at 0.5 and pack
them on the device into the program's word layout: bit ``i`` of word ``k``
of a row is pixel column ``32 k + i``, tail bits zero.
"""

from __future__ import annotations

import functools

import numpy as np

CHUNK = 1024                       # masks rendered per device call, at most
CHUNK_PIXELS = CHUNK * 224 * 224   # and pixels
MAX_BLOBS = 4
WORD_BITS = 32


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent NumPy generator per (seed, stream).  NumPy takes
    seeds of any size, so seeds past 32 bits never collide."""
    tag = int.from_bytes(stream.encode(), "little")
    return np.random.default_rng([int(seed) & (2**64 - 1), tag])


def object_boxes(rng, n: int, h: int, w: int) -> np.ndarray:
    """(n, 4) int32 half-open boxes (r0, c0, r1, c1): sides in [H/4, H/2)."""
    bh = rng.integers(h // 4, h // 2, n)
    bw = rng.integers(w // 4, w // 2, n)
    r0 = rng.integers(0, h - bh, n)
    c0 = rng.integers(0, w - bw, n)
    return np.stack([r0, c0, r0 + bh, c0 + bw], axis=1).astype(np.int32)


def mask_params(seed: int, n: int, h: int, w: int, *,
                attacked_fraction: float, in_box_fraction: float) -> dict:
    """Every per-mask number the renderer needs, as float32 arrays."""
    rng = rng_for(seed, "masks")
    boxes = object_boxes(rng, n, h, w)
    k = rng.integers(1, MAX_BLOBS + 1, n)
    bg = rng.uniform(0.0, 0.15, n)
    in_box = rng.random(n) < in_box_fraction
    attacked = rng.random(n) < attacked_fraction
    u = rng.random((n, MAX_BLOBS, 5))
    r0, c0, r1, c1 = (boxes[:, i, None].astype(np.float64) for i in range(4))
    bh, bw = r1 - r0, c1 - c0
    # free blobs anywhere; the first blob of an in-box mask sits inside it
    cy = (0.15 + 0.7 * u[..., 0]) * h
    cx = (0.15 + 0.7 * u[..., 1]) * w
    sy = (0.05 + 0.2 * u[..., 2]) * h
    sx = (0.05 + 0.2 * u[..., 3]) * w
    amp = np.where(in_box[:, None], 0.3 + 0.4 * u[..., 4], 0.5 + 0.5 * u[..., 4])
    box_cy = r0[:, 0] + (0.25 + 0.5 * u[:, 0, 0]) * bh[:, 0]
    box_cx = c0[:, 0] + (0.25 + 0.5 * u[:, 0, 1]) * bw[:, 0]
    box_sy = (0.15 + 0.2 * u[:, 0, 2]) * bh[:, 0]
    box_sx = (0.15 + 0.2 * u[:, 0, 3]) * bw[:, 0]
    box_amp = 0.9 + 0.3 * u[:, 0, 4]
    cy[:, 0] = np.where(in_box, box_cy, cy[:, 0])
    cx[:, 0] = np.where(in_box, box_cx, cx[:, 0])
    sy[:, 0] = np.where(in_box, box_sy, sy[:, 0])
    sx[:, 0] = np.where(in_box, box_sx, sx[:, 0])
    amp[:, 0] = np.where(in_box, box_amp, amp[:, 0])
    amp = np.where(np.arange(MAX_BLOBS)[None, :] < k[:, None], amp, 0.0)
    noise = rng.uniform(size=(n, 3))
    f32 = np.float32
    return {
        "boxes": boxes,
        "blobs": np.stack([cy, cx, sy, sx, amp], axis=-1).astype(f32),
        "bg": bg.astype(f32),
        "attacked": attacked.astype(f32),
        "noise": np.stack([0.25 + 0.25 * noise[:, 0], 3 + 6 * noise[:, 1],
                           3 + 6 * noise[:, 2]], axis=-1).astype(f32),
    }


@functools.lru_cache(maxsize=None)
def _renderer(h: int, w: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def render(blobs, bg, attacked, noise):
        yy = jnp.arange(h, dtype=jnp.float32)[None, :, None]
        xx = jnp.arange(w, dtype=jnp.float32)[None, None, :]
        field = jnp.broadcast_to(bg[:, None, None], (bg.shape[0], h, w))
        for j in range(MAX_BLOBS):
            cy, cx, sy, sx, amp = (blobs[:, j, i][:, None, None]
                                   for i in range(5))
            field = field + amp * jnp.exp(-(((yy - cy) / sy) ** 2 +
                                            ((xx - cx) / sx) ** 2))
        a, fy, fx = (noise[:, i][:, None, None] for i in range(3))
        hit = 0.45 * field + a * jnp.abs(jnp.sin(yy / fy) * jnp.cos(xx / fx))
        field = jnp.where(attacked[:, None, None] > 0, hit, field)
        lo = field.min(axis=(1, 2), keepdims=True)
        hi = field.max(axis=(1, 2), keepdims=True)
        return ((field - lo) / jnp.maximum(hi - lo, 1e-9)
                * jnp.float32(1.0 - 1e-6))

    return render


def chunk_for(h: int, w: int) -> int:
    """Masks per device call: a power of two up to ``CHUNK`` with at most
    ``CHUNK_PIXELS`` pixels, so the transients stay the same size at any
    resolution."""
    return min(CHUNK, 1 << max((CHUNK_PIXELS // (h * w)).bit_length() - 1, 0))


def render_chunks(params: dict, h: int, w: int, chunk: int | None = None):
    """Yield ``(start, stop, masks)`` with ``masks`` a device array of
    ``chunk`` rows (the tail chunk padded with repeats of its last mask,
    so one compiled program serves every call); rows past ``stop - start``
    are padding."""
    import jax.numpy as jnp
    render = _renderer(h, w)
    chunk = chunk or chunk_for(h, w)
    n = len(params["bg"])
    for s in range(0, n, chunk):
        e = min(s + chunk, n)
        idx = np.minimum(np.arange(s, s + chunk), e - 1)
        yield s, e, render(*(jnp.asarray(params[k][idx])
                             for k in ("blobs", "bg", "attacked", "noise")))


def words_for(width: int) -> int:
    return (width + WORD_BITS - 1) // WORD_BITS


@functools.lru_cache(maxsize=None)
def _packer(w: int):
    import jax
    import jax.numpy as jnp
    words = words_for(w)
    pad = words * WORD_BITS - w

    @jax.jit
    def pack(masks):
        """(B, H, W) float → (binary (B, H, W) float32, words (B, H, words)
        uint32): pixels > 0.5 set, LSB-first, tail bits zero."""
        bits = masks > 0.5
        padded = jnp.pad(bits, ((0, 0), (0, 0), (0, pad)))
        b, h = masks.shape[:2]
        grouped = padded.reshape(b, h, words, WORD_BITS).astype(jnp.uint32)
        shifts = jnp.arange(WORD_BITS, dtype=jnp.uint32)
        packed = jnp.sum(grouped << shifts, axis=-1, dtype=jnp.uint32)
        return bits.astype(jnp.float32), packed

    return pack


def threshold_and_pack(masks):
    return _packer(int(masks.shape[-1]))(masks)
