"""Serving driver: prefill + batched greedy decode with a KV cache.

    PYTHONPATH=src python -m repro.launch.serve --arch granite_3_2b --smoke \
        --batch 4 --prompt-len 16 --gen 32

Demonstrates the serve_step path: the cache layout, position bookkeeping,
and (on a real mesh) seq-sharded KV.
"""

from __future__ import annotations

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp

from ..configs import ARCH_IDS, load_arch, load_smoke
from ..models import build_model


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    args = ap.parse_args(argv)

    cfg = load_smoke(args.arch) if args.smoke else load_arch(args.arch)
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    max_len = args.prompt_len + args.gen
    if cfg.is_encoder_decoder:
        batch = {
            "audio_feats": rng.standard_normal(
                (args.batch, 64, cfg.d_model)).astype(np.float32),
            "tokens": rng.integers(0, cfg.vocab_size,
                                   (args.batch, args.prompt_len)).astype(np.int32),
        }
        cache = model.init_cache(args.batch, enc_len=64)
    else:
        batch = {"tokens": rng.integers(
            0, cfg.vocab_size, (args.batch, args.prompt_len)).astype(np.int32)}
        if cfg.num_patches:
            batch["patches"] = rng.standard_normal(
                (args.batch, cfg.num_patches, cfg.d_model)).astype(np.float32)
        cache = model.init_cache(args.batch, max_len)

    prefill = jax.jit(model.prefill)
    decode = jax.jit(model.decode_step)

    t0 = time.time()
    logits, cache = prefill(params, batch, cache)
    token = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    t_prefill = time.time() - t0
    print(f"prefill {args.batch}x{args.prompt_len}: {t_prefill * 1e3:.1f} ms")

    pos0 = args.prompt_len + (cfg.num_patches or 0)
    out_tokens = [np.asarray(token)]
    t0 = time.time()
    for i in range(args.gen - 1):
        logits, cache = decode(params, cache, token, jnp.int32(pos0 + i))
        token = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        out_tokens.append(np.asarray(token))
    jax.block_until_ready(token)
    dt = time.time() - t0
    toks = np.concatenate(out_tokens, axis=1)
    print(f"decoded {args.gen - 1} steps x{args.batch} in {dt * 1e3:.1f} ms "
          f"({(args.gen - 1) * args.batch / max(dt, 1e-9):.0f} tok/s)")
    print("sample:", toks[0, :16].tolist())
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
