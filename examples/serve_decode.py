"""Serving example: batched KV-cache decode for any assigned architecture
(reduced CPU variant), including the hybrid/SSM caches.

    PYTHONPATH=src python examples/serve_decode.py --arch jamba-v0.1-52b
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.cache import enable_compile_cache
from repro.config import reduced
from repro.configs import get_config
from repro.models import model as mm


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=48)
    ap.add_argument("--temperature", type=float, default=0.8)
    args = ap.parse_args()

    cfg = reduced(get_config(args.arch))
    key = jax.random.PRNGKey(0)
    params = mm.init_params(key, cfg)
    total = args.prompt_len + args.max_new
    cache = mm.init_cache(cfg, args.batch, total)
    prompts = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    batch = {"tokens": prompts}
    enc_out = None
    if cfg.is_encoder_decoder:
        batch["frame_embeds"] = jax.random.normal(
            key, (args.batch, cfg.encoder_seq_len, cfg.d_model))

    decode = jax.jit(
        lambda p, t, c, pos: mm.decode_step(
            p, cfg, t, c, pos,
            batch=batch if cfg.is_encoder_decoder else None),
        donate_argnums=(2,))

    logits = None
    t0 = time.time()
    for t in range(args.prompt_len):
        logits, cache = decode(params, prompts[:, t:t + 1], cache,
                               jnp.int32(t))
    print(f"prefill (teacher-forced): {time.time()-t0:.2f}s")

    toks = []
    t0 = time.time()
    for t in range(args.prompt_len, total):
        nxt = jax.random.categorical(
            jax.random.fold_in(key, t),
            logits[:, -1].astype(jnp.float32) / args.temperature)
        toks.append(nxt)
        logits, cache = decode(params, nxt[:, None], cache, jnp.int32(t))
    dt = time.time() - t0
    print(f"decoded {args.max_new} tokens x {args.batch} seqs in {dt:.2f}s "
          f"({args.max_new*args.batch/dt:.1f} tok/s on CPU)")
    print("sample ids:", jnp.stack(toks, 1)[0][:16].tolist())


if __name__ == "__main__":
    enable_compile_cache()
    main()
