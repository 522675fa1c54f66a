"""Batched serving driver: prefill a batch of prompts, then greedy-decode
with the ring-buffer KV cache (int8-quantized with --int8-kv).

It runs on one device and places no sharding of its own; on a CPU keep
``--preset smoke`` (the reduced config).

  PYTHONPATH=src python -m repro.launch.serve --arch gemma3-12b \
      --batch 4 --prompt-len 32 --new-tokens 16 [--int8-kv]
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

import repro.configs as C
from repro.models.model import build_model, make_batch


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="phi3-medium-14b", choices=C.ARCH_IDS)
    ap.add_argument("--preset", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--int8-kv", action="store_true")
    args = ap.parse_args()

    cfg = C.get(args.arch)
    if args.preset == "smoke":
        cfg = cfg.reduced()
    if args.int8_kv:
        cfg = dataclasses.replace(cfg, kv_dtype="int8")
    model = build_model(cfg)
    key = jax.random.PRNGKey(0)
    params = model.init(key)

    batch = make_batch(key, cfg, args.batch, args.prompt_len)
    cache_len = args.prompt_len + args.new_tokens
    n_prefix = cfg.n_frontend_tokens if cfg.family == "vlm" else 0

    prefill = jax.jit(lambda p, b: model.prefill(p, b, cache_len=n_prefix + cache_len))
    decode = jax.jit(model.decode)

    t0 = time.time()
    logits, cache = prefill(params, batch)
    tok = jnp.argmax(logits[..., :cfg.vocab], axis=-1).astype(jnp.int32)
    jax.block_until_ready(tok)
    t_prefill = time.time() - t0

    out_tokens = [tok]
    t0 = time.time()
    for i in range(args.new_tokens - 1):
        pos = jnp.asarray(n_prefix + args.prompt_len + i, jnp.int32)
        logits, cache = decode(params, cache, tok, pos)
        tok = jnp.argmax(logits[..., :cfg.vocab], axis=-1).astype(jnp.int32)
        out_tokens.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.time() - t0

    gen = jnp.concatenate(out_tokens, axis=1)
    print(f"arch={cfg.name} kv={cfg.kv_dtype or cfg.dtype} batch={args.batch}")
    print(f"prefill {args.prompt_len} tok: {t_prefill*1e3:.1f} ms "
          f"| decode {args.new_tokens-1} steps: "
          f"{t_decode/(args.new_tokens-1)*1e3:.1f} ms/token")
    print("generated token ids (seq 0):", gen[0].tolist())
    assert bool(jnp.all((gen >= 0) & (gen < cfg.vocab)))


if __name__ == "__main__":
    main()
