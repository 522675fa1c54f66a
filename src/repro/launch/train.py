"""End-to-end FedMM LM training driver (deliverable b).

Trains an assigned architecture (reduced or full, per --preset) with the
FedMM federated trainer on synthetic heterogeneous token data. On this CPU
container use --preset smoke (reduced configs) or --preset 100m; on a real
slice drop --preset to train the full config on the production mesh.

  PYTHONPATH=src python -m repro.launch.train --arch phi3-medium-14b \
      --preset 100m --steps 300 --batch 8 --seq 256
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import NamedTuple

import jax
import numpy as np

import repro.configs as C
from repro.data.synthetic import token_stream
from repro.fed import trainer as FT
from repro.models.model import build_model
from repro.checkpoint import checkpoint as ckpt
from repro.launch.cache import enable_compile_cache


def preset_config(cfg, preset: str):
    if preset == "full":
        return cfg
    if preset == "smoke":
        return cfg.reduced()
    if preset == "100m":
        # ~100M-parameter variant of the same family
        return dataclasses.replace(
            cfg.reduced(), n_layers=max(4, cfg.reduced().n_layers),
            d_model=512, d_ff=1536,
            n_heads=8 if cfg.n_heads else 0,
            n_kv_heads=min(cfg.n_kv_heads, 4) if cfg.n_kv_heads else 0,
            head_dim=64 if cfg.head_dim else 0,
            vocab=min(cfg.vocab, 32768), rwkv_head_dim=64, dtype="float32")
    raise ValueError(preset)


class TrainResult(NamedTuple):
    state: FT.FedLMState
    losses: list           # per-step all-client mean loss (host floats)
    compiled: object       # the compiled train step the loop ran
    compile_seconds: float
    step_seconds: float    # mean wall seconds per step, compile excluded


def main(argv=None) -> TrainResult:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="phi3-medium-14b", choices=C.ARCH_IDS)
    ap.add_argument("--preset", default="smoke",
                    choices=["smoke", "100m", "full"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8, help="global batch")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--participation", type=float, default=1.0)
    ap.add_argument("--alpha", type=float, default=0.1)
    ap.add_argument("--quant-bits", type=int, default=8)
    ap.add_argument("--gamma", type=float, default=0.5)
    ap.add_argument("--rho", type=float, default=0.05)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = preset_config(C.get(args.arch), args.preset)
    model = build_model(cfg)
    fcfg = FT.FedLMConfig(
        n_clients=args.clients, rho=args.rho, p=args.participation,
        alpha=args.alpha, quant_bits=args.quant_bits, client_mode="logical")

    key = jax.random.PRNGKey(0)
    state = FT.init_state(model, key, fcfg)
    n_params = FT.param_count(model)
    print(f"arch={cfg.name} preset={args.preset} params={n_params/1e6:.1f}M "
          f"clients={args.clients} p={args.participation} "
          f"quant={args.quant_bits}b")

    b_local = args.batch // args.clients

    # heterogeneous client token streams (non-IID unigram skew)
    def sample_batch(k):
        k1, k2 = jax.random.split(k)
        toks = jax.vmap(
            lambda kk: token_stream(kk, b_local, args.seq + 1, cfg.vocab)
        )(jax.random.split(k1, args.clients))          # (n, b, S+1)
        batch = {"tokens": toks[..., :-1], "labels": toks[..., 1:]}
        if cfg.family == "vlm":
            batch["patches"] = jax.random.normal(
                k2, (args.clients, b_local, cfg.n_frontend_tokens,
                     cfg.d_model)) * 0.02
        elif cfg.family == "audio":
            batch["frames"] = jax.random.normal(
                k2, (args.clients, b_local, cfg.n_frontend_tokens,
                     cfg.d_model)) * 0.02
        return batch

    def gamma_at(t):
        return np.float32(args.gamma / (1.0 + t) ** 0.5)

    key, kb, ks = jax.random.split(key, 3)
    batch = sample_batch(kb)
    lowered = jax.jit(FT.make_train_step(model, fcfg)).lower(
        state, batch, ks, gamma_at(0))
    t0 = time.time()
    compiled = lowered.compile()   # reads the persistent cache when placed
    compile_seconds = time.time() - t0

    losses = []
    t0 = time.time()
    for t in range(args.steps):
        if t:
            key, kb, ks = jax.random.split(key, 3)
            batch = sample_batch(kb)
        state, m = compiled(state, batch, ks, gamma_at(t))
        losses.append(float(m["loss"]))
        if t % args.log_every == 0 or t == args.steps - 1:
            print(f"step {t:5d}  loss={losses[-1]:.4f} "
                  f"e_s={float(m['e_s']):.3e}  active={int(m['n_active'])} "
                  f"({time.time() - t0:.1f}s)", flush=True)
    step_seconds = (time.time() - t0) / max(args.steps, 1)
    if args.checkpoint:
        ckpt.save(args.checkpoint, state.s_hat)
        print(f"saved mirror parameter to {args.checkpoint}")
    return TrainResult(state, losses, compiled, compile_seconds,
                       step_seconds)


if __name__ == "__main__":
    main()
