"""Public model API: build_model(cfg) -> Model(init, loss_fn, prefill, decode).

Batch contract (all families):
    {"tokens": (B, S) int32, "labels": (B, S) int32}
  vlm adds   {"patches": (B, P, d_model)}   (stub ViT embeddings)
  audio adds {"frames":  (B, F, d_model)}   (stub mel+conv embeddings)

Decode contract: cache pytree from ``prefill`` (or ``init_cache`` for the
dry-run's ShapeDtypeStruct stand-ins), one int32 token per sequence, the
current position; returns next-token logits + updated cache.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from . import layers as L
from . import transformer as T
from ..configs.base import ArchConfig


class Model(NamedTuple):
    cfg: ArchConfig
    init: Callable            # key -> params
    loss_fn: Callable         # (params, batch) -> scalar (mean xent + moe aux)
    prefill: Callable         # (params, batch) -> (last_logits, cache)
    decode: Callable          # (params, cache, token (B,1), pos) -> (logits, cache)
    init_cache: Callable      # (batch_size, seq_len) -> zero cache pytree
    # (params, batch) -> (loss, {"expert_load": (n_moe_layers, E_held)
    # int32}) for a model that holds a share of its experts; else None
    loss_stats: Optional[Callable] = None


def _dtype(cfg):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def build_model(cfg: ArchConfig) -> Model:
    dtype = _dtype(cfg)
    lead, pattern = T.split_pattern(cfg)

    def init(key):
        k_e, k_s, k_enc, k_n = jax.random.split(key, 4)
        params = {
            "embedding": L.embedding_init(k_e, cfg, dtype),
            "stack": T.stack_init(k_s, cfg, dtype, pattern)["params"],
            "final_norm": L.rmsnorm_init(cfg.d_model, dtype),
        }
        if lead:
            params["lead"] = T.stack_init(k_n, cfg, dtype, lead)["params"]
        if cfg.family == "audio":
            enc_pat = ["enc_mlp"] * cfg.n_encoder_layers
            params["encoder"] = T.stack_init(k_enc, cfg, dtype, enc_pat)["params"]
            params["enc_norm"] = L.rmsnorm_init(cfg.d_model, dtype)
        return params

    def _stack(params, name="stack", pat=pattern):
        c = T._cycle(pat)
        return {"kinds": tuple(pat[:c]), "params": params[name],
                "n_blocks": len(pat) // c}

    def _enc_stack(params):
        return {"kinds": ("enc_mlp",), "params": params["encoder"],
                "n_blocks": cfg.n_encoder_layers}

    def _encode(params, frames):
        x, _, _, _ = T.stack_forward(_enc_stack(params), cfg,
                                     frames.astype(dtype), want_cache=False)
        return L.rmsnorm(params["enc_norm"], x)

    def _embed_inputs(params, batch):
        """Token embeddings (+ modality fusion). Returns (x, enc_out, n_prefix)."""
        x = L.embed(params["embedding"], batch["tokens"]).astype(dtype)
        enc_out, n_prefix = None, 0
        if cfg.family == "vlm":
            patches = batch["patches"].astype(dtype)
            x = jnp.concatenate([patches, x], axis=1)     # early fusion
            n_prefix = patches.shape[1]
        elif cfg.family == "audio":
            enc_out = _encode(params, batch["frames"])
        return x, enc_out, n_prefix

    def loss_stats(params, batch):
        x, enc_out, n_prefix = _embed_inputs(params, batch)
        if lead:
            x, _, _, _ = T.stack_forward(_stack(params, "lead", lead), cfg,
                                         x, want_cache=False, remat=True)
        x, _, aux, stats = T.stack_forward(_stack(params), cfg, x, enc_out,
                                           want_cache=False, remat=True)
        x = L.rmsnorm(params["final_norm"], x)
        if n_prefix:
            x = x[:, n_prefix:]
        loss = L.chunked_softmax_xent(params["embedding"], x,
                                      batch["labels"], cfg)
        counts = [s for s in stats if s is not None]
        return loss + 0.01 * aux, (
            {"expert_load": jnp.concatenate(counts)} if counts else {})

    def loss_fn(params, batch):
        return loss_stats(params, batch)[0]

    def prefill(params, batch, cache_len=None):
        """cache_len: optionally allocate full-attention caches longer than
        the prompt (extra slots are masked in decode via the slot<=pos rule)."""
        x, enc_out, n_prefix = _embed_inputs(params, batch)
        x, caches, _, _ = T.stack_forward(_stack(params), cfg, x, enc_out,
                                          want_cache=True, remat=False)
        if cache_len is not None:
            c = T._cycle(pattern)
            kinds = pattern[:c]

            def pad(cache, kind):
                if kind in ("attn_mlp", "attn_moe", "cross_mlp") and cache:
                    n = cache_len - cache["k"].shape[2]
                    if n > 0:
                        pad_kv = ((0, 0), (0, 0), (0, n), (0, 0), (0, 0))
                        cache = dict(cache, **{
                            key: jnp.pad(cache[key], pad_kv)
                            for key in ("k", "v", "k_scale", "v_scale")
                            if key in cache})
                return cache

            caches = tuple(pad(cc, kk) for cc, kk in zip(caches, kinds))
        x = L.rmsnorm(params["final_norm"], x)
        last = L.logits_fn(params["embedding"], x[:, -1:], cfg)
        return last, caches

    def decode(params, caches, token, pos):
        """token: (B, 1) int32; pos: scalar int32 (next position index)."""
        x = L.embed(params["embedding"], token).astype(dtype)
        x, new_caches = T.stack_decode(_stack(params), cfg, x, caches, pos)
        x = L.rmsnorm(params["final_norm"], x)
        logits = L.logits_fn(params["embedding"], x, cfg)
        return logits, new_caches

    def init_cache(batch_size, seq_len):
        """Zero-filled cache pytree shaped like prefill's output (used to
        build ShapeDtypeStruct stand-ins in the dry-run)."""
        c = T._cycle(pattern)
        kinds = pattern[:c]
        n_blocks = len(pattern) // c
        B = batch_size
        KV, hd = cfg.n_kv_heads, cfg.hd
        di = cfg.expand * cfg.d_model
        H_rwkv = cfg.d_model // cfg.rwkv_head_dim if cfg.family == "ssm" else 0

        int8 = cfg.kv_dtype == "int8"
        kv_store = jnp.int8 if int8 else dtype

        def kv_entry(S):
            out = {"k": jnp.zeros((n_blocks, B, S, KV, hd), kv_store),
                   "v": jnp.zeros((n_blocks, B, S, KV, hd), kv_store)}
            if int8:
                out["k_scale"] = jnp.zeros((n_blocks, B, S, KV, 1), jnp.bfloat16)
                out["v_scale"] = jnp.zeros((n_blocks, B, S, KV, 1), jnp.bfloat16)
            return out

        def one(kind):
            if kind in ("attn_mlp", "attn_moe"):
                return kv_entry(seq_len)
            if kind == "swa_mlp":
                return kv_entry(min(cfg.window, seq_len))
            if kind == "rwkv":
                return {"shift_tm": jnp.zeros((n_blocks, B, cfg.d_model), dtype),
                        "shift_cm": jnp.zeros((n_blocks, B, cfg.d_model), dtype),
                        "wkv": jnp.zeros((n_blocks, B, H_rwkv,
                                          cfg.rwkv_head_dim, cfg.rwkv_head_dim),
                                         jnp.float32)}
            if kind in ("mamba_mlp", "mamba_moe"):
                return {"conv": jnp.zeros((n_blocks, B, cfg.d_conv - 1, di), dtype),
                        "ssm": jnp.zeros((n_blocks, B, di, cfg.d_state),
                                         jnp.float32)}
            if kind == "cross_mlp":
                F = cfg.n_frontend_tokens
                return dict(kv_entry(seq_len),
                            ek=jnp.zeros((n_blocks, B, F, KV, hd), dtype),
                            ev=jnp.zeros((n_blocks, B, F, KV, hd), dtype))
            raise ValueError(kind)

        return tuple(one(k) for k in kinds)

    return Model(cfg=cfg, init=init, loss_fn=loss_fn, prefill=prefill,
                 decode=decode, init_cache=init_cache,
                 loss_stats=loss_stats if cfg.experts_held else None)


def make_batch(key, cfg: ArchConfig, batch_size: int, seq_len: int):
    """Random batch matching the family's contract (for smoke tests)."""
    k1, k2, k3 = jax.random.split(key, 3)
    batch = {
        "tokens": jax.random.randint(k1, (batch_size, seq_len), 0, cfg.vocab),
        "labels": jax.random.randint(k2, (batch_size, seq_len), 0, cfg.vocab),
    }
    if cfg.family == "vlm":
        batch["patches"] = jax.random.normal(
            k3, (batch_size, cfg.n_frontend_tokens, cfg.d_model)) * 0.02
    elif cfg.family == "audio":
        batch["frames"] = jax.random.normal(
            k3, (batch_size, cfg.n_frontend_tokens, cfg.d_model)) * 0.02
    return batch
