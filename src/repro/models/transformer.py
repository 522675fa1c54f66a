"""Layer stacks for all assigned architecture families.

Layer "kinds" (composable sublayer patterns):
  attn_mlp   pre-norm GQA attention + SwiGLU MLP        (dense / global)
  swa_mlp    sliding-window attention + MLP             (gemma3 local)
  attn_moe   attention + mixture-of-experts             (llama4/qwen3/jamba)
  rwkv       RWKV6 time-mix + channel-mix               (rwkv6)
  mamba_mlp  Mamba SSM + MLP                            (jamba)
  mamba_moe  Mamba SSM + MoE                            (jamba)
  cross_mlp  self-attn + cross-attn(enc) + MLP          (whisper decoder)
  enc_mlp    bidirectional attention + MLP              (whisper encoder)
  mla_mlp    latent attention + MLP                     (moonlight, leading)
  mla_moe    latent attention + held expert share       (moonlight)
             + shared experts

Leading dense layers (``cfg.first_dense``) form a stack of their own ahead
of the scanned cycle (``split_pattern``), so that they never join a
remat block of the repeating layers.

A model is a repeating *cycle* of kinds (dense: cycle 1; gemma3: cycle 6 =
5 local + 1 global; jamba: cycle 8 = 7 mamba + 1 attn with MoE every other
layer). Parameters are stacked per cycle position with a leading
(n_layers / cycle) dim and the stack runs under one jax.lax.scan whose body
unrolls the cycle — compact HLO even for 94-layer, 128-expert configs.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from . import layers as L
from . import mamba as M
from . import moe as MOE
from . import rwkv as R


# ---------------------------------------------------------------------------
# per-kind init / full-seq forward / decode
# ---------------------------------------------------------------------------

def layer_init(kind, key, cfg, dtype):
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    if kind in ("attn_mlp", "swa_mlp", "enc_mlp"):
        return {"norm1": L.rmsnorm_init(cfg.d_model, dtype),
                "attn": L.attention_init(k1, cfg, dtype),
                "norm2": L.rmsnorm_init(cfg.d_model, dtype),
                "mlp": L.mlp_init(k2, cfg.d_model, cfg.d_ff, dtype)}
    if kind == "attn_moe":
        return {"norm1": L.rmsnorm_init(cfg.d_model, dtype),
                "attn": L.attention_init(k1, cfg, dtype),
                "norm2": L.rmsnorm_init(cfg.d_model, dtype),
                "moe": MOE.moe_init(k2, cfg, dtype)}
    if kind in ("mla_mlp", "mla_moe"):
        p = {"norm1": L.rmsnorm_init(cfg.d_model, dtype),
             "attn": L.mla_init(k1, cfg, dtype),
             "norm2": L.rmsnorm_init(cfg.d_model, dtype)}
        if kind == "mla_moe":
            p["moe"] = MOE.moe_share_init(k2, cfg, dtype)
        else:
            p["mlp"] = L.mlp_init(k2, cfg.d_model, cfg.d_ff, dtype)
        return p
    if kind == "rwkv":
        return R.rwkv_block_init(k1, cfg, dtype)
    if kind == "mamba_mlp":
        return {"norm1": L.rmsnorm_init(cfg.d_model, dtype),
                "mamba": M.mamba_init(k1, cfg, dtype),
                "norm2": L.rmsnorm_init(cfg.d_model, dtype),
                "mlp": L.mlp_init(k2, cfg.d_model, cfg.d_ff, dtype)}
    if kind == "mamba_moe":
        return {"norm1": L.rmsnorm_init(cfg.d_model, dtype),
                "mamba": M.mamba_init(k1, cfg, dtype),
                "norm2": L.rmsnorm_init(cfg.d_model, dtype),
                "moe": MOE.moe_init(k2, cfg, dtype)}
    if kind == "cross_mlp":
        return {"norm1": L.rmsnorm_init(cfg.d_model, dtype),
                "attn": L.attention_init(k1, cfg, dtype),
                "norm_x": L.rmsnorm_init(cfg.d_model, dtype),
                "xattn": L.attention_init(k3, cfg, dtype),
                "norm2": L.rmsnorm_init(cfg.d_model, dtype),
                "mlp": L.mlp_init(k2, cfg.d_model, cfg.d_ff, dtype)}
    raise ValueError(kind)


def layer_forward(kind, params, cfg, x, enc_out=None, want_cache=False):
    """Full-sequence forward. Returns (x, cache_or_None, aux_loss, stats):
    ``stats`` is the held experts' assignment counts of an ``mla_moe``
    layer, else None."""
    aux = jnp.zeros((), jnp.float32)
    use_rope = cfg.family != "hybrid"
    cache = None
    if kind in ("mla_mlp", "mla_moe"):
        if want_cache:
            raise NotImplementedError(
                "latent attention has no cache here: training only")
        x = x + L.mla_attention(params["attn"], cfg,
                                L.rmsnorm(params["norm1"], x))
        h = L.rmsnorm(params["norm2"], x)
        if kind == "mla_mlp":
            return x + L.mlp(params["mlp"], h), None, aux, None
        h, counts = MOE.moe_share_block(params["moe"], cfg, h)
        return x + h, None, aux, counts
    if kind in ("attn_mlp", "swa_mlp", "enc_mlp", "attn_moe"):
        window = cfg.window if kind == "swa_mlp" else 0
        causal = kind != "enc_mlp"
        h, k, v = L.full_seq_attention(
            params["attn"], cfg, L.rmsnorm(params["norm1"], x),
            causal=causal, window=window, use_rope=use_rope)
        x = x + h
        if want_cache and kind != "enc_mlp":
            if window:
                k, v = k[:, -window:], v[:, -window:]
            if getattr(cfg, "kv_dtype", "") == "int8":
                kq, ks = L.kv_quantize(k)
                vq, vs = L.kv_quantize(v)
                cache = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
            else:
                cache = {"k": k, "v": v}
        if kind == "attn_moe":
            h, aux = MOE.moe_block(params["moe"], cfg, L.rmsnorm(params["norm2"], x))
        else:
            h = L.mlp(params["mlp"], L.rmsnorm(params["norm2"], x))
        return x + h, cache, aux, None
    if kind == "rwkv":
        x, state = R.rwkv_block(params, cfg, x)
        return x, (state if want_cache else None), aux, None
    if kind in ("mamba_mlp", "mamba_moe"):
        h, state = M.mamba_block(params["mamba"], cfg,
                                 L.rmsnorm(params["norm1"], x))
        x = x + h
        if kind == "mamba_moe":
            h, aux = MOE.moe_block(params["moe"], cfg, L.rmsnorm(params["norm2"], x))
        else:
            h = L.mlp(params["mlp"], L.rmsnorm(params["norm2"], x))
        return x + h, (state if want_cache else None), aux, None
    if kind == "cross_mlp":
        h, k, v = L.full_seq_attention(
            params["attn"], cfg, L.rmsnorm(params["norm1"], x), causal=True)
        x = x + h
        h, ek, ev = L.full_seq_attention(
            params["xattn"], cfg, L.rmsnorm(params["norm_x"], x),
            kv_x=enc_out, causal=False, use_rope=False)
        x = x + h
        if want_cache:
            cache = {"k": k, "v": v, "ek": ek, "ev": ev}
        h = L.mlp(params["mlp"], L.rmsnorm(params["norm2"], x))
        return x + h, cache, aux, None
    raise ValueError(kind)


def layer_decode(kind, params, cfg, x, cache, pos):
    """Single-token decode. x: (B, 1, d). Returns (x, new_cache)."""
    use_rope = cfg.family != "hybrid"
    if kind in ("attn_mlp", "swa_mlp", "attn_moe"):
        h, new_cache = L.decode_attention(
            params["attn"], cfg, L.rmsnorm(params["norm1"], x),
            cache, pos, use_rope=use_rope)
        x = x + h
        if kind == "attn_moe":
            h, _ = MOE.moe_block(params["moe"], cfg, L.rmsnorm(params["norm2"], x))
        else:
            h = L.mlp(params["mlp"], L.rmsnorm(params["norm2"], x))
        return x + h, new_cache
    if kind == "rwkv":
        return R.rwkv_block(params, cfg, x, state=cache, single_step=True)
    if kind in ("mamba_mlp", "mamba_moe"):
        h, state = M.mamba_block(params["mamba"], cfg,
                                 L.rmsnorm(params["norm1"], x),
                                 state=cache, single_step=True)
        x = x + h
        if kind == "mamba_moe":
            h, _ = MOE.moe_block(params["moe"], cfg, L.rmsnorm(params["norm2"], x))
        else:
            h = L.mlp(params["mlp"], L.rmsnorm(params["norm2"], x))
        return x + h, state
    if kind == "cross_mlp":
        h, self_cache = L.decode_attention(
            params["attn"], cfg, L.rmsnorm(params["norm1"], x),
            cache, pos)
        x = x + h
        # cross attention over the static encoder K/V held in the cache
        hd, H, KV = cfg.hd, cfg.n_heads, cfg.n_kv_heads
        xq = L.rmsnorm(params["norm_x"], x)
        q = (xq @ params["xattn"]["wq"]).reshape(x.shape[0], 1, H, hd)
        out = L.gqa_core(q, cache["ek"], cache["ev"])
        x = x + out.reshape(x.shape[0], 1, H * hd) @ params["xattn"]["wo"]
        h = L.mlp(params["mlp"], L.rmsnorm(params["norm2"], x))
        return x + h, dict(self_cache, ek=cache["ek"], ev=cache["ev"])
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# pattern + stack
# ---------------------------------------------------------------------------

def layer_pattern(cfg):
    Lh = cfg.n_layers
    if cfg.kv_lora_rank:
        return (["mla_mlp"] * cfg.first_dense
                + ["mla_moe" if cfg.n_experts else "mla_mlp"]
                * (Lh - cfg.first_dense))
    if cfg.family in ("dense", "vlm") and not cfg.global_every:
        return ["attn_mlp"] * Lh
    if cfg.global_every:  # gemma3: (k-1) local : 1 global
        return [("attn_mlp" if (i + 1) % cfg.global_every == 0 else "swa_mlp")
                for i in range(Lh)]
    if cfg.family == "moe":
        return ["attn_moe"] * Lh
    if cfg.family == "ssm":
        return ["rwkv"] * Lh
    if cfg.family == "hybrid":
        pat = []
        for i in range(Lh):
            attn = (i % cfg.attn_every) == (cfg.attn_every - 1)
            moe = (i % cfg.moe_every) == (cfg.moe_every - 1)
            if attn:
                pat.append("attn_moe" if moe else "attn_mlp")
            else:
                pat.append("mamba_moe" if moe else "mamba_mlp")
        return pat
    if cfg.family == "audio":
        return ["cross_mlp"] * Lh
    raise ValueError(cfg.family)


def split_pattern(cfg):
    """(leading dense layers, the repeating rest) of the layer pattern."""
    pattern = layer_pattern(cfg)
    return pattern[:cfg.first_dense], pattern[cfg.first_dense:]


def _cycle(pattern):
    for c in range(1, len(pattern) + 1):
        if len(pattern) % c == 0 and pattern == pattern[:c] * (len(pattern) // c):
            return c
    return len(pattern)


def stack_init(key, cfg, dtype, pattern=None):
    pattern = pattern or layer_pattern(cfg)
    c = _cycle(pattern)
    n_blocks = len(pattern) // c
    kinds = tuple(pattern[:c])
    keys = jax.random.split(key, len(pattern))
    keys = keys.reshape((n_blocks, c) + keys.shape[1:])
    stacked = tuple(
        jax.vmap(lambda kk: layer_init(kinds[pos], kk, cfg, dtype))(keys[:, pos])
        for pos in range(c))
    return {"kinds": kinds, "params": stacked, "n_blocks": n_blocks}


def stack_forward(stack, cfg, x, enc_out=None, want_cache=False, remat=True):
    """Scan over cycle blocks. Returns (x, caches (stacked per pos), aux,
    stats (per cycle position, stacked over blocks; None where a layer
    keeps none))."""
    kinds = stack["kinds"]

    def block(x, block_params):
        caches, stats, aux = [], [], jnp.zeros((), jnp.float32)
        for kind, p in zip(kinds, block_params):
            x, cache, a, st = layer_forward(kind, p, cfg, x, enc_out,
                                            want_cache)
            caches.append(cache)
            stats.append(st)
            aux = aux + a
        return x, (tuple(caches), aux, tuple(stats))

    body = jax.checkpoint(block) if remat else block
    x, (caches, aux, stats) = jax.lax.scan(body, x, stack["params"])
    return x, caches, jnp.sum(aux), stats


def stack_decode(stack, cfg, x, caches, pos):
    kinds = stack["kinds"]

    def block(x, inp):
        block_params, block_caches = inp
        new = []
        for kind, p, cch in zip(kinds, block_params, block_caches):
            x, c2 = layer_decode(kind, p, cfg, x, cch, pos)
            new.append(c2)
        return x, tuple(new)

    x, new_caches = jax.lax.scan(block, x, (stack["params"], caches))
    return x, new_caches
