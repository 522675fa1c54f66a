"""Transformer building blocks: norms, RoPE, GQA attention (full / sliding /
cross), SwiGLU MLP, embeddings. Pure functions over param dicts; bf16-friendly
(norm + softmax statistics in f32)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e9  # safe for bf16/f32 masking


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key, d_in, d_out, dtype):
    scale = 1.0 / jnp.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out)) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm_init(d, dtype):
    return {"scale": jnp.ones((d,), dtype)}

def rmsnorm(params, x, eps=1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * params["scale"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope(x, positions, theta=1e4):
    """x: (..., S, H, hd) even hd; positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions[..., None].astype(jnp.float32) * freqs      # (..., S, hd/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    xr1 = x1 * cos - x2 * sin
    xr2 = x1 * sin + x2 * cos
    return jnp.stack([xr1, xr2], axis=-1).reshape(x.shape).astype(x.dtype)


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------

def attention_init(key, cfg, dtype):
    hd = cfg.hd
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": dense_init(k1, cfg.d_model, cfg.n_heads * hd, dtype),
        "wk": dense_init(k2, cfg.d_model, cfg.n_kv_heads * hd, dtype),
        "wv": dense_init(k3, cfg.d_model, cfg.n_kv_heads * hd, dtype),
        "wo": dense_init(k4, cfg.n_heads * hd, cfg.d_model, dtype),
    }


def _split_heads(x, n_heads, hd):
    return x.reshape(x.shape[:-1] + (n_heads, hd))


def gqa_scores_mask(q_pos, k_pos, window: int = 0, causal: bool = True):
    """(Sq, Sk) boolean mask: True = attend."""
    m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        m &= k_pos[None, :] <= q_pos[:, None]
    if window:
        m &= k_pos[None, :] > q_pos[:, None] - window
    return m


def gqa_attention(params, cfg, x, kv_x=None, mask=None, positions=None,
                  kv_positions=None, use_rope=True):
    """General GQA attention. x: (B, Sq, d); kv_x for cross-attention.
    mask: (Sq, Sk) or None (no masking). Returns (B, Sq, d)."""
    hd, H, KV = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    kv_in = x if kv_x is None else kv_x
    q = _split_heads(x @ params["wq"], H, hd)
    k = _split_heads(kv_in @ params["wk"], KV, hd)
    v = _split_heads(kv_in @ params["wv"], KV, hd)
    if use_rope:
        if positions is None:
            positions = jnp.arange(x.shape[1])[None]
        if kv_positions is None:
            kv_positions = jnp.arange(kv_in.shape[1])[None]
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, kv_positions, cfg.rope_theta)
    out = gqa_core(q, k, v, mask)
    out = out.reshape(out.shape[:2] + (H * hd,))
    return out @ params["wo"]


def gqa_core(q, k, v, mask=None, kv_valid=None):
    """q: (B, Sq, H, hd), k/v: (B, Sk, KV, hd). GQA via head grouping.
    Softmax statistics in f32. ``kv_valid``: optional (Sk,) bool marking
    filled cache slots (decode with a partially filled cache).
    Returns (B, Sq, H, hd)."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    q = q.reshape(B, Sq, KV, G, hd)
    logits = jnp.einsum("bqkgh,bskh->bkgqs", q, k).astype(jnp.float32)
    logits = logits / jnp.sqrt(hd).astype(jnp.float32)
    if mask is not None:
        logits = jnp.where(mask[None, None, None], logits, NEG_INF)
    if kv_valid is not None:
        logits = jnp.where(kv_valid[None, None, None, None], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", w.astype(v.dtype), v)
    return out.reshape(B, Sq, H, hd)


def kv_quantize(t):
    """Per-(token, head) int8 quantization of K/V: t (B, S, KV, hd) ->
    (codes int8, scale bf16 (B, S, KV, 1)). Production KV-cache compression:
    halves cache HBM footprint and read bytes."""
    scale = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1, keepdims=True)
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.round(t.astype(jnp.float32) / safe * 127.0).astype(jnp.int8)
    return q, (safe / 127.0).astype(jnp.bfloat16)


def kv_dequantize(q, scale, dtype):
    """On TPU this multiply fuses into the attention kernel's VMEM load
    (kernels/flash_attention.py); under XLA it materializes per layer."""
    return (q.astype(jnp.float32) * scale.astype(jnp.float32)).astype(dtype)


def decode_attention(params, cfg, x, cache, pos, use_rope=True):
    """One-token decode: x (B, 1, d); cache {"k","v"[,"k_scale","v_scale"]}
    with k/v (B, S, KV, hd) (int8 codes + scales when cfg.kv_dtype=="int8").
    The new token's K/V are written into the cache as a ring buffer at
    ``pos % S`` and the query attends over the full (updated) cache. A
    cache sharded over its sequence axis lets GSPMD partition the
    contraction + softmax with psum collectives (the TPU analogue of
    split-K decode attention).
    Returns (out (B, 1, d), new_cache)."""
    hd, H, KV = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    int8 = getattr(cfg, "kv_dtype", "") == "int8"
    S = cache["k"].shape[1]
    q = _split_heads(x @ params["wq"], H, hd)
    k_new = _split_heads(x @ params["wk"], KV, hd)
    v_new = _split_heads(x @ params["wv"], KV, hd)
    if use_rope:
        q = rope(q, jnp.full((1, 1), pos), cfg.rope_theta)
        k_new = rope(k_new, jnp.full((1, 1), pos), cfg.rope_theta)
    slot = (pos % S).astype(jnp.int32)

    def write(buf, val):
        return jax.lax.dynamic_update_slice(
            buf, val.astype(buf.dtype), (0, slot, 0, 0))

    new_cache = dict(cache)
    if int8:
        kq, ks = kv_quantize(k_new)
        vq, vs = kv_quantize(v_new)
        new_cache["k"] = write(cache["k"], kq)
        new_cache["v"] = write(cache["v"], vq)
        new_cache["k_scale"] = write(cache["k_scale"], ks)
        new_cache["v_scale"] = write(cache["v_scale"], vs)
        k_att = kv_dequantize(new_cache["k"], new_cache["k_scale"], x.dtype)
        v_att = kv_dequantize(new_cache["v"], new_cache["v_scale"], x.dtype)
    else:
        new_cache["k"] = k_att = write(cache["k"], k_new)
        new_cache["v"] = v_att = write(cache["v"], v_new)
    # slot i holds position i (mod S); every slot with index <= pos is
    # filled — once the ring wraps (pos >= S) everything is valid.
    kv_valid = jnp.arange(S) <= pos
    out = gqa_core(q, k_att, v_att, mask=None, kv_valid=kv_valid)
    out = out.reshape(out.shape[:2] + (H * hd,))
    return out @ params["wo"], new_cache


def blocked_attention(q, k, v, *, causal=True, window=0,
                      q_block=256, kv_block=512):
    """Memory-bounded GQA attention with online softmax (flash-style, pure
    jnp — this is also the oracle mirrored by kernels/flash_attention.py).

    q: (B, Sq, H, dk); k: (B, Sk, KV, dk); v: (B, Sk, KV, dv) — the value
    width may differ from the query/key width (latent attention). Never
    materializes (Sq, Sk). Blocks that the mask empties entirely (above
    the causal diagonal, outside the window) are skipped. The backward pass
    is its own (``_flash_bwd``): it keeps O(S) residuals — the output and
    each row's f32 log-sum-exp — and recomputes each block's probabilities,
    with softmax statistics and the score gradient in f32.
    """
    return _flash(q, k, v, causal, window, min(q_block, q.shape[1]),
                  min(kv_block, k.shape[1]))


def _blocks(q, k, v, QB, KB):
    """Pad q to whole query blocks and k/v to whole key blocks; q grouped
    as (B, nq, QB, KV, G, dk)."""
    B, Sq, H, dk = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    nq, nk = -(-Sq // QB), -(-Sk // KB)
    q = jnp.pad(q, ((0, 0), (0, nq * QB - Sq), (0, 0), (0, 0)))
    k = jnp.pad(k, ((0, 0), (0, nk * KB - Sk), (0, 0), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, nk * KB - Sk), (0, 0), (0, 0)))
    return q.reshape(B, nq, QB, KV, H // KV, dk), k, v, nq, nk


def _block_mask(qi, ki, QB, KB, Sk, causal, window):
    """(QB, KB) mask of query block ``qi`` against key block ``ki``, and
    whether any entry of it is set."""
    q_pos = qi * QB + jnp.arange(QB)
    k_pos = ki * KB + jnp.arange(KB)
    mask = jnp.broadcast_to(k_pos[None, :] < Sk, (QB, KB))   # key padding
    live = ki * KB < Sk
    if causal:
        mask &= k_pos[None, :] <= q_pos[:, None]
        live &= ki * KB <= qi * QB + QB - 1
    if window:
        mask &= k_pos[None, :] > q_pos[:, None] - window
        live &= ki * KB + KB - 1 > qi * QB - window
    return mask, live


def _scores(qblk, kblk, mask, scale):
    """f32 logits (B, KV, G, QB, KB), masked entries at NEG_INF."""
    s = jnp.einsum("bqkgh,bskh->bkgqs", qblk, kblk,
                   preferred_element_type=jnp.float32) * scale
    return jnp.where(mask[None, None, None], s, NEG_INF)


def _flash_fwd(q, k, v, causal, window, QB, KB):
    B, Sq, H, dk = q.shape
    Sk, KV, dv = k.shape[1], k.shape[2], v.shape[-1]
    G = H // KV
    qr, kp, vp, nq, nk = _blocks(q, k, v, QB, KB)
    scale = 1.0 / float(np.sqrt(dk))
    # block indices that depend on an input: under jax.checkpoint, values
    # computed from constants alone (the masks) are saved as residuals
    # rather than recomputed, which would stack every block's mask
    zero = jax.lax.stop_gradient(q.reshape(-1)[0]).astype(jnp.int32) * 0

    def one_q_block(qi):
        qblk = qr[:, qi]                                  # (B, QB, KV, G, dk)

        def kv_step(carry, ki):
            mask, live = _block_mask(qi, ki, QB, KB, Sk, causal, window)

            def update(carry):
                m, l, acc = carry
                kblk = jax.lax.dynamic_slice_in_dim(kp, ki * KB, KB, 1)
                vblk = jax.lax.dynamic_slice_in_dim(vp, ki * KB, KB, 1)
                s = _scores(qblk, kblk, mask, scale)
                m_new = jnp.maximum(m, s.max(axis=-1))
                corr = jnp.exp(m - m_new)
                p = jnp.exp(s - m_new[..., None])
                l_new = l * corr + p.sum(axis=-1)
                pv = jnp.einsum("bkgqs,bskh->bkgqh", p.astype(vblk.dtype),
                                vblk, preferred_element_type=jnp.float32)
                return m_new, l_new, acc * corr[..., None] + pv

            return jax.lax.cond(live, update, lambda c: c, carry), None

        m0 = jnp.full((B, KV, G, QB), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, QB), jnp.float32)
        a0 = jnp.zeros((B, KV, G, QB, dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                      jnp.arange(nk) + zero)
        l = jnp.maximum(l, 1e-30)
        out = (acc / l[..., None]).astype(v.dtype)
        return jnp.moveaxis(out, 3, 1), m + jnp.log(l)   # (B, QB, KV, G, dv)

    outs, lse = jax.lax.map(one_q_block, jnp.arange(nq) + zero)
    out = jnp.moveaxis(outs, 0, 1).reshape(B, nq * QB, H, dv)[:, :Sq]
    return out, (q, k, v, out, lse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, window, QB, KB):
    return _flash_fwd(q, k, v, causal, window, QB, KB)[0]


def _flash_bwd(causal, window, QB, KB, res, dout):
    q, k, v, out, lse = res                  # lse: (nq, B, KV, G, QB)
    with jax.named_scope("model.attention.bwd"):
        B, Sq, H, dk = q.shape
        Sk, KV, dv = k.shape[1], k.shape[2], v.shape[-1]
        G = H // KV
        qr, kp, vp, nq, nk = _blocks(q, k, v, QB, KB)
        scale = 1.0 / float(np.sqrt(dk))
        pad = ((0, 0), (0, nq * QB - Sq), (0, 0), (0, 0))
        dor = jnp.pad(dout, pad).reshape(B, nq, QB, KV, G, dv)
        # D_i = sum_j P_ij dP_ij = rowsum(dO * O), in f32
        delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1)
        delta = jnp.pad(delta, pad[:3]).reshape(B, nq, QB, KV, G)
        delta = jnp.transpose(delta, (1, 0, 3, 4, 2))    # like lse

        def kv_block(dq, ki):
            kblk = jax.lax.dynamic_slice_in_dim(kp, ki * KB, KB, 1)
            vblk = jax.lax.dynamic_slice_in_dim(vp, ki * KB, KB, 1)

            def q_step(carry, qi):
                mask, live = _block_mask(qi, ki, QB, KB, Sk, causal, window)

                def update(carry):
                    dq, dk_b, dv_b = carry
                    qblk, doblk = qr[:, qi], dor[:, qi]
                    s = _scores(qblk, kblk, mask, scale)
                    p = jnp.where(mask[None, None, None],
                                  jnp.exp(s - lse[qi][..., None]), 0.0)
                    dv_b = dv_b + jnp.einsum(
                        "bkgqs,bqkgh->bskh", p.astype(v.dtype), doblk,
                        preferred_element_type=jnp.float32)
                    dp = jnp.einsum("bqkgh,bskh->bkgqs", doblk, vblk,
                                    preferred_element_type=jnp.float32)
                    ds = (p * (dp - delta[qi][..., None])).astype(q.dtype)
                    dq_i = jnp.einsum("bkgqs,bskh->bqkgh", ds, kblk,
                                      preferred_element_type=jnp.float32)
                    dq = dq.at[:, qi].add(dq_i * scale)
                    dk_b = dk_b + scale * jnp.einsum(
                        "bkgqs,bqkgh->bskh", ds, qblk,
                        preferred_element_type=jnp.float32)
                    return dq, dk_b, dv_b

                return jax.lax.cond(live, update, lambda c: c, carry), None

            zk = jnp.zeros((B, KB, KV, dk), jnp.float32)
            zv = jnp.zeros((B, KB, KV, dv), jnp.float32)
            (dq, dk_b, dv_b), _ = jax.lax.scan(q_step, (dq, zk, zv),
                                               jnp.arange(nq))
            return dq, (dk_b, dv_b)

        dq0 = jnp.zeros(qr.shape, jnp.float32)
        dq, (dkb, dvb) = jax.lax.scan(kv_block, dq0, jnp.arange(nk))
        dq = dq.reshape(B, nq * QB, H, dk)[:, :Sq]
        dk_ = jnp.moveaxis(dkb, 0, 1).reshape(B, nk * KB, KV, dk)[:, :Sk]
        dv_ = jnp.moveaxis(dvb, 0, 1).reshape(B, nk * KB, KV, dv)[:, :Sk]
    return dq.astype(q.dtype), dk_.astype(k.dtype), dv_.astype(v.dtype)


_flash.defvjp(lambda q, k, v, causal, window, QB, KB:
              _flash_fwd(q, k, v, causal, window, QB, KB), _flash_bwd)


def full_seq_attention(params, cfg, x, *, causal=True, window=0, kv_x=None,
                       use_rope=True, positions=None):
    """Projection + RoPE + blocked attention + output projection.
    x: (B, S, d). kv_x (cross-attention) implies non-causal, no RoPE on kv."""
    hd, H, KV = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    kv_in = x if kv_x is None else kv_x
    q = _split_heads(x @ params["wq"], H, hd)
    k = _split_heads(kv_in @ params["wk"], KV, hd)
    v = _split_heads(kv_in @ params["wv"], KV, hd)
    if use_rope:
        if positions is None:
            positions = jnp.arange(x.shape[1])[None]
        q = rope(q, positions, cfg.rope_theta)
        if kv_x is None:
            k = rope(k, positions, cfg.rope_theta)
        else:
            k = rope(k, jnp.arange(kv_in.shape[1])[None], cfg.rope_theta)
    out = blocked_attention(q, k, v, causal=causal, window=window)
    out = out.reshape(out.shape[:2] + (H * hd,))
    return out @ params["wo"], k, v


# ---------------------------------------------------------------------------
# multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 §2.1)
# ---------------------------------------------------------------------------

def mla_init(key, cfg, dtype):
    """Queries projected directly (no q low rank); keys and values through
    a ``kv_lora_rank`` latent with one shared rotary key of
    ``qk_rope_head_dim``."""
    H, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {"wq": dense_init(k1, cfg.d_model, H * (dn + dr), dtype),
            "wkv_a": dense_init(k2, cfg.d_model, r + dr, dtype),
            "kv_norm": rmsnorm_init(r, dtype),
            "wkv_b": dense_init(k3, r, H * (dn + dv), dtype),
            "wo": dense_init(k4, H * dv, cfg.d_model, dtype)}


def mla_attention(params, cfg, x):
    """Causal latent attention over the whole sequence. x: (B, S, d).
    Each head's key is [k_nope_h, k_rope] (width dn + dr), its query
    [q_nope_h, q_rope_h]; the softmax scale is 1/sqrt(dn + dr) and the
    value width dv."""
    B, S, _ = x.shape
    H, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    with jax.named_scope("model.mla"):
        pos = jnp.arange(S)[None]
        q = _split_heads(x @ params["wq"], H, dn + dr)
        q = jnp.concatenate(
            [q[..., :dn], rope(q[..., dn:], pos, cfg.rope_theta)], axis=-1)
        ckv = x @ params["wkv_a"]
        c = rmsnorm(params["kv_norm"], ckv[..., :r], cfg.norm_eps)
        k_rope = rope(ckv[..., None, r:], pos, cfg.rope_theta)  # (B,S,1,dr)
        kv = _split_heads(c @ params["wkv_b"], H, dn + dv)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_rope, (B, S, H, dr))], axis=-1)
        v = kv[..., dn:]
    out = blocked_attention(q, k, v, causal=True)
    return out.reshape(B, S, H * dv) @ params["wo"]


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------

def mlp_init(key, d, ff, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, d, ff, dtype),
        "w_in": dense_init(k2, d, ff, dtype),
        "w_out": dense_init(k3, ff, d, dtype),
    }


def mlp(params, x):
    h = jax.nn.silu(x @ params["w_gate"]) * (x @ params["w_in"])
    return h @ params["w_out"]


# ---------------------------------------------------------------------------
# Embedding / LM head (vocab padded to a multiple of 128)
# ---------------------------------------------------------------------------

def embedding_init(key, cfg, dtype):
    k1, k2 = jax.random.split(key)
    V = cfg.padded_vocab
    return {
        "embed": (jax.random.normal(k1, (V, cfg.d_model)) * 0.02).astype(dtype),
        "lm_head": (jax.random.normal(k2, (V, cfg.d_model)) * 0.02).astype(dtype),
    }


def embed(params, tokens):
    return jnp.take(params["embed"], tokens, axis=0)


def logits_fn(params, x, cfg):
    """x: (B, S, d) -> (B, S, V_padded); padded tail masked to NEG_INF."""
    logits = x @ params["lm_head"].T
    pad = cfg.padded_vocab - cfg.vocab
    if pad:
        mask = jnp.arange(cfg.padded_vocab) < cfg.vocab
        logits = jnp.where(mask, logits, NEG_INF)
    return logits


def chunked_softmax_xent(params, x, labels, cfg, chunk: int = 128):
    """Cross-entropy without materializing (B, S, V): scan over sequence
    chunks (a 262k-vocab * 1M-token logits tensor would be ~0.5 TB/device
    otherwise). x: (B, S, d); labels: (B, S) int32."""
    B, S, d = x.shape
    chunk = min(chunk, S)
    n_chunks = S // chunk
    rem = S - n_chunks * chunk
    head = params["lm_head"]
    vmask = (jnp.arange(cfg.padded_vocab) < cfg.vocab)

    # recomputed in the backward pass: otherwise every chunk's f32 logits
    # stay live as residuals, (S / chunk) x B x chunk x V x 4 bytes
    @jax.checkpoint
    def chunk_loss(xc, yc):
        lg = (xc @ head.T).astype(jnp.float32)
        lg = jnp.where(vmask, lg, NEG_INF)
        lse = jax.nn.logsumexp(lg, axis=-1)
        tgt = jnp.take_along_axis(lg, yc[..., None], axis=-1)[..., 0]
        return jnp.sum(lse - tgt)

    if n_chunks > 0:
        xs = x[:, :n_chunks * chunk].reshape(B, n_chunks, chunk, d).swapaxes(0, 1)
        ys = labels[:, :n_chunks * chunk].reshape(B, n_chunks, chunk).swapaxes(0, 1)

        def body(acc, xy):
            xc, yc = xy
            return acc + chunk_loss(xc, yc), None

        total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xs, ys))
    else:
        total = jnp.zeros((), jnp.float32)
    if rem:
        total = total + chunk_loss(x[:, n_chunks * chunk:], labels[:, n_chunks * chunk:])
    return total / (B * S)
