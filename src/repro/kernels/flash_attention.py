"""Pallas TPU kernel: GQA flash attention (causal / sliding window).

TPU-native tiling: the MXU wants 128-aligned matmul dims, so Q/K tiles are
(QB, hd) x (KB, hd) with QB, KB multiples of 128 when the sequence allows;
the online-softmax running state (m, l, acc) lives in VMEM scratch across
the KV-block grid dimension.

Grid: (batch*kv_heads*q_groups, n_q_blocks, n_kv_blocks); the KV dimension is
the innermost (sequential) axis so the carry is valid. Causal + window
masking happens on the fly from block indices (no (S, S) mask materialized).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
            *, scale, causal, window, kb_total, q_block, kv_block, seq_k):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                      # (QB, hd)
    k = k_ref[0].astype(jnp.float32)                      # (KB, hd)
    v = v_ref[0].astype(jnp.float32)
    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ()))) * scale           # (QB, KB)

    q_pos = qi * q_block + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 0)
    k_pos = ki * kv_block + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    mask = k_pos < seq_k
    if causal:
        mask &= k_pos <= q_pos
    if window:
        mask &= k_pos > q_pos - window
    logits = jnp.where(mask, logits, NEG_INF)

    m_prev, l_prev, acc_prev = m_scr[...], l_scr[...], acc_scr[...]
    m_new = jnp.maximum(m_prev, logits.max(axis=1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(logits - m_new)
    l_new = l_prev * corr + p.sum(axis=1, keepdims=True)
    acc_new = acc_prev * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))

    m_scr[...], l_scr[...], acc_scr[...] = m_new, l_new, acc_new

    @pl.when(ki == kb_total - 1)
    def _finish():
        o_ref[0] = (acc_new / jnp.maximum(l_new, 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, causal: bool = True, window: int = 0,
                           q_block: int = 128, kv_block: int = 128,
                           interpret: bool = True):
    """q: (B, Sq, H, hd); k, v: (B, Sk, KV, hd). Returns (B, Sq, H, hd).

    The (batch, kv_head, group) axes are flattened into the leading grid dim;
    each program instance handles one (QB, hd) query tile against one
    (KB, hd) KV tile.
    """
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    QB = min(q_block, Sq)
    KB = min(kv_block, Sk)
    nq, nk = -(-Sq // QB), -(-Sk // KB)
    q_pad, k_pad = nq * QB - Sq, nk * KB - Sk

    # (B, S, KV, G, hd) -> (B*KV*G, S, hd): one head-stream per grid row
    qh = jnp.moveaxis(q.reshape(B, Sq, KV, G, hd), 1, 3).reshape(B * KV * G, Sq, hd)
    kh = jnp.repeat(jnp.moveaxis(k, 1, 2), G, axis=1).reshape(B * KV * G, Sk, hd)
    vh = jnp.repeat(jnp.moveaxis(v, 1, 2), G, axis=1).reshape(B * KV * G, Sk, hd)
    if q_pad:
        qh = jnp.pad(qh, ((0, 0), (0, q_pad), (0, 0)))
    if k_pad:
        kh = jnp.pad(kh, ((0, 0), (0, k_pad), (0, 0)))
        vh = jnp.pad(vh, ((0, 0), (0, k_pad), (0, 0)))

    BH = B * KV * G
    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=1.0 / (hd ** 0.5), causal=causal, window=window,
            kb_total=nk, q_block=QB, kv_block=KB, seq_k=Sk),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, QB, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, KB, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, KB, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, QB, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, nq * QB, hd), q.dtype),
        scratch_shapes=[
            # VMEM online-softmax state carried across the kv grid dim
            pltpu.VMEM((QB, 1), jnp.float32),
            pltpu.VMEM((QB, 1), jnp.float32),
            pltpu.VMEM((QB, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qh, kh, vh)
    out = out[:, :Sq].reshape(B, KV, G, Sq, hd)
    return jnp.moveaxis(out, 3, 1).reshape(B, Sq, H, hd)
