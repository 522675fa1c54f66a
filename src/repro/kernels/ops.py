"""jit'd public wrappers for the Pallas kernels.

``INTERPRET`` follows the default backend at import: on a CPU backend the
kernels execute in interpret mode (the kernel body runs in Python for
validation); on TPU they compile to Mosaic kernels.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from .quantize_block import (decode_reduce_grouped_pallas,
                             quantize_block_pallas,
                             quantize_encode_grouped_pallas,
                             quantize_grouped_pallas)
from .flash_attention import flash_attention_pallas
from .rwkv_scan import rwkv_scan_pallas

INTERPRET = jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("bits", "block"))
def quantize_dequantize_with_dither(x, u, bits: int = 8, block: int = 256):
    """Block quantize->dequantize of a flat float32 stream with caller-
    provided uniform draws ``u`` (same shape as ``x``). Pads internally to
    the quant block. This is the entry point ``core/compression.py`` uses
    for its flat kernel dispatch: the dither source (fused hash /
    jax.random) stays orthogonal to the kernel, so kernel and jnp-oracle
    paths are bit-identical given the same draws."""
    n = x.shape[0]
    padded = -(-n // block) * block
    xp = jnp.pad(x, (0, padded - n))
    up = jnp.pad(u, (0, padded - n))
    out = quantize_block_pallas(xp, up, bits=bits, block=block,
                                interpret=INTERPRET)
    return out[:n]


@functools.partial(jax.jit, static_argnames=("bits", "group"))
def quantize_dequantize_grouped(x2, u2, bits: int = 8, group: int = 256):
    """Grouped quantize->dequantize: x2, u2 (R, D) float32 with
    D % group == 0 (the multi-dim shard_safe dispatch — groups stay on the
    last axis, no flatten)."""
    return quantize_grouped_pallas(x2, u2, bits=bits, group=group,
                                   interpret=INTERPRET)


@functools.partial(jax.jit, static_argnames=("bits", "group"))
def quantize_dequantize_kernel_dither(x2, seed, bits: int = 8,
                                      group: int = 256):
    """Grouped quantize->dequantize with the dither generated IN-KERNEL
    (hardware PRNG on TPU, in-kernel hash under interpret): 2 instead of 3
    HBM arrays per element. ``seed`` is the folded-key int32 scalar."""
    return quantize_grouped_pallas(x2, bits=bits, group=group, seed=seed,
                                   interpret=INTERPRET)


@functools.partial(jax.jit, static_argnames=("bits", "group"))
def quantize_encode_grouped(x2, u2, bits: int = 8, group: int = 256):
    """Wire-format encode: (codes int8 (R, D), scales f32 (R, D // group))
    with streamed dither draws."""
    return quantize_encode_grouped_pallas(x2, u2, bits=bits, group=group,
                                          interpret=INTERPRET)


@functools.partial(jax.jit, static_argnames=("bits", "group"))
def quantize_encode_kernel_dither(x2, seed, bits: int = 8, group: int = 256):
    """Wire-format encode with the in-kernel dither (see
    ``quantize_dequantize_kernel_dither``)."""
    return quantize_encode_grouped_pallas(x2, bits=bits, group=group,
                                          seed=seed, interpret=INTERPRET)


# ---------------------------------------------------------------------------
# shard_map wrappers: the kernel on GSPMD-sharded leaves, one pallas_call
# per shard (ROADMAP "a shard_map wrapper so multi-dim sharded leaves can
# use the kernel"). shard_safe grouping keeps quantization groups along the
# last axis with g dividing the per-shard width, so every group is
# shard-LOCAL and the per-shard kernel is bit-identical to the unsharded
# kernel/oracle given the same streamed dither draws (which the caller
# computes from GLOBAL element indices and shards alongside x).
# ---------------------------------------------------------------------------

def _full_pspec(sharding: NamedSharding, ndim: int) -> PartitionSpec:
    """The leaf's PartitionSpec padded to full rank (shard_map in_specs
    want one entry per dimension)."""
    spec = tuple(sharding.spec)
    return PartitionSpec(*(spec + (None,) * (ndim - len(spec))))


def rows_view(x, group: int):
    """The (R, D) kernel view — the ONE definition of the row layout every
    dispatch path shares (``core/compression.py`` delegates here):
    multi-dim leaves collapse leading dims and keep the grouped LAST axis;
    flat leaves tile into group-wide rows. Row-major order means the
    global element index (the hash-dither stream) is unchanged, which is
    what keeps kernel, per-shard kernel and jnp-oracle paths bit-identical
    for the same draws."""
    return x.reshape(-1, x.shape[-1]) if x.ndim > 1 \
        else x.reshape(-1, group)


def quantize_dequantize_sharded(x, u, bits: int, group: int,
                                sharding: NamedSharding):
    """Grouped quantize->dequantize of a sharded leaf: each shard collapses
    its LOCAL leading dims to rows and runs the Pallas kernel on its own
    block — no gather, no resharding. ``u`` is the globally-indexed dither
    (same shape as x); it is committed to x's sharding so each shard reads
    exactly the draws of its own elements."""
    pspec = _full_pspec(sharding, x.ndim)
    u = jax.device_put(u, NamedSharding(sharding.mesh, pspec))

    def body(xb, ub):
        x2 = rows_view(xb, group)
        out = quantize_dequantize_grouped(x2, ub.reshape(x2.shape),
                                          bits=bits, group=group)
        return out.reshape(xb.shape)

    return jax.shard_map(body, mesh=sharding.mesh, in_specs=(pspec, pspec),
                         out_specs=pspec, check_vma=False)(x, u)


def quantize_encode_sharded(x, u, bits: int, group: int,
                            sharding: NamedSharding):
    """Wire-format encode of a sharded leaf, one kernel per shard. Returns
    ``(codes int8 shaped like x, scales f32 shaped x.shape[:-1] +
    (D // group,))``, both sharded like x (the scales' last axis divides by
    the same factor since group | per-shard width)."""
    pspec = _full_pspec(sharding, x.ndim)
    u = jax.device_put(u, NamedSharding(sharding.mesh, pspec))

    def body(xb, ub):
        x2 = rows_view(xb, group)
        codes, scales = quantize_encode_grouped(x2, ub.reshape(x2.shape),
                                                bits=bits, group=group)
        return (codes.reshape(xb.shape),
                scales.reshape(xb.shape[:-1] + (-1,)))

    return jax.shard_map(body, mesh=sharding.mesh, in_specs=(pspec, pspec),
                         out_specs=(pspec, pspec), check_vma=False)(x, u)


@functools.partial(jax.jit, static_argnames=("bits", "group"))
def dequantize_reduce_grouped(codes, scales, w, bits: int = 8,
                              group: int = 256):
    """Fused dequantize + weighted accumulate over the leading client axis
    (the ``uplink="reduce"`` server-side partial aggregation): returns
    ``sum_c w[c] * dequant(codes[c], scales[c])`` without materializing the
    decoded f32 client stack. codes: (C, R, D) int8 with D % group == 0;
    scales: (C, R, D // group) f32; w: (C,) f32. Dequant math is the exact
    tail of ``ref.decode_groups_ref``; the c-sequential accumulation is
    the order of ``core.compression.weighted_sum``."""
    return decode_reduce_grouped_pallas(codes, scales, w, bits=bits,
                                        group=group, interpret=INTERPRET)


@functools.partial(jax.jit, static_argnames=("bits", "block"))
def quantize_dequantize(x, key, bits: int = 8, block: int = 256):
    """Unbiased block quantize->dequantize of a flat float32 stream.
    Draws the stochastic-rounding dither from ``key`` (threefry). This is
    the FedMM Quant operator (A4) on the wire-critical path."""
    u = jax.random.uniform(key, x.shape)
    return quantize_dequantize_with_dither(x, u, bits=bits, block=block)


@functools.partial(jax.jit, static_argnames=("causal", "window",
                                             "q_block", "kv_block"))
def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    q_block: int = 128, kv_block: int = 128):
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  q_block=q_block, kv_block=kv_block,
                                  interpret=INTERPRET)


@functools.partial(jax.jit, static_argnames=("chunk",))
def rwkv_wkv(r, k, v, w, u, chunk: int = 64):
    return rwkv_scan_pallas(r, k, v, w, u, chunk=chunk, interpret=INTERPRET)
