"""Pallas TPU kernels: block-wise stochastic quantize, dequantize-fused and
encode (wire-format) variants.

This is the communication hot spot of FedMM (Algorithm 2 lines 8-9): every
round each client quantizes its control-variate-corrected surrogate delta
before the uplink all-reduce. On TPU the quantize -> all-reduce -> apply path
runs at HBM bandwidth, so the kernels tile the grouped parameter stream into
VMEM blocks and do the scale/round on-chip in one pass.

Layout: every caller reshapes its leaf to a 2-D ``(R, D)`` view with
quantization groups of size ``g`` along the LAST axis (``D % g == 0``,
``G = D // g`` groups per row). The grid runs over row tiles only; each
block is a full-width ``(rt, D)`` row tile, and the kernel walks its G
groups as static 128-aligned lane slices ``[j*g, (j+1)*g)``. A full-width
tile is what makes every block lane-legal for Mosaic: the per-group
scales block is ``(rt, G)``, equal to the scales array's last dim, where
a per-group tile would need a 1-lane-wide ``(rt, 1)`` block. ``rt`` is a
multiple of 32 (the int8 sublane tile) sized from D (``_row_tile``). The
historical flat path is the ``D == g`` special case (one group per row);
multi-dim shard_safe leaves dispatch with ``D = leaf.shape[-1]`` so the
last-axis grouping (and hence GSPMD sharding) is preserved — no flatten
required.

Three kernel families:

  * ``quantize_grouped_pallas`` — quantize->dequantize fused (what the
    server receives), same math as ``ref.quantize_groups_ref``;
  * ``quantize_encode_grouped_pallas`` — the WIRE variant: emits int8 codes
    plus one f32 scale per group (``ref.encode_groups_ref``). The dequantized
    f32 array never touches HBM; the uplink moves ``n + 4 * n/g`` bytes
    instead of ``4 n``;
  * ``decode_reduce_grouped_pallas`` — the server side of the fused reduce
    uplink (Algorithm 2 line 13): sum_c w_c * dequant(codes_c, scales_c)
    over a stacked C-client payload, accumulating the weighted dequant
    on-chip — the decoded f32 client stack never touches HBM (the
    ``uplink="reduce"`` shard-local partial aggregation of
    ``api/driver.py`` via ``core/compression.py:decode_reduce_tree``).

Dither sources (per call, orthogonal to the kernel math):

  * streamed (``u`` argument) — the caller materializes the uniform draws
    (hash or threefry) in HBM and the kernel reads them alongside ``x``:
    3 HBM arrays per element (x in, u in, out);
  * in-kernel (``seed`` argument, ``u=None``) — the dither is generated
    on-chip: 2 HBM arrays per element. On real TPU (``interpret=False``)
    the draws come from the hardware PRNG (``pltpu.prng_seed`` /
    ``pltpu.prng_random_bits``), seeded once per row tile from the folded
    key and drawn group by group — the quantize and encode kernels share
    tiling and draw order, so ``decode(encode) == apply`` holds on the
    chip too. In interpret mode (CPU validation) the same murmur3-finalizer
    hash as ``core.compression.hash_dither`` is evaluated in-kernel from
    the global element index, so interpret-mode in-kernel draws are
    BIT-IDENTICAL to the streamed ``dither="hash"`` path — the
    structural/statistical properties are testable on CPU. Hardware-PRNG
    draws differ from the hash draws by construction, which is why
    ``dither="kernel"`` is opt-in and never golden-pinned (see
    ``core/compression.py``).

The kernel bodies are the SAME computation as the ``ref.py`` oracles —
together they are the repo's single quantizer implementation. All callers
reach them through ``core/compression.py`` via ``kernels/ops.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# one f32 (rt, D) row tile is about this many bytes: with x, u and the
# codes double-buffered that is ~4.5 MiB of scoped VMEM (v5e's default
# scoped limit is 16 MiB), leaving room for the per-group temporaries
_TILE_BYTES = 1 << 20
_SUBLANES = 32          # int8 sublane tile: rt of the codes block


def _row_tile(R: int, D: int) -> int:
    """Rows per full-width tile: a multiple of 32 holding ~``_TILE_BYTES``
    of f32, or all R rows when fewer (a block equal to the array's dim is
    always legal)."""
    rt = max(_SUBLANES, (_TILE_BYTES // (4 * D)) // _SUBLANES * _SUBLANES)
    return min(rt, R)


def _compiler_params(rt: int, D: int, bytes_per_elem: int):
    """A raised scoped-VMEM limit when the double-buffered blocks plus the
    per-group f32 temporaries outgrow v5e's default 16 MiB — only rows
    wider than 8192, where even 32-row tiles are large; else None."""
    need = 2 * rt * D * bytes_per_elem + 4 * rt * D * 4
    if need <= 16 << 20:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=min(need, 100 << 20))


# ---------------------------------------------------------------------------
# kernel bodies
# ---------------------------------------------------------------------------

def _quant_core(x, u, levels: float):
    """scale / stochastic-round shared by every variant (== the ref oracle)."""
    scale = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    safe = jnp.where(scale > 0, scale, 1.0)
    y = x / safe * levels
    lo = jnp.floor(y)
    q = lo + (u < (y - lo)).astype(jnp.float32)     # stochastic rounding
    return q, scale, safe


def _hash_uniform_u32(idx, seed):
    """murmur3-finalizer hash of a uint32 index -> f32 uniform in [0, 1) with
    24-bit resolution. MUST stay formula-identical to
    ``core.compression.hash_dither`` (the interpret-mode in-kernel dither
    reproduces the streamed hash draws exactly)."""
    x = idx * jnp.uint32(2654435761) + seed
    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return (x >> jnp.uint32(8)).astype(jnp.float32) * jnp.float32(2.0 ** -24)


def _group_dither(seed_ref, rt: int, group: int, j: int, row_stride: int,
                  hw: bool):
    """Dither for group j of the current (rt, D) row tile, generated
    entirely on-chip (zero HBM traffic).

    hw=True: the next (rt, group) draw of the hardware PRNG, which
    ``_seed_tile`` seeded for this tile. The top 24 bits convert through
    int32 (Mosaic has no uint32 -> f32 cast; 24 bits are exact in both).
    hw=False (interpret): murmur hash of the GLOBAL element index — the
    same draw ``hash_dither`` would have streamed in for this element.
    """
    shape = (rt, group)
    if hw:
        bits = pltpu.prng_random_bits(shape)
        top = jax.lax.shift_right_logical(pltpu.bitcast(bits, jnp.int32), 8)
        return top.astype(jnp.float32) * jnp.float32(2.0 ** -24)
    i = pl.program_id(0)
    seed = seed_ref[0, 0].astype(jnp.uint32)
    row = jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
    gidx = ((i.astype(jnp.uint32) * jnp.uint32(rt) + row)
            * jnp.uint32(row_stride) + jnp.uint32(j * group) + lane)
    return _hash_uniform_u32(gidx, seed)


def _seed_tile(seed_ref, hw: bool):
    """Seed the hardware PRNG once per row tile (no-op in interpret)."""
    if hw:
        pltpu.prng_seed(seed_ref[0, 0]
                        + pl.program_id(0) * jnp.int32(0x9E3779B9 - 2 ** 32))


def _groups(D: int, group: int):
    """The static 128-aligned lane slices of a row's quantization groups."""
    return [(j, pl.ds(j * group, group)) for j in range(D // group)]


def _dequant_kernel(*refs, levels: float, group: int, hw: bool,
                    streamed: bool):
    if streamed:
        x_ref, u_ref, o_ref = refs
        seed_ref = None
    else:
        seed_ref, x_ref, o_ref = refs
        _seed_tile(seed_ref, hw)
    rt, D = x_ref.shape
    for j, sl in _groups(D, group):
        x = x_ref[:, sl].astype(jnp.float32)        # (rt, g)
        u = (u_ref[:, sl].astype(jnp.float32) if streamed else
             _group_dither(seed_ref, rt, group, j, D, hw))
        q, scale, safe = _quant_core(x, u, levels)
        # multiply by the precomputed reciprocal: bit-identical to the jnp
        # oracle and to the wire-format decode in every compilation regime
        deq = q * safe * (1.0 / levels)
        o_ref[:, sl] = jnp.where(scale > 0, deq, 0.0).astype(o_ref.dtype)


def _encode_kernel(*refs, levels: float, group: int, hw: bool,
                   streamed: bool):
    if streamed:
        x_ref, u_ref, codes_ref, scale_ref = refs
        seed_ref = None
    else:
        seed_ref, x_ref, codes_ref, scale_ref = refs
        _seed_tile(seed_ref, hw)
    rt, D = x_ref.shape
    col = jax.lax.broadcasted_iota(jnp.int32, scale_ref.shape, 1)
    scales = jnp.zeros(scale_ref.shape, jnp.float32)
    for j, sl in _groups(D, group):
        x = x_ref[:, sl].astype(jnp.float32)
        u = (u_ref[:, sl].astype(jnp.float32) if streamed else
             _group_dither(seed_ref, rt, group, j, D, hw))
        q, scale, _ = _quant_core(x, u, levels)
        codes_ref[:, sl] = q.astype(jnp.int8)
        scales = jnp.where(col == j, scale, scales)  # (rt, 1) -> column j
    scale_ref[...] = scales


# ---------------------------------------------------------------------------
# dispatchers
# ---------------------------------------------------------------------------

def _grid_pad(x2, u2, rt: int):
    """Pad the row axis to a whole number of tiles. Padded rows quantize to
    scale 0 -> codes 0 and are sliced off by the caller."""
    R = x2.shape[0]
    n_tiles = -(-R // rt)
    pad = n_tiles * rt - R
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
        if u2 is not None:
            u2 = jnp.pad(u2, ((0, pad), (0, 0)))
    return x2, u2, n_tiles


def _quantize_call(kernel, x2, u2, seed, out_specs, out_shape, *, bits,
                   group, interpret, out_bytes_per_elem):
    """The shared pallas_call of the quantize and encode families: one
    full-width row tile per grid step, dither streamed (``u2``) or made
    in-kernel (``seed``)."""
    R, D = x2.shape
    assert D % group == 0, "last axis must be a whole number of groups"
    if u2 is None and seed is None:
        raise ValueError("need streamed draws u2 or an in-kernel dither seed")
    rt = _row_tile(R, D)
    x2p, u2p, n_tiles = _grid_pad(x2, u2, rt)
    tile = pl.BlockSpec((rt, D), lambda i: (i, 0))
    streamed = u2 is not None
    body = functools.partial(kernel, levels=2.0 ** (bits - 1) - 1.0,
                             group=group, hw=not interpret,
                             streamed=streamed)
    in_bytes = 8 if streamed else 4
    call = pl.pallas_call(
        body, grid=(n_tiles,),
        in_specs=([tile, tile] if streamed else
                  [pl.BlockSpec(memory_space=pltpu.SMEM), tile]),
        out_specs=out_specs(rt, tile),
        out_shape=out_shape(n_tiles * rt),
        compiler_params=_compiler_params(rt, D,
                                         in_bytes + out_bytes_per_elem),
        interpret=interpret)
    if streamed:
        return call(x2p, u2p)
    return call(jnp.asarray(seed, jnp.int32).reshape(1, 1), x2p)


def quantize_grouped_pallas(x2, u2=None, *, bits: int = 8, group: int = 256,
                            seed=None, interpret: bool = True):
    """Fused quantize->dequantize of a grouped 2-D stream.

    x2: (R, D) float32 with D % group == 0 — groups along the last axis.
    u2: (R, D) uniform draws (streamed dither), or None to generate the
    dither in-kernel from ``seed`` (int32 scalar; 2 instead of 3 HBM arrays
    per element). Returns the dequantized (R, D) array.

    interpret=True validates the kernel body on CPU; on TPU pass
    interpret=False for the compiled Mosaic kernel (and the hardware PRNG
    when seed-driven).
    """
    R, D = x2.shape
    out = _quantize_call(
        _dequant_kernel, x2, u2, seed,
        out_specs=lambda rt, tile: tile,
        out_shape=lambda rows: jax.ShapeDtypeStruct((rows, D), x2.dtype),
        bits=bits, group=group, interpret=interpret,
        out_bytes_per_elem=x2.dtype.itemsize)
    return out[:R]


def quantize_encode_grouped_pallas(x2, u2=None, *, bits: int = 8,
                                   group: int = 256, seed=None,
                                   interpret: bool = True):
    """Wire-format encode of a grouped 2-D stream: int8 codes + f32 scales.

    x2: (R, D) float32 with D % group == 0. Returns
    ``(codes int8 (R, D), scales f32 (R, D // group))`` — the dequantized
    array is never materialized (1 + 4/group output bytes per element
    instead of 4). Dither exactly as in ``quantize_grouped_pallas``.
    """
    R, D = x2.shape
    G = D // group
    codes, scales = _quantize_call(
        _encode_kernel, x2, u2, seed,
        out_specs=lambda rt, tile: [
            tile, pl.BlockSpec((rt, G), lambda i: (i, 0))],
        out_shape=lambda rows: [
            jax.ShapeDtypeStruct((rows, D), jnp.int8),
            jax.ShapeDtypeStruct((rows, G), jnp.float32)],
        bits=bits, group=group, interpret=interpret, out_bytes_per_elem=1)
    return codes[:R], scales[:R]


def _decode_reduce_kernel(w_ref, codes_ref, scales_ref, o_ref, *,
                          levels: float, group: int):
    """One (rt, D) row tile of one client c: dequantize (== the tail of
    ``ref.decode_groups_ref``) and accumulate w_c * deq into the output
    block. The client grid dim is INNERMOST, so each output block stays
    resident while every client's contribution lands on it."""
    c = pl.program_id(1)
    w = w_ref[c, 0]
    scales = scales_ref[0].astype(jnp.float32)      # (rt, G)
    col = jax.lax.broadcasted_iota(jnp.int32, scales.shape, 1)
    for j, sl in _groups(codes_ref.shape[2], group):
        # column j of the scales: the max of one scale >= 0 and zeros is
        # that scale exactly (a NaN scale only ever fails `scale > 0`)
        scale = jnp.max(jnp.where(col == j, scales, 0.0), axis=1,
                        keepdims=True)                # (rt, 1)
        q = codes_ref[0, :, sl].astype(jnp.float32)   # (rt, g)
        safe = jnp.where(scale > 0, scale, 1.0)
        deq = q * safe * (1.0 / levels)
        contrib = w * jnp.where(scale > 0, deq, 0.0)

        @pl.when(c == 0)
        def _init():
            o_ref[:, sl] = contrib

        @pl.when(c > 0)
        def _acc():
            o_ref[:, sl] += contrib


def decode_reduce_grouped_pallas(codes, scales, w, *, bits: int = 8,
                                 group: int = 256, interpret: bool = True):
    """Fused dequantize + weighted accumulate over the client axis.

    codes: (C, R, D) int8 with D % group == 0; scales: (C, R, D // group)
    f32 (one per quantization group); w: (C,) f32 client weights. Returns
    the (R, D) f32 weighted sum sum_c w[c] * dequant(codes[c], scales[c])
    — the decoded per-client f32 arrays never exist in HBM (the output is
    the only f32 array the kernel writes). Dequant math is the exact tail
    of ``ref.decode_groups_ref``; the accumulation order is sequential in
    c — the order of ``core.compression.weighted_sum``.
    """
    C, R, D = codes.shape
    G = D // group
    assert D % group == 0, "last axis must be a whole number of groups"
    assert scales.shape == (C, R, G), scales.shape
    assert w.shape == (C,), w.shape
    rt = _row_tile(R, D)
    n_tiles = -(-R // rt)
    pad = n_tiles * rt - R
    if pad:
        # padded rows carry scale 0 -> contribute exactly 0
        codes = jnp.pad(codes, ((0, 0), (0, pad), (0, 0)))
        scales = jnp.pad(scales, ((0, 0), (0, pad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_decode_reduce_kernel,
                          levels=2.0 ** (bits - 1) - 1.0, group=group),
        grid=(n_tiles, C),                           # c innermost
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((1, rt, D), lambda i, c: (c, i, 0)),
                  pl.BlockSpec((1, rt, G), lambda i, c: (c, i, 0))],
        out_specs=pl.BlockSpec((rt, D), lambda i, c: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_tiles * rt, D), jnp.float32),
        compiler_params=_compiler_params(rt, D, 1 + 4),
        interpret=interpret,
    )(w.astype(jnp.float32).reshape(C, 1), codes, scales)
    return out[:R]


def quantize_block_pallas(x, u, bits: int = 8, block: int = 256,
                          interpret: bool = True):
    """Historical flat entry point: x, u flat (n,) float32 with
    n % block == 0. The (n // block, block) reshape is the D == g special
    case of the grouped dispatcher (one group per row)."""
    n = x.shape[0]
    assert n % block == 0, "pad the stream to a multiple of the quant block"
    out = quantize_grouped_pallas(
        x.reshape(-1, block), u.reshape(-1, block), bits=bits, group=block,
        interpret=interpret)
    return out.reshape(-1)
