"""Unbiased compression operators (assumption A4) and the partial-
participation composition of Lemma 1 (Appendix D.2).

Every operator is a pair (compress_fn, omega) with

    E[Quant(s)] = s,      E[||Quant(s) - s||^2] <= omega ||s||^2.

Operators act leaf-wise on pytrees and fold the RNG key per leaf.

This module is the ONE compression subsystem of the repo: the reference
Algorithm 2 (``core/fedmm.py``), the transformer-scale trainer
(``fed/trainer.py``), the benchmarks, and the tests all route through the
``Compressor`` objects built here. The stochastic-rounding block quantizer
has exactly one rounding semantics, defined by the pure-jnp oracle
``kernels/ref.py:quantize_groups_ref``; ``quantize_leaf`` below dispatches

  * large leaves (>= ``KERNEL_DISPATCH_MIN`` elements with a 128-aligned
    group — ANY rank: multi-dim leaves collapse their leading dims to rows
    while the grouped last axis stays intact) to the Pallas kernels in
    ``kernels/quantize_block.py`` via ``kernels/ops.py`` (interpret mode on
    CPU, compiled Mosaic on TPU), and
  * everything else to the jnp oracle — in shard_safe mode applied
    group-wise along the LAST axis only, an elementwise-fusable graph that
    preserves GSPMD sharding. (The kernel's leading-dim collapse keeps the
    last axis — the 'model'-sharded one — intact; on a sharded mesh the
    pallas_call itself still needs a shard_map wrapper, so multi-host
    sharded leaves should keep the jnp path.)

Grouping has two modes behind ``shard_safe=``:

  * ``shard_safe=False`` (default — the paper's block-p quantizer, used by
    the reference Algorithm 2 and the figures): each leaf is flattened and
    padded to full ``block``-sized groups, so every leaf is genuinely
    quantized at the requested block size;
  * ``shard_safe=True`` (the trainer at transformer scale): groups stay
    along the LAST axis with size ``group_size(D, block)`` — the largest
    power-of-2 that divides the per-shard width under worst-case 32-way
    sharding. Leaves whose last dim yields g == 1 pass through unquantized
    (and are billed at their dtype by ``payload_bytes``).

The stochastic-rounding dither comes from one of three sources behind the
``dither=`` flag:

  * ``"uniform"`` — ``jax.random.uniform`` (threefry; statistically clean,
    but several u32 intermediates per element on parameter-sized tensors);
  * ``"hash"``    — a fused murmur3-finalizer hash of the element index and
    the folded key, producing 24-bit-resolution uniforms in [0, 1). Zero
    extra memory; the trainer's default at scale.
  * ``"kernel"``  — OPT-IN: the dither is generated INSIDE the Pallas
    kernel (2 instead of 3 HBM arrays per element). On real TPU the draws
    come from the hardware PRNG (``pltpu.prng_seed``/``prng_random_bits``
    seeded from the folded key + grid position) and therefore DIFFER from
    the streamed sources — this mode is never golden-pinned. In interpret
    mode (CPU validation) the kernel evaluates the same murmur hash as
    ``"hash"`` in-kernel, so CPU draws match ``"hash"`` exactly. Leaves
    that do not dispatch to the kernel fall back to ``"hash"``.

Both streamed paths compare the dither against the round-up fraction in
float32 (24-bit resolution), so the quantizer is unbiased to ~2^-24 per
element — see ``tests/test_compression_unified.py`` for the 1/sqrt(trials)
check.

Compute dtype is a third axis behind ``compute=``: ``"f32"`` (default) is
the oracle semantics — the whole chain in float32, bit-identical to the
Pallas kernel; ``"native"`` keeps everything except the dither comparison
in the input dtype (the ROADMAP bf16 path: half the transient HBM on
parameter-sized bf16 chains, codes within ±1 level of the oracle on the
~2^-8-measure bf16 ratio-rounding boundary — see
``kernels/ref.py:quantize_groups_native``).

Wire format (the PACKED low-bit uplink; see src/repro/api/README.md)
--------------------------------------------------------------------
``block_quant`` compressors additionally expose an ``encode``/``decode``
pair with a REAL wire format: per leaf, a ``PackedLeaf`` of

  * ``codes``  — the integer quantization codes: int8 (1 byte/coord) for
    4 < bits <= 8, bit-packed two-per-byte uint8 (0.5 bytes/coord) for
    bits <= 4 (adjacent pairs along the code stream's last axis);
  * ``scales`` — one scale per quantization group, float32 under the
    oracle semantics (input dtype under ``compute="native"``).

``decode(encode(key, tree))`` is BIT-IDENTICAL to ``apply(key, tree)``
(same draws, same dispatch, same arithmetic order — the int8/nibble
round-trip of the integer codes is exact), so the federated golden
trajectories are unchanged when drivers aggregate in code space.
``payload_bytes`` counts EXACTLY the bytes of those buffers (codes +
scales, including flat-mode pad), and ``encoded_bytes``/``wire_bytes``
measure the same number off an actual payload / eval_shape.

``decode_reduce_tree`` is the server side of the driver's fused
``uplink="reduce"`` collective: the mu-weighted sum over a stacked
C-client payload with dequantize fused into the accumulation (the Pallas
``decode_reduce_grouped_pallas`` kernel for large aligned leaves — the
decoded f32 client stack never materializes; jnp decode + tensordot,
bit-identical to decode-then-reduce, everywhere else).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from ..kernels import ops as kernel_ops
from ..kernels import ref as kernel_ref

Pytree = object

# Leaves at least this large (with a 128-aligned group) go to the Pallas
# kernel.
KERNEL_DISPATCH_MIN = 1 << 16

# at or below this code width, two codes travel per byte
PACK_BITS = 4

DITHERS = ("hash", "uniform", "kernel")


# ---------------------------------------------------------------------------
# the wire format
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PackedLeaf:
    """One leaf's uplink payload: packed codes + per-group scales.

    codes: int8 ``(..., D)`` (shard mode) / ``(padded,)`` (flat mode), or
    uint8 with half the last dim when bit-packed (bits <= 4). scales: one
    per group — ``(..., D // g)`` shard / ``(n_blocks,)`` flat. ``check``
    is the optional wire-integrity checksum: one uint32 per payload (a
    position-weighted murmur-mixed digest of the codes AND scales
    buffers, ``leaf_checksum``), computed by the sender at encode time
    and verified by ``verify_payload`` at decode — ``None`` for
    compressors built without ``checksum=True``. The remaining fields
    are static pytree metadata (shape/dtype of the original leaf, code
    width, group size, grouping mode), so ``vmap`` batches the buffers
    and leaves the layout alone."""
    codes: Pytree
    scales: Pytree
    shape: tuple
    dtype: str
    bits: int
    group: int
    mode: str  # "shard" | "flat"
    check: Pytree = None  # uint32 digest (stacked under vmap) | None


jax.tree_util.register_dataclass(
    PackedLeaf, data_fields=("codes", "scales", "check"),
    meta_fields=("shape", "dtype", "bits", "group", "mode"))


def pack_nibbles(codes):
    """int8 codes in [-8, 7], even last dim -> uint8 with adjacent pairs in
    one byte (low nibble = even index, high nibble = odd index)."""
    lo = codes[..., 0::2]
    hi = codes[..., 1::2]
    return ((lo & 0x0F) | ((hi & 0x0F) << 4)).astype(jnp.uint8)


def unpack_nibbles(packed):
    """Exact inverse of ``pack_nibbles`` (arithmetic-shift sign extension)."""
    b = packed.astype(jnp.int8)
    lo = jnp.left_shift(b, 4) >> 4
    hi = b >> 4
    return jnp.stack([lo, hi], axis=-1).reshape(packed.shape[:-1] + (-1,))


def _maybe_pack(codes, bits: int):
    if bits <= PACK_BITS and codes.shape[-1] % 2 == 0:
        return pack_nibbles(codes)
    return codes


def _tree_bytes(tree) -> int:
    """Actual buffer bytes of a pytree (arrays or ShapeDtypeStructs)."""
    total = 0
    for leaf in jax.tree.leaves(tree):
        shape = getattr(leaf, "shape", ())
        n = int(math.prod(shape)) if shape else 1
        total += n * jnp.dtype(getattr(leaf, "dtype", jnp.float32)).itemsize
    return total


# ---------------------------------------------------------------------------
# wire integrity: per-leaf checksums on the packed payload
# ---------------------------------------------------------------------------

# one uint32 digest per PackedLeaf on the wire
CHECKSUM_BYTES = 4

_CKSUM_GOLDEN = 0x9E3779B9   # position salt (golden-ratio odd constant)
_CKSUM_SCALE_SALT = 0x85EBCA6B  # domain separation: scales vs codes stream


def _mix32(u):
    """murmur3 finalizer on uint32 — the same mixer ``hash_dither`` uses,
    applied per element so ANY single-element change flips the digest
    term (modular-sum collisions are the 2^-32 birthday bound, not a
    structured weakness like a plain sum's swap-invariance)."""
    u = (u ^ (u >> 16)) * jnp.uint32(0x7FEB352D)
    u = (u ^ (u >> 15)) * jnp.uint32(0x846CA68B)
    return u ^ (u >> 16)


def _as_u32_stream(buf, n_batch: int):
    """Bitcast any codes/scales buffer to a ``batch + (m,)`` uint32 view
    (value-preserving per element: int8/uint8 widen, f32 bitcasts, bf16
    bitcasts to u16 then widens)."""
    dt = jnp.dtype(buf.dtype)
    if dt == jnp.float32:
        u = jax.lax.bitcast_convert_type(buf, jnp.uint32)
    elif dt.kind == "f":
        # sub-f32 floats (bf16/f16): bitcast to the same-width uint, widen
        u = jax.lax.bitcast_convert_type(
            buf, jnp.dtype(f"uint{dt.itemsize * 8}")).astype(jnp.uint32)
    else:
        # int8 codes widen through int32 (sign-extended, deterministic)
        u = buf.astype(jnp.int32).astype(jnp.uint32)
    batch = buf.shape[:n_batch]
    return u.reshape(batch + (-1,))


def _digest(buf, n_batch: int, salt: int):
    u = _as_u32_stream(buf, n_batch)
    pos = jax.lax.broadcasted_iota(jnp.uint32, u.shape, u.ndim - 1)
    terms = _mix32(u + pos * jnp.uint32(_CKSUM_GOLDEN) + jnp.uint32(salt))
    # uint32 sum wraps mod 2^32 — order-independent, so the stacked
    # (batched) recompute at verify time matches the per-client encode
    return jnp.sum(terms, axis=-1, dtype=jnp.uint32)


def leaf_checksum(codes, scales, n_batch: int = 0):
    """The wire digest of one payload leaf's buffers: position-weighted
    murmur-mixed uint32 sum over the codes stream and the (domain-
    separated) scales stream. ``n_batch`` leading axes are treated as
    batch dims — one digest per batch row — so the same function computes
    the sender digest (``n_batch=0``, inside the per-client vmap) and the
    receiver recompute on a stacked n-client payload (``n_batch=1``)."""
    return (_digest(codes, n_batch, 0)
            + _digest(scales, n_batch, _CKSUM_SCALE_SALT))


def payload_batch_dims(p: "PackedLeaf") -> int:
    """How many leading axes of ``p.codes`` are client/batch stacking on
    top of the recorded wire layout (the convention ``decode_leaf`` uses:
    shard mode keeps the leaf's rank, flat mode is a 1-D stream)."""
    base = len(p.shape) if p.mode == "shard" else 1
    return p.codes.ndim - base


def verify_leaf(p):
    """Recompute one leaf's digest and compare to the wire checksum.
    Returns a bool array over the leaf's batch dims (scalar True for
    unbatched / unchecksummed / raw leaves)."""
    if not isinstance(p, PackedLeaf) or p.check is None:
        return jnp.bool_(True)
    nb = payload_batch_dims(p)
    return jnp.equal(leaf_checksum(p.codes, p.scales, nb), p.check)


def verify_payload(payload):
    """Per-client wire verification of a (possibly stacked) payload:
    AND of every checksummed leaf's digest match, broadcast over the
    batch dims — ``ok[c] == True`` iff EVERY leaf of client c's payload
    arrived intact. Scalar True when nothing carries a checksum."""
    ok = jnp.bool_(True)
    for leaf in jax.tree.leaves(
            payload, is_leaf=_is_payload_leaf):
        ok = jnp.logical_and(ok, verify_leaf(leaf))
    return ok


def zero_invalid_rows(payload, ok):
    """Null out every buffer row of clients that failed verification
    (``ok`` broadcastable over each buffer's leading batch axes), BEFORE
    decode: corrupted scale bits can decode to NaN/inf, and a NaN times a
    zero weight is NaN — the poison would survive any masked reduction.
    Zero codes x zero scales decode to exact zeros, so a dropped client
    contributes nothing on every downstream path (decode, decode_reduce,
    variate updates)."""
    okb = jnp.asarray(ok, jnp.bool_)

    def _zero(buf):
        sel = okb.reshape(okb.shape + (1,) * (buf.ndim - okb.ndim))
        return jnp.where(sel, buf, jnp.zeros((), buf.dtype))

    def leaf(p):
        if not isinstance(p, PackedLeaf):
            return p
        return dataclasses.replace(
            p, codes=_zero(p.codes), scales=_zero(p.scales),
            check=None if p.check is None else _zero(p.check))

    return jax.tree.map(leaf, payload, is_leaf=_is_payload_leaf)


@dataclasses.dataclass(frozen=True)
class Compressor:
    """An unbiased compressor satisfying A4(omega), with communication
    accounting (payload bytes per uplink, effective omega under Lemma 1).

    ``apply`` is the fused quantize->dequantize operator (what legacy
    callers see). Compressors with a real wire format also carry
    ``encode`` (-> pytree with ``PackedLeaf`` leaves; unquantized leaves
    pass through raw) and ``decode`` (its exact inverse up to quantization:
    ``decode . encode == apply`` bit-for-bit). ``decode`` accepts stacked
    payloads (extra leading axes on the buffers) so servers can aggregate
    straight off an n-client payload stack."""

    apply: Callable  # (key, pytree) -> pytree
    omega: float     # relative variance bound
    bits: float      # payload bits per coordinate (for communication accounting)
    name: str = "compressor"
    # per-leaf payload model: (shape, itemsize) -> bytes on the wire
    # (None -> bits/8 * n)
    payload_fn: Optional[Callable] = None
    encode: Optional[Callable] = None  # (key, pytree) -> payload pytree
    decode: Optional[Callable] = None  # payload pytree -> pytree
    # (payload, w, fused=None) -> weighted partial aggregate in the
    # accumulation dtype: the server side of the driver's fused
    # ``uplink="reduce"`` stage, carrying this compressor's OWN kernel
    # dispatch policy (threshold, alignment) — see ``decode_reduce_tree``
    decode_reduce: Optional[Callable] = None
    # encode stamps each PackedLeaf with its wire digest (CHECKSUM_BYTES
    # per leaf, billed in payload_fn) and the server verifies at decode
    checksum: bool = False
    # (key, partial pytree) -> payload pytree: re-enter the wire format at
    # a topology tier boundary (requantize the f32 edge partial before it
    # crosses the backbone). Stamps FRESH digests — each tier's hop is
    # independently verifiable. None for compressors without a wire format.
    reencode: Optional[Callable] = None

    def __call__(self, key, s):
        return self.apply(key, s)

    def _leaf_payload(self, shape, itemsize: float = 4.0) -> float:
        n = float(math.prod(shape)) if shape else 1.0
        if self.payload_fn is not None:
            return float(self.payload_fn(tuple(shape), float(itemsize)))
        return n * self.bits / 8.0

    def payload_bytes(self, tree) -> float:
        """Uplink bytes for one client's payload of ``tree``'s shape.
        Accepts arrays or ShapeDtypeStructs (shape + dtype are read, so
        uncompressed bf16 leaves bill 2 bytes/coord, not 4). For wire-format
        compressors this equals the ACTUAL encoded buffer bytes —
        ``tests/test_wire_format.py`` pins it against ``encoded_bytes``."""
        total = 0.0
        for leaf in jax.tree.leaves(tree):
            shape = getattr(leaf, "shape", ())
            dt = getattr(leaf, "dtype", None)
            itemsize = float(jnp.dtype(dt).itemsize) if dt is not None else 4.0
            total += self._leaf_payload(shape, itemsize)
        return total

    def encoded_bytes(self, payload) -> int:
        """Actual wire bytes of one encoded payload (codes + scales buffers,
        raw passthrough leaves at their dtype)."""
        return _tree_bytes(payload)

    def wire_bytes(self, tree) -> float:
        """Exact uplink bytes for one client, measured off the encoded
        buffers via ``eval_shape`` (no FLOPs); falls back to the analytic
        ``payload_bytes`` model for compressors without a wire format."""
        if self.encode is None:
            return self.payload_bytes(tree)
        structs = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), tree)
        payload = jax.eval_shape(self.encode, jax.random.PRNGKey(0), structs)
        return float(self.encoded_bytes(payload))

    def round_metrics(self, tree, p: float = 1.0) -> dict:
        """Static per-round accounting: payload per client, A4 variance
        bound, and the Lemma-1 effective bound under participation p."""
        return {
            "payload_bytes_per_client": self.payload_bytes(tree),
            "omega": self.omega,
            "omega_eff": effective_omega(self.omega, p),
        }


def _tree_keyed_map(fn, key, tree):
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(treedef, [fn(k, x) for k, x in zip(keys, leaves)])


# ---------------------------------------------------------------------------
# Identity (omega = 0)
# ---------------------------------------------------------------------------

def identity() -> Compressor:
    return Compressor(
        apply=lambda key, s: s, omega=0.0, bits=32.0, name="identity",
        payload_fn=lambda shape, itemsize:
            (float(math.prod(shape)) if shape else 1.0) * itemsize)


# ---------------------------------------------------------------------------
# Stochastic uniform quantization in blocks (block-p quantization of
# Dieuleveut et al. 2021, Supp. B; QSGD-style): per group of size g along the
# last axis, scale = max|x|, stochastic-round x/scale to 2^(b-1) levels.
# A4 bound: per-coord Var <= (scale/levels)^2 / 4 and scale^2 <= ||group||^2,
# so E||Q(s)-s||^2 <= g/(4 levels^2) ||s||^2 <= block/(4 levels^2) ||s||^2.
# ---------------------------------------------------------------------------

def group_size(D: int, block: int) -> int:
    """Largest power-of-2 quantization group that divides the per-shard
    width of the last dim (worst case 32-way sharding), capped at ``block``.
    Keeping groups shard-local is what lets GSPMD partition the quantizer —
    a flat reshape across sharded dims would force full rematerialization
    of parameter-sized tensors (observed: 7 TB/device on qwen3-235b)."""
    per = D
    for s in (32, 16):
        if D % s == 0:
            per = D // s
            break
    per = max(per, 1)
    g = 1
    while per % (g * 2) == 0 and g * 2 <= block:
        g *= 2
    return g


def fold_seed(key):
    """The int32 scalar seed of the folded key — the SAME derivation
    ``hash_dither`` uses (kd[0] ^ kd[-1]), handed to the in-kernel dither
    so interpret-mode kernel draws replicate the streamed hash draws."""
    kd = jax.random.key_data(key).astype(jnp.uint32)
    return (kd.reshape(-1)[0] ^ kd.reshape(-1)[-1]).astype(jnp.int32)


def hash_dither(key, shape):
    """Stochastic-rounding dither: murmur3-style integer hash of the element
    coordinates, seeded by the (folded) JAX key, mapped to float32 uniforms
    in [0, 1) with 24-bit resolution. Elementwise + broadcast only, so it
    fuses into the surrounding quantization chain, costs zero extra HBM, and
    respects sharding (threefry on parameter-sized tensors costs several
    u32/u64 intermediates per element — ~20 GB/device observed)."""
    kd = jax.random.key_data(key).astype(jnp.uint32)
    seed = kd.reshape(-1)[0] ^ kd.reshape(-1)[-1]
    idx = jnp.zeros(shape, jnp.uint32)
    stride = jnp.uint32(1)
    for d in range(len(shape) - 1, -1, -1):
        idx = idx + jax.lax.broadcasted_iota(jnp.uint32, shape, d) * stride
        stride = stride * jnp.uint32(shape[d])
    x = idx * jnp.uint32(2654435761) + seed
    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    # top 24 bits -> [0, 1): exact in f32, so P(u < t) = t +- 2^-24. The old
    # trainer path compared a uint8-truncated threshold instead, which
    # systematically rounded fractions near 1 down (bias up to ~0.4%/elem).
    return (x >> jnp.uint32(8)).astype(jnp.float32) * jnp.float32(2.0 ** -24)


def _make_dither(dither: str, key, shape):
    if dither == "hash":
        return hash_dither(key, shape)
    if dither == "uniform":
        return jax.random.uniform(key, shape, jnp.float32)
    raise ValueError(f"unknown dither source {dither!r} (want 'hash'|"
                     f"'uniform'; 'kernel' is resolved by the dispatcher)")


def _stream_dither(dither: str) -> str:
    """The streamed fallback for leaves that do not reach the kernel:
    'kernel' degrades to 'hash' (zero-memory, same uniform quality)."""
    return "hash" if dither == "kernel" else dither


def _kernel_route(x, g: int, kernel_threshold: int) -> str:
    """One dispatch decision shared by apply and encode (they MUST agree,
    or decode . encode would not be bit-identical to apply). Returns

      * ``"kernel"``    — the direct Pallas path: large leaf, 128-aligned
        group, and the leaf's buffers live on ONE device (unsharded,
        fully replicated, or a single-device process);
      * ``"shard_map"`` — the leaf is genuinely partitioned under a
        ``NamedSharding`` whose per-shard last-axis width keeps whole
        groups: run the kernel per shard via the ``kernels/ops.py``
        shard_map wrappers (shard-safe groups are shard-local by
        construction, so per-shard kernels are bit-identical to the
        global oracle). Only the shard_safe caller honors this — the
        flat (block-p) layout groups across the global element stream,
        which shards do not preserve;
      * ``"jnp"``       — everything else (small/misaligned leaves,
        opaque or group-splitting shardings, and TRACED leaves inside a
        jit on a multi-device process, whose sharding is unknowable at
        trace time — the conservative pre-sharding behavior).

    This replaces the old process-wide ``jax.device_count() > 1`` guard,
    which silently dropped the kernel for every multi-dim leaf on a
    multi-device host even when the leaf was unsharded or fully
    replicated (tests/test_sharded_driver.py pins the regression under
    8 fake CPU devices)."""
    if x.size < kernel_threshold or g % 128 != 0 or g < 2:
        return "jnp"
    # the tracer check is EXPLICIT (not "has no .sharding attribute"):
    # newer jax versions expose abstract shardings on tracers, which must
    # never route to the eager-only shard_map wrapper
    sharding = (None if isinstance(x, jax.core.Tracer)
                else getattr(x, "sharding", None))
    if sharding is None:
        # traced leaf (or ShapeDtypeStruct): sharding unknowable — keep
        # the conservative behavior for multi-dim leaves so a pjit'd
        # caller never pays a GSPMD gather around an unshardable
        # pallas_call
        # repro: allow[RPL001] tracer fallback only — eager leaves above
        if x.ndim > 1 and jax.device_count() > 1:
            return "jnp"
        return "kernel"
    if sharding.is_fully_replicated or len(sharding.device_set) == 1:
        return "kernel"
    if isinstance(sharding, jax.sharding.NamedSharding):
        shard_shape = sharding.shard_shape(tuple(x.shape))
        if shard_shape[-1] % g == 0:
            return "shard_map"
    return "jnp"


def _kernel_eligible(x, g: int, kernel_threshold: int) -> bool:
    """The flat-mode predicate: only the direct single-device kernel path
    (the flat element stream's groups cross shard boundaries, so sharded
    leaves keep the jnp path there)."""
    return _kernel_route(x, g, kernel_threshold) == "kernel"


def _rows_view(x, g: int):
    """The (R, D) kernel view — ONE definition shared with the per-shard
    dispatch (``kernels/ops.py:rows_view``): the row layout is bit-
    identity-critical (it fixes the global dither element stream)."""
    return kernel_ops.rows_view(x, g)


def quantize_leaf(key, x, bits: int = 8, block: int = 256,
                  dither: str = "uniform", shard_safe: bool = False,
                  kernel_threshold: int = KERNEL_DISPATCH_MIN,
                  compute: str = "f32"):
    """Quantize-dequantize ONE array leaf. Single source of truth for the
    repo's stochastic-rounding block quantizer: grouping via ``shard_safe``
    (see module docstring), dither via ``dither=``, math via the kernel
    oracle pair (Pallas for large leaves — any rank — the jnp oracle
    otherwise; bit-identical given the same draws).

    ``compute``:
      * ``"f32"``    (default) — oracle semantics: the whole chain runs in
        float32 regardless of input dtype (bit-identical to the kernel);
      * ``"native"`` — the ROADMAP bf16 compute path: scale/ratio/dequant
        stay in the input dtype, ONLY the dither-vs-fraction comparison is
        f32 (``kernels/ref.py:quantize_groups_native``, which documents the
        ±1-level equivalence tolerance for bf16 ratio rounding). Halves the
        transient HBM on parameter-sized bf16 chains; no-op for f32 inputs.
    """
    if compute not in ("f32", "native"):
        raise ValueError(f"compute={compute!r} (want 'f32'|'native')")
    if dither not in DITHERS:
        raise ValueError(f"dither={dither!r} (want one of {DITHERS})")
    if bits == 0 or x.ndim == 0 or x.size == 0:
        return x
    orig_dtype = x.dtype
    native = compute == "native" and orig_dtype != jnp.float32

    if shard_safe:
        # groups along the last axis only: elementwise-fusable, preserves
        # GSPMD sharding of parameter-sized leaves
        D = x.shape[-1]
        g = group_size(D, block)
        if g < 2:
            return x  # one-element groups reproduce x exactly; skip the work
        if native:
            u = _make_dither(_stream_dither(dither), key, x.shape)
            xg = x.reshape(x.shape[:-1] + (D // g, g))
            deq = kernel_ref.quantize_groups_native(xg, u.reshape(xg.shape),
                                                    bits=bits)
            return deq.reshape(x.shape)
        route = _kernel_route(x, g, kernel_threshold)
        if route == "kernel":
            x2 = _rows_view(x.astype(jnp.float32), g)
            if dither == "kernel":
                out = kernel_ops.quantize_dequantize_kernel_dither(
                    x2, fold_seed(key), bits=bits, group=g)
            else:
                u = _make_dither(dither, key, x.shape)
                out = kernel_ops.quantize_dequantize_grouped(
                    x2, u.reshape(x2.shape), bits=bits, group=g)
            return out.reshape(x.shape).astype(orig_dtype)
        if route == "shard_map":
            # partitioned leaf: one kernel per shard (groups are shard-
            # local). The dither is streamed from GLOBAL element indices,
            # so the draws — and hence the codes — are bit-identical to
            # the unsharded kernel/oracle. ``dither="kernel"`` seeds from
            # grid position, which is not stable under resharding, so it
            # degrades to the streamed hash here like every off-kernel
            # leaf.
            u = _make_dither(_stream_dither(dither), key, x.shape)
            out = kernel_ops.quantize_dequantize_sharded(
                x.astype(jnp.float32), u, bits=bits, group=g,
                sharding=x.sharding)
            return out.astype(orig_dtype)
        u = _make_dither(_stream_dither(dither), key, x.shape)
        xg = x.astype(jnp.float32).reshape(x.shape[:-1] + (D // g, g))
        deq = kernel_ref.quantize_groups_ref(xg, u.reshape(xg.shape),
                                             bits=bits)
        return deq.reshape(x.shape).astype(orig_dtype)

    # reference block-p semantics (Dieuleveut et al. 2021, Supp. B): flat
    # stream padded to full blocks — every leaf quantized at the requested
    # block size (pad entries quantize to 0 and are discarded)
    n = x.size
    pad = (-n) % block
    if native:
        u = _make_dither(_stream_dither(dither), key, (n + pad,))
        flat = x.reshape(-1)
        if pad:
            flat = jnp.pad(flat, (0, pad))
        out = kernel_ref.quantize_groups_native(
            flat.reshape(-1, block), u.reshape(-1, block), bits=bits)
        return out.reshape(-1)[:n].reshape(x.shape)
    flat = x.astype(jnp.float32).reshape(-1)
    if pad:
        flat = jnp.pad(flat, (0, pad))
    if _kernel_eligible(x, block, kernel_threshold):
        if dither == "kernel":
            out = kernel_ops.quantize_dequantize_kernel_dither(
                flat.reshape(-1, block), fold_seed(key), bits=bits,
                group=block).reshape(-1)
        else:
            u = _make_dither(dither, key, (n + pad,))
            out = kernel_ops.quantize_dequantize_with_dither(
                flat, u, bits=bits, block=block)
    else:
        u = _make_dither(_stream_dither(dither), key, (n + pad,))
        out = kernel_ref.quantize_block_ref(flat, u, bits=bits, block=block)
    return out[:n].reshape(x.shape).astype(orig_dtype)


def encode_leaf(key, x, bits: int = 8, block: int = 256,
                dither: str = "uniform", shard_safe: bool = False,
                kernel_threshold: int = KERNEL_DISPATCH_MIN,
                compute: str = "f32", checksum: bool = False):
    """Encode ONE leaf to the wire format (``PackedLeaf``), or pass it
    through raw when ``quantize_leaf`` would (bits == 0 / scalar / empty /
    shard-safe g == 1). Draw-for-draw and dispatch-for-dispatch identical
    to ``quantize_leaf`` — ``decode_leaf(encode_leaf(key, x)) ==
    quantize_leaf(key, x)`` bit-exactly (tests/test_wire_format.py).
    ``checksum=True`` stamps the leaf with its wire digest
    (``leaf_checksum`` over the final packed buffers); ``decode`` ignores
    it, so the roundtrip identity is unchanged."""
    if compute not in ("f32", "native"):
        raise ValueError(f"compute={compute!r} (want 'f32'|'native')")
    if dither not in DITHERS:
        raise ValueError(f"dither={dither!r} (want one of {DITHERS})")
    if bits > 8:
        raise ValueError(f"wire format carries <= 8-bit codes, got {bits}")
    if bits == 0 or x.ndim == 0 or x.size == 0:
        return x
    orig_dtype = x.dtype
    native = compute == "native" and orig_dtype != jnp.float32

    if shard_safe:
        D = x.shape[-1]
        g = group_size(D, block)
        if g < 2:
            return x
        route = None if native else _kernel_route(x, g, kernel_threshold)
        if native:
            u = _make_dither(_stream_dither(dither), key, x.shape)
            xg = x.reshape(x.shape[:-1] + (D // g, g))
            codes, scales = kernel_ref.encode_groups_ref(
                xg, u.reshape(xg.shape), bits=bits)
        elif route == "kernel":
            x2 = _rows_view(x.astype(jnp.float32), g)
            if dither == "kernel":
                c2, s2 = kernel_ops.quantize_encode_kernel_dither(
                    x2, fold_seed(key), bits=bits, group=g)
            else:
                u = _make_dither(dither, key, x.shape)
                c2, s2 = kernel_ops.quantize_encode_grouped(
                    x2, u.reshape(x2.shape), bits=bits, group=g)
            codes = c2.reshape(x.shape[:-1] + (D // g, g))
            scales = s2.reshape(x.shape[:-1] + (D // g, 1))
        elif route == "shard_map":
            # per-shard encode kernels; draws streamed from global indices
            # (see quantize_leaf) — codes/scales stay sharded like x
            u = _make_dither(_stream_dither(dither), key, x.shape)
            c2, s2 = kernel_ops.quantize_encode_sharded(
                x.astype(jnp.float32), u, bits=bits, group=g,
                sharding=x.sharding)
            codes = c2.reshape(x.shape[:-1] + (D // g, g))
            scales = s2.reshape(x.shape[:-1] + (D // g, 1))
        else:
            u = _make_dither(_stream_dither(dither), key, x.shape)
            xg = x.astype(jnp.float32).reshape(x.shape[:-1] + (D // g, g))
            codes, scales = kernel_ref.encode_groups_ref(
                xg, u.reshape(xg.shape), bits=bits)
        wire_codes = _maybe_pack(codes.reshape(x.shape), bits)
        wire_scales = scales.reshape(x.shape[:-1] + (D // g,))
        return PackedLeaf(
            codes=wire_codes, scales=wire_scales,
            shape=tuple(x.shape), dtype=str(orig_dtype), bits=bits,
            group=g, mode="shard",
            check=leaf_checksum(wire_codes, wire_scales) if checksum
            else None)

    n = x.size
    pad = (-n) % block
    if native:
        u = _make_dither(_stream_dither(dither), key, (n + pad,))
        flat = x.reshape(-1)
        if pad:
            flat = jnp.pad(flat, (0, pad))
        codes, scales = kernel_ref.encode_groups_ref(
            flat.reshape(-1, block), u.reshape(-1, block), bits=bits)
    else:
        flat = x.astype(jnp.float32).reshape(-1)
        if pad:
            flat = jnp.pad(flat, (0, pad))
        if _kernel_eligible(x, block, kernel_threshold):
            if dither == "kernel":
                codes, scales = kernel_ops.quantize_encode_kernel_dither(
                    flat.reshape(-1, block), fold_seed(key), bits=bits,
                    group=block)
            else:
                u = _make_dither(dither, key, (n + pad,))
                codes, scales = kernel_ops.quantize_encode_grouped(
                    flat.reshape(-1, block), u.reshape(-1, block), bits=bits,
                    group=block)
        else:
            u = _make_dither(_stream_dither(dither), key, (n + pad,))
            codes, scales = kernel_ref.encode_groups_ref(
                flat.reshape(-1, block), u.reshape(-1, block), bits=bits)
    wire_codes = _maybe_pack(codes.reshape(-1), bits)
    wire_scales = scales.reshape(-1)
    return PackedLeaf(
        codes=wire_codes, scales=wire_scales,
        shape=tuple(x.shape), dtype=str(orig_dtype), bits=bits,
        group=block, mode="flat",
        check=leaf_checksum(wire_codes, wire_scales) if checksum else None)


def decode_leaf(p):
    """Dequantize one wire-format leaf (raw leaves pass through). Accepts
    stacked payloads: any leading axes on codes/scales beyond the recorded
    layout are treated as batch dims (this is what lets the server decode
    an n-client payload stack without a vmap)."""
    if not isinstance(p, PackedLeaf):
        return p
    bits, g, shape = p.bits, p.group, p.shape
    codes = p.codes
    if codes.dtype == jnp.uint8:
        codes = unpack_nibbles(codes)
    if p.mode == "shard":
        batch = codes.shape[:codes.ndim - len(shape)]
        D = shape[-1]
        cg = codes.reshape(batch + shape[:-1] + (D // g, g))
        sg = p.scales.reshape(batch + shape[:-1] + (D // g, 1))
        deq = kernel_ref.decode_groups_ref(cg, sg, bits=bits)
        out = deq.reshape(batch + shape)
    else:
        batch = codes.shape[:-1]
        n = int(math.prod(shape))
        cg = codes.reshape(batch + (-1, g))
        sg = p.scales.reshape(batch + (p.scales.shape[-1], 1))
        deq = kernel_ref.decode_groups_ref(cg, sg, bits=bits)
        out = deq.reshape(batch + (-1,))[..., :n].reshape(batch + shape)
    return out.astype(jnp.dtype(p.dtype))


def _is_payload_leaf(x) -> bool:
    return isinstance(x, PackedLeaf)


def decode_tree(payload):
    """Decode every wire-format leaf of a payload pytree (stacked or not)."""
    return jax.tree.map(decode_leaf, payload, is_leaf=_is_payload_leaf)


def weighted_sum(w, x):
    """``sum_c w[c] * x[c]`` over the leading client axis, accumulated in
    the promoted dtype (f32 under f32 weights) in a FIXED sequential
    order: a loop carry the compiler cannot reassociate. A ``tensordot``
    leaves the order to XLA, which picks it per fusion context — XLA:CPU
    sums a stack fused with the vmapped encode in another order than the
    same stack out of a shard_map all_gather, a 1-ulp split between the
    mesh and single-device trajectories. The sequential order is also the
    Pallas ``decode_reduce`` kernel's (client grid axis innermost)."""
    def body(c, acc):
        return acc + w[c] * x[c]
    return jax.lax.fori_loop(1, x.shape[0], body, w[0] * x[0])


def decode_reduce_leaf(p, w, kernel_threshold: int = KERNEL_DISPATCH_MIN,
                       fused: Optional[bool] = None):
    """Weighted reduction over the leading client axis of ONE stacked
    payload leaf: ``sum_c w[c] * decode(p[c])``, decoding in the same
    pass. Returns the ACCUMULATION dtype (f32 under f32 weights), not the
    leaf dtype — low-precision (bf16) payloads must not round per partial
    when partials are later summed across devices; the caller downcasts
    ONCE after its final reduction (the driver: after the psum).

    ``PackedLeaf`` leaves whose per-client buffer is large enough (>=
    ``kernel_threshold`` elements with a 128-aligned group) dispatch to the
    fused Pallas dequantize+accumulate kernel (``kernels/ops.py:
    dequantize_reduce_grouped``) — the decoded f32 C-client stack never
    materializes; nibble-packed codes unpack to int8 first (1 byte/coord,
    still never the 4-byte f32 stack). Everything else — small/misaligned
    leaves and raw passthrough leaves — decodes via the jnp oracle and
    reduces with ``weighted_sum`` (bit-identical to decode-then-reduce).
    The kernel accumulates sequentially in c, the same order.

    ``fused`` routes the kernel dispatch the same way ``_kernel_route``
    does for apply/encode (the PR-4 lesson: guard per leaf, not by
    convention): ``None`` (default) inspects the codes buffer — eager
    single-device / fully-replicated buffers take the kernel, traced
    leaves on multi-device processes and genuinely partitioned buffers
    keep the conservative jnp path (a pallas_call under GSPMD would force
    a gather of the whole stacked payload). ``True`` asserts the caller
    is already in a per-device (manual / shard_map) context — the
    driver's reduce uplink; ``False`` forces the jnp path."""
    if not isinstance(p, PackedLeaf):
        return weighted_sum(w, p)
    shape, g, bits = p.shape, p.group, p.bits
    n = int(math.prod(shape))
    C = w.shape[0]
    one_batch_axis = (p.codes.ndim - (len(shape) if p.mode == "shard"
                                      else 1)) == 1
    # the kernel route is f32-ONLY: for low-precision leaves, ``decode``
    # rounds every dequantized element to the leaf dtype before any
    # reduction — the gather path's per-element semantics. Accumulating
    # the raw f32 dequant instead would differ by up to a leaf-dtype ulp
    # per element (far beyond the documented f32 reduction-order
    # tolerance), so bf16 payloads keep the decode-then-tensordot path.
    route_ok = (fused is not False and n >= kernel_threshold
                and g % 128 == 0 and g >= 2 and one_batch_axis
                and jnp.dtype(p.dtype) == jnp.float32
                and p.scales.dtype == jnp.float32)
    if route_ok and fused is None:
        if isinstance(p.codes, jax.core.Tracer):
            # sharding unknowable at trace time: only safe on a
            # single-device process (mirrors _kernel_route)
            # repro: allow[RPL001] tracer fallback mirroring _kernel_route
            route_ok = jax.device_count() == 1
        else:
            sh = getattr(p.codes, "sharding", None)
            route_ok = (sh is None or sh.is_fully_replicated
                        or len(sh.device_set) == 1)
    if route_ok:
        codes = p.codes
        if codes.dtype == jnp.uint8:
            codes = unpack_nibbles(codes)
        if p.mode == "shard":
            D = shape[-1]
            c3 = codes.reshape(C, -1, D)
            s3 = p.scales.reshape(C, -1, D // g)
        else:
            # flat stream: group-wide rows, one scale per row (D == g)
            c3 = codes.reshape(C, -1, g)
            s3 = p.scales.reshape(C, -1, 1)
        out = kernel_ops.dequantize_reduce_grouped(c3, s3, w, bits=bits,
                                                   group=g)
        if p.mode == "flat":
            out = out.reshape(-1)[:n]
        return out.reshape(shape)
    return weighted_sum(w, decode_leaf(p))


def decode_reduce_tree(payload, w,
                       kernel_threshold: int = KERNEL_DISPATCH_MIN,
                       fused: Optional[bool] = None):
    """``decode_reduce_leaf`` over a payload pytree: the mu-weighted
    partial aggregate of a stacked C-client payload, fusing dequantize
    into the accumulation leaf-wise (the ``uplink="reduce"`` server
    stage). ``w`` is the (C,) weight vector — fold the participation mask
    in by passing ``mu * mask`` (exact: the mask is 0.0/1.0). Partials
    come back in the accumulation dtype (see ``decode_reduce_leaf``);
    downcast once after the cross-device reduction."""
    return jax.tree.map(
        lambda p: decode_reduce_leaf(p, w, kernel_threshold=kernel_threshold,
                                     fused=fused),
        payload, is_leaf=_is_payload_leaf)


def block_quant(bits: int = 8, block: int = 256, dither: str = "uniform",
                shard_safe: bool = False,
                kernel_threshold: int = KERNEL_DISPATCH_MIN,
                compute: str = "f32", checksum: bool = False) -> Compressor:
    levels = 2.0 ** (bits - 1) - 1.0
    omega = block / (4.0 * levels * levels)

    def apply(key, s):
        return _tree_keyed_map(
            lambda k, x: quantize_leaf(k, x, bits=bits, block=block,
                                       dither=dither, shard_safe=shard_safe,
                                       kernel_threshold=kernel_threshold,
                                       compute=compute),
            key, s)

    def encode(key, s):
        return _tree_keyed_map(
            lambda k, x: encode_leaf(k, x, bits=bits, block=block,
                                     dither=dither, shard_safe=shard_safe,
                                     kernel_threshold=kernel_threshold,
                                     compute=compute, checksum=checksum),
            key, s)

    def decode_reduce(payload, w, fused=None):
        # honors THIS compressor's kernel_threshold (a closure argument,
        # not a Compressor field) — callers that disabled kernel dispatch
        # keep the bit-identical jnp reduce here too
        return decode_reduce_tree(payload, w,
                                  kernel_threshold=kernel_threshold,
                                  fused=fused)

    def payload(shape, itemsize):
        # EXACT wire bytes (mirrors encode_leaf): packed codes (1 byte per
        # coordinate, 0.5 when bits <= 4) + one scale per group (f32 under
        # the oracle semantics, input dtype under compute='native') + the
        # wire digest when checksum is on (billed honestly — integrity is
        # not free bytes); leaves encode() passes through raw (ndim-0
        # always; in shard-safe mode also g == 1 last dims) travel
        # uncompressed at their dtype and carry no digest
        n = float(math.prod(shape)) if shape else 1.0
        if not shape:
            return n * itemsize
        scale_sz = itemsize if compute == "native" and itemsize != 4.0 \
            else 4.0
        ck = float(CHECKSUM_BYTES) if (checksum and bits <= 8) else 0.0
        if not shard_safe:
            n_blocks = math.ceil(n / block)
            padded = n_blocks * block
            code_b = padded / 2.0 if (bits <= PACK_BITS and padded % 2 == 0) \
                else float(padded)
            return code_b + n_blocks * scale_sz + ck
        g = group_size(shape[-1], block)
        if g < 2:
            return n * itemsize
        code_b = n / 2.0 if bits <= PACK_BITS else n
        return code_b + (n / g) * scale_sz + ck

    tag = f"{dither},shard" if shard_safe else dither
    if compute == "native":
        tag += ",native"
    if checksum:
        tag += ",ck"
    return Compressor(apply=apply, omega=float(omega), bits=float(bits),
                      name=f"block_quant{bits}b{block}[{tag}]",
                      payload_fn=payload,
                      encode=encode if bits <= 8 else None,
                      decode=decode_tree if bits <= 8 else None,
                      decode_reduce=decode_reduce if bits <= 8 else None,
                      checksum=checksum and bits <= 8,
                      # the quantizer's tier-boundary reencode IS its
                      # encode: an edge partial is just another f32 tree,
                      # and encode stamps fresh per-tier digests
                      reencode=encode if bits <= 8 else None)


# ---------------------------------------------------------------------------
# Rand-k sparsification (Wangni et al. 2018): keep each coordinate with
# probability k/n, rescale by n/k. omega = n/k - 1.
# ---------------------------------------------------------------------------

def rand_k(fraction: float) -> Compressor:
    assert 0.0 < fraction <= 1.0
    omega = 1.0 / fraction - 1.0

    def leaf(key, x):
        mask = jax.random.bernoulli(key, fraction, x.shape)
        return jnp.where(mask, x / fraction, 0.0).astype(x.dtype)

    def apply(key, s):
        return _tree_keyed_map(leaf, key, s)

    def payload(shape, itemsize):
        # a sparse payload is (value, coordinate) pairs: each surviving
        # coordinate carries its value (itemsize bytes) PLUS its index —
        # ceil(log2 n) bits, clamped to >= 1 (an index field cannot be
        # narrower than a bit: the old model billed 0 index bits for
        # n == 1 leaves and called log2 on n == 0 for empty ones). The
        # pre-PR-3 model billed values only — a free-coordinates fiction
        # that understated e.g. a 1M-coord f32 leaf at fraction 0.1 by
        # ~38%.
        n = float(math.prod(shape)) if shape else 1.0
        if n == 0:
            return 0.0
        idx_bits = max(1, math.ceil(math.log2(n)))
        return n * fraction * (itemsize + idx_bits / 8.0)

    return Compressor(apply=apply, omega=float(omega), bits=32.0 * fraction,
                      name=f"rand_k{fraction:g}", payload_fn=payload)


# ---------------------------------------------------------------------------
# Lemma 1: partial participation composed on top of any compressor.
#   QuantTilde(s) = (U / p) * Quant(s),  U ~ Bernoulli(p)
#   => unbiased with omega_p = omega + (1 - p)(1 + omega)/p.
# ---------------------------------------------------------------------------

def with_participation(base: Compressor, p: float) -> Compressor:
    assert 0.0 < p <= 1.0
    omega_p = effective_omega(base.omega, p)

    def apply(key, s):
        k_u, k_q = jax.random.split(key)
        u = jax.random.bernoulli(k_u, p).astype(jnp.float32)
        q = base.apply(k_q, s)
        return jax.tree.map(lambda x: (u / p) * x, q)

    return Compressor(apply=apply, omega=float(omega_p), bits=base.bits * p,
                      name=f"{base.name}+pp{p:g}",
                      payload_fn=lambda shape, itemsize:
                          p * base._leaf_payload(shape, itemsize))


def effective_omega(omega: float, p: float) -> float:
    """omega_p = omega + (1 + omega)(1 - p)/p  (Lemma 1 / Theorem 1)."""
    return omega + (1.0 + omega) * (1.0 - p) / p
