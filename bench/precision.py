"""Matrix products for the references, at a stated precision.

The references compute in float32 with every product at full precision
(``highest``). A correctness control puts the same reference in the
program's place one precision step lower, and must then fail the
comparison. The lower precisions are emulated explicitly on float32
arrays, so a control reads the same on the CPU as on the chip (where
``default_matmul_precision`` would otherwise be a no-op on the CPU):

* ``high``  — three bf16 passes (bf16_3x): each operand split into a
  bf16 head and a bf16 tail, ``hi*hi + hi*lo + lo*hi`` accumulated in f32;
* ``fp8``   — float8 e4m3 operands with one scale per tensor (the format
  of fp8 training), in the forward and in both backward products.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def _dot(a, b):
    return jnp.matmul(a, b, precision=HI, preferred_element_type=jnp.float32)


def mm_highest(a, b):
    return _dot(a.astype(jnp.float32), b.astype(jnp.float32))


def _split_bf16(x):
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (x - hi).astype(jnp.bfloat16).astype(jnp.float32)
    return hi, lo


def mm_high(a, b):
    ah, al = _split_bf16(a.astype(jnp.float32))
    bh, bl = _split_bf16(b.astype(jnp.float32))
    return _dot(ah, bh) + (_dot(ah, bl) + _dot(al, bh))


F8_MAX = 448.0


def _fp8(x):
    x = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    s = jax.lax.stop_gradient(s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@jax.custom_vjp
def mm_fp8(a, b):
    return _dot(_fp8(a), _fp8(b))


def _fp8_fwd(a, b):
    return mm_fp8(a, b), (a, b)


def _fp8_bwd(res, g):
    a, b = res
    ga = _dot(_fp8(g), _fp8(jnp.swapaxes(b, -1, -2)))
    gb = _dot(_fp8(jnp.swapaxes(a, -1, -2)), _fp8(g))
    # broadcast batch dims of an operand back to its own shape
    while ga.ndim > a.ndim:
        ga = ga.sum(0)
    while gb.ndim > b.ndim:
        gb = gb.sum(0)
    return ga.astype(a.dtype), gb.astype(b.dtype)


mm_fp8.defvjp(_fp8_fwd, _fp8_bwd)

MATMULS = {"highest": mm_highest, "high": mm_high, "fp8": mm_fp8}
