"""The wire kernels compile for a described TPU v5e at LM leaf widths.

No chip is needed: the TPU compiler is installed and compiles for a chip
that is described and not attached. Interpret mode accepts blocks and
casts that Mosaic refuses (a 1-lane-wide scales block, a uint32 -> f32
cast); these tests compile the real Mosaic kernels and find them in the
compiled program (``tpu_custom_call``). The topology is described inside a
module-scoped fixture, never at import: only the worker that runs this
file loads the TPU library, and every worker collects the same tests.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import quantize_block as qb

GROUP = 256
C = 4
# whisper-base leaf widths: the (vocab, d_model) embedding and the two
# MLP projections; and a 32768-wide row, whose 32-row tiles compile only
# under a raised scoped-VMEM limit
SHAPES = [(51865, 512), (512, 2048), (2048, 512), (64, 32768)]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _encode_streamed(sds, R, D):
    return (lambda x, u: qb.quantize_encode_grouped_pallas(
        x, u, group=GROUP, interpret=False),
        sds((R, D), jnp.float32), sds((R, D), jnp.float32))


def _encode_kernel_dither(sds, R, D):
    return (lambda x, s: qb.quantize_encode_grouped_pallas(
        x, group=GROUP, seed=s, interpret=False),
        sds((R, D), jnp.float32), sds((), jnp.int32))


def _quantize_dequantize(sds, R, D):
    return (lambda x, u: qb.quantize_grouped_pallas(
        x, u, group=GROUP, interpret=False),
        sds((R, D), jnp.float32), sds((R, D), jnp.float32))


def _decode_reduce(sds, R, D):
    return (lambda c, s, w: qb.decode_reduce_grouped_pallas(
        c, s, w, group=GROUP, interpret=False),
        sds((C, R, D), jnp.int8), sds((C, R, D // GROUP), jnp.float32),
        sds((C,), jnp.float32))


KERNELS = {"encode_streamed": _encode_streamed,
           "encode_kernel_dither": _encode_kernel_dither,
           "quantize_dequantize": _quantize_dequantize,
           "decode_reduce_c4": _decode_reduce}


@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: f"{s[0]}x{s[1]}")
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_grouped_kernel_compiles_for_v5e(kernel, shape, one_chip,
                                         no_persistent_cache):
    def sds(s, dt):
        return jax.ShapeDtypeStruct(s, dt, sharding=one_chip)

    fn, *args = KERNELS[kernel](sds, *shape)
    assert "tpu_custom_call" in _compile_text(fn, *args)


def test_flat_encode_compiles_for_v5e(one_chip, no_persistent_cache):
    """The flat (block-p) layout: one 256-wide group per row."""
    n = 51865 * 512
    R = -(-n // GROUP)
    x = jax.ShapeDtypeStruct((R, GROUP), jnp.float32, sharding=one_chip)
    text = _compile_text(lambda a, u: qb.quantize_encode_grouped_pallas(
        a, u, group=GROUP, interpret=False), x, x)
    assert "tpu_custom_call" in text
