"""The weight and federated-state layout (``models.sharding.param_specs``,
``fed.trainer.state_specs`` / ``batch_spec``) at published widths, on a
16 x 16 ('data', 'model') mesh: shapes only, no devices."""
import math

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

import repro.configs as C
from repro.fed import trainer as FT
from repro.models.model import build_model
from repro.models.sharding import param_specs

AXIS_SIZE = {"data": 16, "model": 16}
REPLICATED_MAX_BYTES = 8 << 20


def test_param_specs_2d_sharding():
    params = {
        "embedding": {"embed": jax.ShapeDtypeStruct((51968, 512), jnp.bfloat16)},
        "layer": {"w_in": jax.ShapeDtypeStruct((2, 512, 2048), jnp.bfloat16),
                  "norm": {"scale": jax.ShapeDtypeStruct((512,), jnp.bfloat16)},
                  "moe": {"experts": {"w_out": jax.ShapeDtypeStruct(
                      (2, 128, 2048, 512), jnp.bfloat16)}}},
    }
    specs = param_specs(params, fsdp=("data",), fsdp_size=16,
                        tp="model", tp_size=16)
    assert specs["embedding"]["embed"] == P("model", ("data",))
    assert specs["layer"]["w_in"] == P(None, ("data",), "model")
    assert specs["layer"]["norm"]["scale"] == P(None)
    # scan-stacked expert leaf: expert dim (index 1) over tp
    assert specs["layer"]["moe"]["experts"]["w_out"] == \
        P(None, "model", ("data",), None)


def _axes(entry):
    if entry is None:
        return ()
    return (entry,) if isinstance(entry, str) else tuple(entry)


def _check_layout(shapes, specs):
    leaves, tree = jax.tree.flatten(shapes)
    spec_leaves = tree.flatten_up_to(specs)
    for leaf, spec in zip(leaves, spec_leaves):
        assert isinstance(spec, P)
        assert len(spec) == leaf.ndim, (leaf.shape, spec)
        named = [a for e in spec for a in _axes(e)]
        assert set(named) <= set(AXIS_SIZE), spec
        assert len(named) == len(set(named)), spec
        for dim, entry in zip(leaf.shape, spec):
            size = math.prod(AXIS_SIZE[a] for a in _axes(entry))
            assert dim % size == 0, (leaf.shape, spec)
        if not named:
            nbytes = math.prod(leaf.shape) * jnp.dtype(leaf.dtype).itemsize
            assert nbytes <= REPLICATED_MAX_BYTES, (leaf.shape, spec)


@pytest.mark.parametrize("arch", C.ARCH_IDS + ["moonlight-16b-a3b"])
def test_state_specs_at_published_widths(arch):
    """Both client modes lay every leaf of (s_hat, v, v_i) over the mesh
    with divisible dims, distinct axes and no large replicated leaf; the
    client dim of v_i is the data axis in physical mode and local in
    logical mode."""
    model = build_model(C.get(arch))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    n = 16
    vi_shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((n,) + x.shape, x.dtype), shapes)
    for mode, lead in (("physical", ("data",)), ("logical", ())):
        cfg = FT.FedLMConfig(n_clients=n, client_mode=mode)
        s_spec, v_spec, vi_spec = FT.state_specs(shapes, cfg,
                                                 fsdp=("data",))
        _check_layout(shapes, s_spec)
        _check_layout(shapes, v_spec)
        _check_layout(vi_shapes, vi_spec)
        for spec in jax.tree.leaves(vi_spec,
                                    is_leaf=lambda x: isinstance(x, P)):
            assert _axes(spec[0]) == lead, (mode, spec)


@pytest.mark.parametrize("mode,spec", [
    ("physical", P(("data",), None, None)),
    ("logical", P(None, ("data",), None)),
])
def test_batch_spec(mode, spec):
    """Tokens (n, B_local, S): the client dim goes over the client axes in
    physical mode, the local-batch dim in logical mode."""
    cfg = FT.FedLMConfig(n_clients=16, client_mode=mode)
    assert FT.batch_spec(cfg, ("data",)) == spec
