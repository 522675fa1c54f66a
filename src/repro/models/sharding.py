"""Weight layout: a PartitionSpec for every parameter leaf, by leaf name.

``param_specs`` assigns a 2-D FSDP x TP layout to any model's parameter
pytree (array or ShapeDtypeStruct leaves). ``fed.trainer.state_specs``
builds the federated state's layout from it.
"""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

# ---------------------------------------------------------------------------
# Parameter layout: 2-D FSDP x TP weight sharding.
#
#   expert stacks (E, a, b)  -> (tp on E, fsdp on a, None)   expert parallel
#   embed/lm_head (V, d)     -> (tp, fsdp)                   vocab + FSDP
#   any other >=2-D weight   -> (..., fsdp on dim[-2], tp on dim[-1])
#   1-D / norms / biases     -> replicated
#
# ``fsdp`` names the client/data mesh axes (e.g. ('data',)): parameters are
# fully sharded for storage and all-gathered at use (ZeRO-3 semantics under
# GSPMD); ``tp`` = 'model'. A dim is sharded only when the axis size
# divides it; otherwise that dim stays replicated.
# ---------------------------------------------------------------------------

def _spec_for(path: str, shape, fsdp, fsdp_size: int, tp,
              tp_size: int) -> P:
    nd = len(shape)

    def div(dim, size):
        return shape[dim] % size == 0 and shape[dim] >= size

    base = [None] * nd
    name = path.split("/")[-1]
    if nd < 2:
        return P(*base)
    if "expert" in path and nd >= 3:
        # (E, a, b) or scan-stacked (n_blocks, E, a, b)
        e_dim = nd - 3
        if div(e_dim, tp_size):
            base[e_dim] = tp                   # expert parallelism
        if div(nd - 2, fsdp_size):
            base[nd - 2] = fsdp
        return P(*base)
    if name in ("embed", "lm_head"):
        if div(nd - 2, tp_size):
            base[nd - 2] = tp
        if div(nd - 1, fsdp_size):
            base[nd - 1] = fsdp
        return P(*base)
    if div(nd - 2, fsdp_size):
        base[nd - 2] = fsdp
    if div(nd - 1, tp_size):
        base[nd - 1] = tp
    return P(*base)


def param_specs(params, fsdp=("data",), fsdp_size: int = 16,
                tp: str = "model", tp_size: int = 16):
    """Build a PartitionSpec pytree matching ``params`` (array or
    ShapeDtypeStruct leaves) using the layout conventions above."""
    fsdp = tuple(fsdp) if not isinstance(fsdp, str) else fsdp
    flat = jax.tree_util.tree_flatten_with_path(params)
    specs = []
    for path, leaf in flat[0]:
        pstr = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        specs.append(_spec_for(pstr, leaf.shape, fsdp, fsdp_size, tp, tp_size))
    return jax.tree_util.tree_unflatten(flat[1], specs)
