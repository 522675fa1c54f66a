"""RPL004 non-firing: partials cross the mesh in f32; ONE downcast after
the collective (the PR-5 invariant)."""
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec


def partial_reduce(mesh, x):
    def body(xl):
        part = xl.sum(axis=0)
        agg = jax.lax.psum(part, "clients")
        return agg.astype(jnp.bfloat16)

    return jax.shard_map(body, mesh=mesh,
                         in_specs=(PartitionSpec("clients"),),
                         out_specs=PartitionSpec())(x)


def host_cast(x):
    # a downcast with no shard_map body anywhere near it: fine
    return x.astype(jnp.bfloat16)


def partial_reduce_one_line(mesh, x):
    def body(xl):
        # the sanctioned pattern as a single expression: the downcast
        # wraps the psum (reduce first, ONE cast after), so the collective
        # is neither at a later position nor an ancestor of the astype
        return jax.lax.psum(xl.sum(axis=0), "clients").astype(jnp.bfloat16)

    return jax.shard_map(body, mesh=mesh,
                         in_specs=(PartitionSpec("clients"),),
                         out_specs=PartitionSpec())(x)
