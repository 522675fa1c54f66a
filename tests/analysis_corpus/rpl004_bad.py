"""RPL004 firing: downcast inside a shard_map body BEFORE the psum."""
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec


def partial_reduce(mesh, x):
    def body(xl):
        part = xl.sum(axis=0).astype(jnp.bfloat16)  # expect: RPL004
        return jax.lax.psum(part, "clients")

    return jax.shard_map(body, mesh=mesh,
                         in_specs=(PartitionSpec("clients"),),
                         out_specs=PartitionSpec())(x)


def partial_reduce_same_line(mesh, x):
    def body(xl):
        # the downcast nested directly in the collective's operand — the
        # most direct form of the PR-5 bug, on ONE line
        return jax.lax.psum(xl.sum(0).astype(jnp.bfloat16), "clients")  # expect: RPL004

    return jax.shard_map(body, mesh=mesh,
                         in_specs=(PartitionSpec("clients"),),
                         out_specs=PartitionSpec())(x)
