"""RPL005 non-firing: collectives inside shard_map / pmap bodies."""
import jax
from jax.sharding import PartitionSpec


def aggregate(mesh, x):
    def body(xl):
        return jax.lax.psum(xl, "clients")

    return jax.shard_map(body, mesh=mesh,
                         in_specs=(PartitionSpec("clients"),),
                         out_specs=PartitionSpec())(x)


def mean_over_devices(x):
    def body(xl):
        return jax.lax.pmean(xl, "devices")

    return jax.pmap(body, axis_name="devices")(x)


def two_tier_aggregate(mesh, x):
    """Edge-scoped collectives on a 2-D (edge, client) mesh: psum over
    the client axis stays within the edge group, the tuple-axis psum
    crosses both tiers — all inside the shard_map's axis binding."""
    def body(xl):
        part = jax.lax.psum(xl, "client")          # within-edge reduce
        total = jax.lax.psum(part, ("edge", "client"))  # both tiers
        return total

    return jax.shard_map(body, mesh=mesh,
                         in_specs=(PartitionSpec(("edge", "client")),),
                         out_specs=PartitionSpec())(x)
