"""MESH001 true-negative: the replication contract is explicit."""
import jax
from jax.sharding import PartitionSpec as P


def build(mesh, local):
    return jax.shard_map(local, mesh=mesh, in_specs=(P("x"),),
                         out_specs=P("x"), check_vma=False)
