"""MESH001 true-positive: shard_map without explicit check_vma (parsed
only, never imported)."""
import jax
from jax.sharding import PartitionSpec as P


def build(mesh, local):
    return jax.shard_map(local, mesh=mesh, in_specs=(P("x"),),
                         out_specs=P("x"))   # MESH001: implicit check_vma
