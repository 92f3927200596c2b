"""GPipe-style pipeline parallelism over the "pod" mesh axis (shard_map).

Layer stacks are reshaped (L, ...) -> (n_stages, L/n_stages, ...) with the
stage dim sharded over ``axis``; microbatches flow stage-to-stage through
``jax.lax.ppermute`` in the classic GPipe schedule (T = M + S - 1 ticks,
bubble fraction (S-1)/T).  Everything runs under one shard_map, so the
whole pipeline is a single SPMD program — pod-to-pod traffic is exactly
one (microbatch x hidden) tensor per tick over the pod-interconnect
links, which is what the multi-pod dry-run's collective-permute entries
account for (see EXPERIMENTS.md §Dry-run).

The default multi-pod configuration treats "pod" as an outer DP axis;
pipeline mode is selected with ``--pipeline`` in the launch drivers.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


from repro.utils.tree import tree_map_with_path


def stack_to_stages(stacked: Any, n_stages: int) -> Any:
    """(L, ...) leaves -> (n_stages, L/n_stages, ...)."""

    def visit(path, leaf):
        L = leaf.shape[0]
        assert L % n_stages == 0, f"{path}: {L} layers not divisible by {n_stages} stages"
        return leaf.reshape((n_stages, L // n_stages) + leaf.shape[1:])

    return tree_map_with_path(visit, stacked)


def pipeline_apply(mesh: Mesh, stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
                   stage_params: Any, xs: jnp.ndarray, axis: str = "pod"
                   ) -> jnp.ndarray:
    """Run the pipeline.

    ``stage_params``: leaves (n_stages, L/S, ...) — sharded over ``axis``.
    ``xs``: (M, mb, ...) microbatch stack (replicated; only stage 0 reads it).
    ``stage_fn(params_one_stage, x) -> y`` applies one stage's layers.
    Returns (M, mb, ...) outputs (replicated).
    """
    n_stages = mesh.shape[axis]
    M = xs.shape[0]
    T = M + n_stages - 1

    def per_stage(params, xs_local):
        params = jax.tree_util.tree_map(lambda p: p[0], params)  # squeeze stage dim
        idx = jax.lax.axis_index(axis)
        # initial carries must be marked pod-varying: they mix with idx-
        # dependent values inside the loop (shard_map vma typing)
        zero = jax.lax.pvary(jnp.zeros_like(xs_local[0]), (axis,))
        outputs = jax.lax.pvary(jnp.zeros_like(xs_local), (axis,))

        def tick(t, state):
            carry, outputs = state
            # stage 0 injects microbatch t; other stages consume the carry
            feed = jax.lax.dynamic_index_in_dim(
                xs_local, jnp.clip(t, 0, M - 1), keepdims=False)
            x_in = jnp.where(idx == 0, feed, carry)
            y = stage_fn(params, x_in)
            # forward the activation one stage down the ring
            carry_next = jax.lax.ppermute(
                y, axis, [(i, i + 1) for i in range(n_stages - 1)])
            # last stage emits microbatch t-(S-1)
            out_t = t - (n_stages - 1)
            upd = jax.lax.dynamic_update_index_in_dim(
                outputs, y, jnp.clip(out_t, 0, M - 1), axis=0)
            outputs = jnp.where((idx == n_stages - 1) & (out_t >= 0), upd, outputs)
            return carry_next, outputs

        _, outputs = jax.lax.fori_loop(0, T, tick, (zero, outputs))
        # broadcast the last stage's outputs to every stage
        mask = (idx == n_stages - 1).astype(outputs.dtype)
        return jax.lax.psum(outputs * mask, axis)

    in_specs = (tree_map_with_path(lambda p, l: P(axis), stage_params), P())
    fn = jax.shard_map(per_stage, mesh=mesh, in_specs=in_specs,
                       out_specs=P(), check_vma=True)  # MESH001: explicit contract
    return fn(stage_params, xs)


def split_microbatches(x: jnp.ndarray, n_micro: int) -> jnp.ndarray:
    B = x.shape[0]
    assert B % n_micro == 0
    return x.reshape((n_micro, B // n_micro) + x.shape[1:])


def merge_microbatches(xs: jnp.ndarray) -> jnp.ndarray:
    return xs.reshape((xs.shape[0] * xs.shape[1],) + xs.shape[2:])
