"""Gradient compression for DP all-reduce: int8 quantization + error feedback.

At 1000-node scale the DP gradient all-reduce is the dominant collective
for small/medium models; int8 with per-tensor scales cuts its bytes 4x.
Error feedback (Seide et al. / EF-SGD) accumulates the quantization
residual locally and re-injects it next step, which provably preserves
SGD convergence.  The low-bit all-reduce is expressed as
all_gather(int8) + local dequant-sum inside shard_map, so the wire
format really is int8 (psum of int8 would overflow).
"""
from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def quantize(g: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-tensor symmetric int8: returns (q int8, scale fp32)."""
    scale = jnp.max(jnp.abs(g.astype(jnp.float32))) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(g.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale


def dequantize(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return q.astype(jnp.float32) * scale


def ef_compress(g: jnp.ndarray, residual: jnp.ndarray
                ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Error-feedback compression of one leaf.

    Returns (q, scale, new_residual): the residual carries what int8
    couldn't represent into the next step."""
    corrected = g.astype(jnp.float32) + residual
    q, scale = quantize(corrected)
    new_residual = corrected - dequantize(q, scale)
    return q, scale, new_residual


def init_residuals(grads: Any) -> Any:
    return jax.tree_util.tree_map(
        lambda g: jnp.zeros(g.shape, jnp.float32), grads)


def compressed_allreduce(mesh: Mesh, grads: Any, residuals: Any,
                         data_axis: str = "data") -> Tuple[Any, Any]:
    """DP mean of ``grads`` over ``data_axis`` with int8 wire format.

    Inputs are per-shard gradients (each device's local grads, batch
    sharded); output is the dequantized mean, replicated over the axis.
    Residuals are per-device state and stay sharded.
    """
    axis_size = mesh.shape[data_axis]

    def leaf_allreduce(g, r):
        def local(gl, rl):
            q, scale, new_r = ef_compress(gl[0], rl[0])
            # all_gather the int8 payload + scales (the 4x-smaller wire)
            qs = jax.lax.all_gather(q, data_axis)          # (D, ...)
            ss = jax.lax.all_gather(scale, data_axis)      # (D,)
            mean = jnp.tensordot(ss.astype(jnp.float32),
                                 qs.astype(jnp.float32), axes=1) / axis_size
            return mean[None], new_r[None]

        fn = jax.shard_map(local, mesh=mesh,
                           in_specs=(P(data_axis), P(data_axis)),
                           out_specs=(P(data_axis), P(data_axis)),
                           check_vma=True)  # MESH001: explicit contract
        mean, new_r = fn(g, r)
        return mean, new_r

    flat_g, tdef = jax.tree_util.tree_flatten(grads)
    flat_r, _ = jax.tree_util.tree_flatten(residuals)
    means, new_rs = [], []
    for g, r in zip(flat_g, flat_r):
        m, nr = leaf_allreduce(g, r)
        means.append(m)
        new_rs.append(nr)
    return (jax.tree_util.tree_unflatten(tdef, means),
            jax.tree_util.tree_unflatten(tdef, new_rs))
