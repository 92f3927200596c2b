"""Row-parallel FISTA (shard_map) + distributed Gram accumulation.

The LASSO (paper Eq. 4) is row-separable: row i of W* solves an
independent problem over the SAME Gram matrix G.  So the inner FISTA
loop shards the m rows of (Y, B) over the "model" axis with G
replicated — **zero collectives per iteration** (DESIGN.md §2).  The
only communication in the whole pruning pipeline is one psum per
operator when the Gram statistics are accumulated from data-sharded
calibration activations.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import fista as fista_lib
from repro.core import gram as gram_lib
from repro.core.gram import GramStats


def sharded_solve(mesh: Mesh, G: jnp.ndarray, B: jnp.ndarray, y0: jnp.ndarray,
                  lam, L, max_iters: int = 20, tol: float = fista_lib.DEFAULT_TOL,
                  axis: str = "model", momentum: str = "fista",
                  step_impl: str = "jnp") -> jnp.ndarray:
    """FISTA with rows of B/y0 sharded over ``axis``; G replicated.

    The row count m must divide the axis size x ... (padding handled by
    the caller; operators here always have 128-multiple rows at scale).
    Stopping uses the local shard's delta — safe because the math of each
    shard is independent; max_iters bounds the divergence between shards
    (they run the same number of iterations under jit anyway since the
    while_loop is per-shard).
    """
    lam = jnp.float32(lam)
    L = jnp.float32(L)

    def local(g, b, y):
        out, _ = fista_lib.solve(g, b, y, lam, L=L, max_iters=max_iters,
                                 tol=tol, momentum=momentum,
                                 step_impl=step_impl)
        return out

    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P(None, None), P(axis, None), P(axis, None)),
                       out_specs=P(axis, None),
                       check_vma=False)  # no replication rule for while_loop
    return fn(G, B.astype(jnp.float32), y0.astype(jnp.float32))


def sharded_accumulate(mesh: Mesh, stats: GramStats, x_dense: jnp.ndarray,
                       x_pruned: jnp.ndarray, wx_dense: jnp.ndarray,
                       data_axis: str = "data") -> GramStats:
    """Gram accumulation with the token batch sharded over ``data_axis``:
    each shard computes its local outer products, then ONE psum merges.
    (This is the only collective of the pruning pipeline.)"""

    def local(G, C, H, h, cnt, xd, xp, wx):
        xd = xd.reshape(-1, xd.shape[-1]).astype(jnp.float32)
        xp = xp.reshape(-1, xp.shape[-1]).astype(jnp.float32)
        wx = wx.reshape(-1, wx.shape[-1]).astype(jnp.float32)
        dG = jax.lax.psum(xp.T @ xp, data_axis)
        dC = jax.lax.psum(xd.T @ xp, data_axis)
        dH = jax.lax.psum(xd.T @ xd, data_axis)
        dh = jax.lax.psum(jnp.sum(wx * wx), data_axis)
        dn = jax.lax.psum(jnp.float32(xd.shape[0]), data_axis)
        return G + dG, C + dC, H + dH, h + dh, cnt + dn

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(None, None), P(None, None), P(None, None), P(), P(),
                  P(data_axis), P(data_axis), P(data_axis)),
        out_specs=(P(None, None), P(None, None), P(None, None), P(), P()),
        check_vma=True)  # MESH001: explicit contract
    G, C, H, h, cnt = fn(stats.G, stats.C, stats.H, stats.h, stats.count,
                         x_dense, x_pruned, wx_dense)
    return GramStats(G=G, C=C, H=H, h=h, count=cnt)
