"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth).

These are the *definitions*; kernels must match them bit-for-bit up to
accumulation order.  They are also the CPU fallback for small problems.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.sparsity import nm_rank


def fista_prox_step(y: jnp.ndarray, G: jnp.ndarray, B: jnp.ndarray,
                    inv_l, thresh) -> jnp.ndarray:
    """shrink(Y - inv_l * (Y @ G - B), thresh)  — paper (5a)+(5b) fused."""
    p = y - inv_l * (y @ G - B)
    return jnp.sign(p) * jnp.maximum(jnp.abs(p) - thresh, 0.0)


def round24(w: jnp.ndarray) -> jnp.ndarray:
    """Keep the 2 largest-|value| entries of every 4-group (row-wise)."""
    rows, cols = w.shape
    g = w.reshape(rows, cols // 4, 4)
    rank = nm_rank(jnp.abs(g), 4)
    return jnp.where(rank < 2, g, 0).reshape(rows, cols)


def pack24(w: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Pack an (exactly-)2:4 matrix into (vals (m, n/2), meta (m, n/4) uint8).

    Per 4-group the two surviving entries are stored in position order in
    ``vals``; ``meta`` packs both within-group positions into one byte
    (``pos0 | pos1 << 2``).  Storage per group: 2 bf16 + 1 uint8 = 5 bytes
    vs 8 bytes dense bf16 => 0.625x.  Groups with fewer than 2 nonzeros
    are padded with zero values (meta picks unused slots), so
    ``pack24(round24(w))`` is always well-formed.
    """
    m, n = w.shape
    g = w.reshape(m, n // 4, 4)
    nz = g != 0
    # order positions: nonzeros first (by position), then zeros (by position)
    pos = jnp.arange(4)[None, None, :]
    key = jnp.where(nz, pos, pos + 4)            # nonzeros sort before zeros
    order = jnp.argsort(key, axis=-1)            # (m, n/4, 4)
    first2 = order[..., :2]                      # positions of the 2 kept
    vals = jnp.take_along_axis(g, first2, axis=-1)           # (m, n/4, 2)
    meta = (first2[..., 0] | (first2[..., 1] << 2)).astype(jnp.uint8)
    return vals.reshape(m, n // 2), meta


def unpack24(vals: jnp.ndarray, meta: jnp.ndarray, n: int) -> jnp.ndarray:
    """Inverse of pack24 -> dense (m, n).

    Scatter-free: per within-group position g the dense column is an
    iota-compare select over the two packed slabs (duplicate meta
    positions sum, matching a scatter-add) — the same rebuild the Pallas
    kernels run in VMEM, and ~10x faster than the old gather-scatter on
    CPU, which matters because ``serve.packed.decode_view`` unpacks
    whole checkpoints through here.
    """
    m = vals.shape[0]
    v0, v1 = vals[:, 0::2], vals[:, 1::2]                    # (m, n/4) each
    mi = meta.astype(jnp.int32)
    i0, i1 = mi & 3, (mi >> 2) & 3
    cols = [v0 * (i0 == g).astype(vals.dtype) + v1 * (i1 == g).astype(vals.dtype)
            for g in range(4)]
    return jnp.stack(cols, axis=-1).reshape(m, n)


def spmm24(x: jnp.ndarray, vals: jnp.ndarray, meta: jnp.ndarray, n: int) -> jnp.ndarray:
    """x (B, n) @ W^T where W (m, n) is 2:4-packed -> (B, m)."""
    w = unpack24(vals, meta, n)
    return x @ w.T


def paged_attention(q, k_pool, v_pool, tables, pos, active, *,
                    block_size: int, window: int = 0, softcap: float = 0.0):
    """Block-table decode attention oracle (kernels/paged_attention.py).

    q (S, nq, hd) post-RoPE queries; pools (T, nkv, hd) or (T, nkv*hd)
    flat block pools with the current token's K/V already written;
    tables (S, MB) int32; pos (S,) absolute positions; active (S,) bool.
    Returns (S, nq, hd).

    Element-for-element the reference gather path: the table row is
    expanded to the same position-order ``gather_idx`` that
    ``transformer.paged_serve_step`` feeds ``mha_decode_paged``, and the
    attention math below repeats that function's exact einsum / cast /
    mask sequence — so on CPU (where ``ops.paged_decode_attn`` routes
    here) the fused decode flag is *bitwise* the reference one.
    """
    import numpy as np
    S, MB = tables.shape
    nq, hd = q.shape[1], q.shape[2]
    nkv = int(np.prod(k_pool.shape[1:])) // hd
    g = nq // nkv
    W = MB * block_size
    j = jnp.arange(W, dtype=jnp.int32)
    blocks = jnp.take_along_axis(tables, jnp.broadcast_to(j // block_size,
                                                          (S, W)), axis=1)
    gather_idx = blocks * block_size + (j % block_size)[None, :]
    kg = jnp.take(k_pool, gather_idx, axis=0).reshape(S, W, nkv, hd)
    vg = jnp.take(v_pool, gather_idx, axis=0).reshape(S, W, nkv, hd)
    idx = jnp.arange(W, dtype=jnp.int32)
    valid = (idx[None, :] <= pos[:, None]) & active[:, None]
    if window:
        valid &= idx[None, :] > pos[:, None] - window
    qg = q.reshape(S, 1, nkv, g, hd)
    scores = jnp.einsum("bqngh,bknh->bngqk", qg, kg).astype(jnp.float32) / np.sqrt(hd)
    if softcap > 0:
        scores = jnp.tanh(scores / softcap) * softcap
    scores = jnp.where(valid[:, None, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bngqk,bknh->bqngh", probs, vg)
    return out.reshape(S, nq, hd)


def fused_mlp24(x, w1_vals, w1_meta, b1, up_vals, up_meta, w2_vals, w2_meta,
                b2, act: str = "silu"):
    """Oracle for the fused packed-2:4 decode MLP: unpack + plain matmuls
    in float32, matching the kernel's accumulation layout."""
    d = x.shape[-1]
    f = w1_vals.shape[0]
    xf = x.astype(jnp.float32)
    h = xf @ unpack24(w1_vals, w1_meta, d).astype(jnp.float32).T
    if b1 is not None:
        h = h + b1.astype(jnp.float32)
    h = jax.nn.gelu(h) if act in ("gelu", "geglu") else jax.nn.silu(h)
    if up_vals is not None:
        h = h * (xf @ unpack24(up_vals, up_meta, d).astype(jnp.float32).T)
    y = h @ unpack24(w2_vals, w2_meta, f).astype(jnp.float32).T
    if b2 is not None:
        y = y + b2.astype(jnp.float32)
    return y.astype(x.dtype)


def flash_attention(q, k, v, causal: bool = True, window: int = 0):
    """Reference attention in (B, H, S, D) layout with GQA head mapping."""
    import numpy as np
    B, Hq, S, D = q.shape
    Hkv = k.shape[1]
    g = Hq // Hkv
    kk = jnp.repeat(k, g, axis=1)
    vv = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   kk.astype(jnp.float32)) / np.sqrt(D)
    rows = jnp.arange(S)[:, None]
    cols = jnp.arange(S)[None, :]
    mask = jnp.ones((S, S), bool)
    if causal:
        mask &= cols <= rows
    if window:
        mask &= (rows - cols) < window
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vv.astype(jnp.float32)).astype(q.dtype)
