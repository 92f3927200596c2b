"""Public jit'd wrappers around the Pallas kernels.

On a TPU backend these call sites compile to Mosaic.  On CPU (this
container) the *offline* kernels (fista_prox_step, round24, flash
prefill) still run in ``interpret=True`` mode for correctness coverage,
but the **decode hot loop** (spmm24, paged_decode_attn, fused_mlp24)
routes to the jnp oracles in ``ref.py`` instead: interpret-mode Pallas
inside a jitted per-token step is ~10x slower than the oracle (the
measured packed-slower-than-dense serve regression), and the
interpret-mode coverage lives in the dedicated ``kernels_interpret``
test marker rather than the serving path.  Small problems always fall
back to the oracle, where kernel launch overhead would dominate.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels import fista_step as _fista_step
from repro.kernels import paged_attention as _paged
from repro.kernels import ref
from repro.kernels import round24 as _round24
from repro.kernels import spmm24 as _spmm24


@functools.cache
def _interpret() -> bool:
    return jax.default_backend() != "tpu"


_MIN_PALLAS_DIM = 128  # below this, use the jnp oracle
_FUSED_MLP_BF = 512    # d_ff tile: keeps the (d, bf/4) meta block lane-aligned
# context tokens per inner step of the lane-dense decode: on a TPU v5e,
# 128 and 256 tie at the batch cell's shapes, 64 and 512 are slower
_DECODE_LANE_TOKENS = 128


def fista_prox_step(y: jnp.ndarray, G: jnp.ndarray, B: jnp.ndarray,
                    inv_l, thresh) -> jnp.ndarray:
    m, n = y.shape
    if min(m, n) < _MIN_PALLAS_DIM:
        return ref.fista_prox_step(y, G, B, inv_l, thresh)
    return _fista_step.fista_prox_step(y, G, B, inv_l, thresh,
                                       interpret=_interpret())


def round24(w: jnp.ndarray) -> jnp.ndarray:
    m, n = w.shape
    if m < 8 or n < 32:
        return ref.round24(w)
    return _round24.round24(w, interpret=_interpret())


def use_spmm24(m: int, n: int) -> bool:
    """True when ``spmm24`` compiles for a packed (m, n) weight without
    padding it: TPU backend, whole 128-row rebuild chunks, and an input
    tile that keeps the meta block lane-aligned (``spmm24.k_tile``)."""
    return (not _interpret()) and m % _spmm24.LANES == 0 \
        and n >= 2 * _MIN_PALLAS_DIM and _spmm24.k_tile(n) is not None


def spmm24(x: jnp.ndarray, vals: jnp.ndarray, meta: jnp.ndarray, n: int) -> jnp.ndarray:
    if not use_spmm24(vals.shape[0], n):
        return ref.spmm24(x, vals, meta, n)
    return _spmm24.spmm24(x, vals, meta, n, interpret=False)


pack24 = ref.pack24
unpack24 = ref.unpack24


# ---------------------------------------------------------------------------
# fused decode fast path (kernels/paged_attention.py)
# ---------------------------------------------------------------------------
def pool_row_shape(num_kv_heads: int, head_dim: int) -> tuple:
    """Shape of one token's K (or V) row in the paged KV pool.

    Heads narrower than a lane row sit side by side in one row of
    ``nkv*hd``: kept as ``(nkv, hd)``, the TPU would pad every head to
    128 lanes or lay the whole pool out token-minor, and neither can be
    read one block at a time (``paged_attention.paged_decode_attn_lanes``
    reads a block as one ``(block_size, nkv*hd)`` tile).  Heads of 128
    lanes or more keep ``(nkv, hd)``.  Shape only, on every backend, so
    CPU runs the layout the chip runs.
    """
    if head_dim < _MIN_PALLAS_DIM:
        return (num_kv_heads * head_dim,)
    return (num_kv_heads, head_dim)


def use_decode_kernel(head_dim: int, block_size: int) -> bool:
    """True when the block-table decode kernel for head_dim >= 128
    (``paged_attention.paged_decode_attn``) compiles for these shapes:
    TPU backend, lane-width head_dim, sublane-aligned block_size.  When
    neither this nor :func:`use_decode_lanes` holds, the fused decode
    path runs the ``ref.py`` oracle — which on CPU is exactly the
    reference gather math, keeping fused == reference bitwise
    (DESIGN.md §11 fallback rules)."""
    return (not _interpret()) and head_dim >= _MIN_PALLAS_DIM \
        and block_size % 8 == 0


def use_decode_lanes(head_dim: int, num_kv_heads: int,
                     block_size: int) -> bool:
    """True when the lane-dense decode kernel
    (``paged_attention.paged_decode_attn_lanes``) takes these shapes:
    TPU backend, head_dim below the lane width, the kv heads of one
    token filling whole 128-lane rows side by side, and a
    sublane-aligned block_size."""
    return (not _interpret()) and head_dim < _MIN_PALLAS_DIM \
        and (num_kv_heads * head_dim) % _MIN_PALLAS_DIM == 0 \
        and block_size % 8 == 0


def paged_decode_attn(q, k_pool, v_pool, tables, pos, active, *,
                      block_size: int, window: int = 0, softcap: float = 0.0):
    """Block-table flash decode -> (S, nq, hd) in q.dtype.

    A kernel on TPU-compilable shapes (head_dim >= 128, or the
    lane-dense one below it), ``ref.paged_attention`` otherwise.
    """
    hd = q.shape[-1]
    if use_decode_kernel(hd, block_size):
        return _paged.paged_decode_attn(q, k_pool, v_pool, tables, pos,
                                        active, block_size=block_size,
                                        window=window, softcap=softcap,
                                        interpret=False)
    if use_decode_lanes(hd, math.prod(k_pool.shape[1:]) // hd, block_size):
        return _paged.paged_decode_attn_lanes(
            q, k_pool, v_pool, tables, pos, active, block_size=block_size,
            window=window, softcap=softcap,
            blocks=max(1, _DECODE_LANE_TOKENS // block_size),
            interpret=False)
    return ref.paged_attention(q, k_pool, v_pool, tables, pos, active,
                               block_size=block_size, window=window,
                               softcap=softcap)


def use_fused_mlp(d_model: int, d_ff: int) -> bool:
    """True when ``fused_mlp24`` compiles for these dims without padding:
    TPU, whole 128-row rebuild chunks of the down projection, and whole
    d_ff tiles; same fallback contract as ``use_decode_kernel``."""
    return (not _interpret()) and d_model % _spmm24.LANES == 0 \
        and d_ff % _FUSED_MLP_BF == 0


def fused_mlp24(x, w1_vals, w1_meta, b1, up_vals, up_meta, w2_vals, w2_meta,
                b2, *, act: str = "silu"):
    """Whole decode MLP over packed-2:4 operands in one dispatch; oracle
    on CPU / small shapes (same fallback contract as above)."""
    d = w1_vals.shape[1] * 2
    f = w1_vals.shape[0]
    if not use_fused_mlp(d, f):
        return ref.fused_mlp24(x, w1_vals, w1_meta, b1, up_vals, up_meta,
                               w2_vals, w2_meta, b2, act=act)
    return _paged.fused_mlp24(x, w1_vals, w1_meta, b1, up_vals, up_meta,
                              w2_vals, w2_meta, b2, act=act,
                              bf=_FUSED_MLP_BF, interpret=False)


# ---------------------------------------------------------------------------
# flash attention: Pallas forward + analytic XLA backward (custom_vjp)
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_mha(q, k, v, causal: bool = True, window: int = 0):
    """Flash attention, (B, Hq, S, D) x (B, Hkv, S, D) -> (B, Hq, S, D).

    Forward streams K/V through VMEM (HBM traffic = Q+K+V+O, no S^2
    tensors).  Backward uses the standard analytic attention gradient in
    plain XLA — scores materialize ONCE in bwd instead of 3x
    (fwd + bwd + remat-recompute) with the unfused reference.
    """
    return _flash_fwd_impl(q, k, v, causal, window)


def _flash_fwd_impl(q, k, v, causal, window):
    from repro.kernels import flash_attention as fa
    S = q.shape[2]
    if S < 128:
        return ref.flash_attention(q, k, v, causal, window)
    bq = bk = min(512, S)
    return fa.flash_attention(q, k, v, causal=causal, window=int(window or 0),
                              bq=bq, bk=bk, interpret=_interpret())


def _flash_fwd(q, k, v, causal, window):
    return _flash_fwd_impl(q, k, v, causal, window), (q, k, v)


def _flash_bwd(causal, window, res, do):
    import numpy as np
    q, k, v = res
    B, Hq, S, D = q.shape
    Hkv = k.shape[1]
    g = Hq // Hkv
    qf = q.astype(jnp.float32)
    kf = jnp.repeat(k, g, axis=1).astype(jnp.float32)
    vf = jnp.repeat(v, g, axis=1).astype(jnp.float32)
    dof = do.astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) / np.sqrt(D)
    rows = jnp.arange(S)[:, None]
    cols = jnp.arange(S)[None, :]
    mask = jnp.ones((S, S), bool)
    if causal:
        mask &= cols <= rows
    if window:
        mask &= (rows - cols) < window
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, dof)
    dp = jnp.einsum("bhqd,bhkd->bhqk", dof, vf)
    ds = p * (dp - jnp.sum(dp * p, axis=-1, keepdims=True)) / np.sqrt(D)
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds, kf)
    dk = jnp.einsum("bhqk,bhqd->bhkd", ds, qf)
    # fold repeated-KV-head grads back onto the Hkv heads
    dk = dk.reshape(B, Hkv, g, S, D).sum(axis=2)
    dv = dv.reshape(B, Hkv, g, S, D).sum(axis=2)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


flash_mha.defvjp(_flash_fwd, _flash_bwd)
