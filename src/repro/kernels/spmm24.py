"""Pallas packed-2:4 sparse matmul: y = x @ W^T with W stored compressed.

TPU adaptation of the paper's 2:4 motivation (DESIGN.md §2): TPUs have no
sparse MXU, so the win is **HBM bandwidth** in the memory-bound decode
GEMV.  Storage per 4-group: 2 bf16 values + 2 uint8 position ids =
5 bytes vs 8 bytes dense bf16 => 0.625x weight traffic, the roofline
bound for batch-1 decode.

The kernel never gathers: the dense weight tile is rebuilt in VMEM from
the packed slabs with iota-compares (:func:`rebuild24_t`), then hits the
MXU against the activation tile.  Grid (rows/br, m/bm, n/bk) with k
innermost for accumulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: lane width: Mosaic strides sublane loads/stores only on refs this wide
LANES = 128


def rebuild_chunk(rows: int) -> int:
    """Rows of a packed tile that :func:`rebuild24_t` takes per call:
    128 on the chip; any smaller tile is taken whole (interpret mode)."""
    return LANES if rows % LANES == 0 else rows


def rebuild24_t(vals: jnp.ndarray, meta: jnp.ndarray, w_ref) -> jnp.ndarray:
    """Dense W^T (2*half, rows) float32 of a packed (rows, half) tile.

    ``vals`` holds the two kept values of every 4-group side by side and
    ``meta`` their positions (``pos0 | pos1 << 2``).  Taking every other
    lane is no TPU vector op, so the tile is transposed first: the slot
    index then runs along sublanes, where strided loads de-interleave
    the slots and strided stores put each rebuilt column at ``4q + g``.
    Mosaic strides only 32-bit data on a 128-lane ref, hence the float32
    scratch ``w_ref`` of shape (2*half, rows) with ``rows`` = 128 on TPU.
    """
    half = vals.shape[1]
    q = half // 2
    w_ref[:half, :] = vals.astype(jnp.float32).T
    mt = meta.astype(jnp.int32).T                           # (q, rows)
    v0 = w_ref[pl.ds(0, q, stride=2), :]
    v1 = w_ref[pl.ds(1, q, stride=2), :]
    i0, i1 = mt & 3, (mt >> 2) & 3
    for g in range(4):
        w_ref[pl.ds(g, q, stride=4), :] = (
            v0 * (i0 == g).astype(jnp.float32)
            + v1 * (i1 == g).astype(jnp.float32))
    return w_ref[...]


def _kernel(x_ref, vals_ref, meta_ref, out_ref, acc_ref, w_ref):
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    dt = jnp.promote_types(x.dtype, vals_ref.dtype)
    wt = rebuild24_t(vals_ref[...], meta_ref[...], w_ref).astype(dt)
    acc_ref[...] += jax.lax.dot_general(
        x.astype(dt), wt, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _done():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def k_tile(n: int):
    """Input-dim tile: the whole row up to 4096 (the block then equals
    the array dim), else the largest divisor that is a multiple of 512,
    so the quarter-width meta block stays 128-lane aligned; None when
    no such tile exists."""
    if n <= 4096:
        return n
    for bk in range(4096, 511, -512):
        if n % bk == 0:
            return bk
    return None


#: rows of x per grid step (decode slots or prefill rows); fewer are taken whole
ROW_TILE = 256


@functools.partial(jax.jit, static_argnames=("n", "bm", "bk", "interpret"))
def spmm24(x: jnp.ndarray, vals: jnp.ndarray, meta: jnp.ndarray, n: int, *,
           bm: int = LANES, bk: int = None,
           interpret: bool = False) -> jnp.ndarray:
    """x (R, n) times packed-2:4 W^T -> (R, m).

    ``vals`` (m, n/2), ``meta`` (m, n/4) uint8 from ``ref.pack24``.  R is
    a decode batch or a prefill chunk's rows, tiled by ``ROW_TILE``.  Pads to
    tile multiples where the shapes need it (never at the widths
    ``kernels/ops.py`` sends here on TPU); padded vals are 0 and
    contribute nothing.
    """
    R, n_in = x.shape
    assert n_in == n
    m = vals.shape[0]
    bm_ = min(bm, m)
    bk_ = min(bk or k_tile(n) or 512, n)
    bk_ -= bk_ % 8  # keep /2 and /4 slabs whole
    br_ = min(R, ROW_TILE)
    pm, pk, pr = -m % bm_, -n % bk_, -R % br_
    vp = jnp.pad(vals, ((0, pm), (0, pk // 2)))
    mp = jnp.pad(meta, ((0, pm), (0, pk // 4)))
    xp = jnp.pad(x, ((0, pr), (0, pk)))
    M, K, RR = m + pm, n + pk, R + pr

    out = pl.pallas_call(
        _kernel,
        grid=(RR // br_, M // bm_, K // bk_),
        in_specs=[
            pl.BlockSpec((br_, bk_), lambda r, i, k: (r, k)),        # x
            pl.BlockSpec((bm_, bk_ // 2), lambda r, i, k: (i, k)),   # vals
            pl.BlockSpec((bm_, bk_ // 4), lambda r, i, k: (i, k)),   # meta
        ],
        out_specs=pl.BlockSpec((br_, bm_), lambda r, i, k: (r, i)),
        out_shape=jax.ShapeDtypeStruct((RR, M), x.dtype),
        scratch_shapes=[pltpu.VMEM((br_, bm_), jnp.float32),
                        pltpu.VMEM((bk_, bm_), jnp.float32)],
        interpret=interpret,
    )(xp, vp, mp)
    return out[:R, :m]
