"""Flash-decoding Pallas kernels that walk the serving block table.

The continuous batcher's reference decode path (``models/common.
mha_decode_paged``) gathers every slot's context out of the flat KV pool
into position order — a ``(S, W, nkv, hd)`` HBM tensor written and
re-read every step, per layer.  The kernels here never materialize that
gather: the block table rides in as a **scalar-prefetch** operand, so
each grid step's BlockSpec index map reads ``tables[s, j]`` and DMAs the
j-th context block of slot ``s`` straight out of the pool, while the
per-slot length mask, the sliding-window cut and the active-slot mask
fold into the online-softmax accumulator.

Grid layout (``paged_decode_attn``): ``(S, MB)`` — serving slot x table
column, table column innermost so the per-head (m, l, acc) scratch
carries the online-softmax state across one slot's context blocks,
exactly like ``flash_attention.py`` carries it across k-blocks.  Each
step DMAs one pool block with all its kv heads — a (block_size, nkv, hd)
tile, whose last two dims are whole pool dims as the TPU tiling rule
asks — and loops over the kv heads inside the kernel.  GQA is
grid-native: head ``h`` attends with its ``g = nq/nkv`` query heads, so
repeated KV heads are never materialized, and a tensor-parallel mesh
shards ``nkv`` before the call (see ``models/common._paged_attn_sharded``).

``paged_decode_attn_lanes`` is the same walk for head_dim < 128, where a
per-head tile would fill half a lane row or less.  The pool stores a
token's kv heads side by side in one row of ``nkv*hd`` lanes
(``ops.pool_row_shape``), so a pool block is one lane-dense
``(block_size, nkv*hd)`` tile.  Grid ``(S,)``: each slot copies in only
its live blocks, from the window's first to ``pos // block_size``,
``blocks`` at a time by manual double-buffered DMA, and scores all its
query heads at once against a block-diagonal query.

``fused_mlp24`` runs the whole decode MLP (gate/up/down or fc1/fc2) over
the packed-2:4 store (``serve/packed.py``) in ONE pallas_call, grid over
d_ff tiles: every packed operand tile is rebuilt in VMEM with the same
iota-compare trick as ``spmm24`` and the hidden activation never leaves
VMEM.

The jnp oracles live in ``kernels/ref.py``; ``kernels/ops.py`` routes
CPU (and kernel-unfriendly shapes) to them — the oracle math is
element-for-element the reference gather path, which is what keeps the
fused decode flag token-identical (DESIGN.md §11).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.spmm24 import rebuild24_t, rebuild_chunk

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# block-table flash decode attention
# ---------------------------------------------------------------------------
def _attn_kernel(tab_ref, pos_ref, act_ref, q_ref, k_ref, v_ref, out_ref,
                 m_ref, l_ref, acc_ref, *, scale: float, block_size: int,
                 window: int, softcap: float):
    s = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    nkv, g = q_ref.shape[1], q_ref.shape[2]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # absolute token positions covered by table column j; the per-slot
    # length mask (tok <= pos), the sliding-window cut and the
    # active-slot mask all fold into the softmax here — trash-padded
    # table tail columns alias positions > pos and mask out on their own
    tok = j * block_size + jax.lax.broadcasted_iota(
        jnp.int32, (g, block_size), 1)
    p = pos_ref[s]
    valid = (tok <= p) & (act_ref[s] > 0)
    if window > 0:
        valid &= tok > p - window

    for h in range(nkv):
        q = q_ref[0, h].astype(jnp.float32)                # (g, hd)
        k = k_ref[0, :, h, :].astype(jnp.float32)          # (bs, hd)
        v = v_ref[0, :, h, :].astype(jnp.float32)
        sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        if softcap > 0:
            sc = jnp.tanh(sc / softcap) * softcap
        sc = jnp.where(valid, sc, NEG_INF)

        m_prev = m_ref[h]                                  # (g, 1)
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        pr = jnp.exp(sc - m_new)                           # (g, bs)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[h] = l_ref[h] * alpha + jnp.sum(pr, axis=1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
            pr, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[h] = m_new

    @pl.when(j == nj - 1)
    def _finish():
        for h in range(nkv):
            o = acc_ref[h] / jnp.maximum(l_ref[h], 1e-30)  # (g, hd)
            out_ref[0, h] = o.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_size", "window",
                                             "softcap", "interpret"))
def paged_decode_attn(q: jnp.ndarray, k_pool: jnp.ndarray,
                      v_pool: jnp.ndarray, tables: jnp.ndarray,
                      pos: jnp.ndarray, active: jnp.ndarray, *,
                      block_size: int, window: int = 0, softcap: float = 0.0,
                      interpret: bool = False) -> jnp.ndarray:
    """Block-table flash decode: q (S, nq, hd) against the flat pools
    (T, nkv, hd), T = num_blocks * block_size.

    ``tables`` (S, MB) int32 block tables, ``pos`` (S,) per-slot write
    positions, ``active`` (S,) bool.  Returns the attention output
    (S, nq, hd) in q.dtype.
    """
    S, nq, hd = q.shape
    T, nkv, _ = k_pool.shape
    MB = tables.shape[1]
    g = nq // nkv

    q4 = q.reshape(S, nkv, g, hd)
    kb = k_pool.reshape(T // block_size, block_size, nkv, hd)
    vb = v_pool.reshape(T // block_size, block_size, nkv, hd)

    kernel = functools.partial(
        _attn_kernel, scale=1.0 / np.sqrt(hd), block_size=block_size,
        window=int(window or 0), softcap=float(softcap))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, MB),
        in_specs=[
            pl.BlockSpec((1, nkv, g, hd), lambda s, j, tab, p, a: (s, 0, 0, 0)),
            pl.BlockSpec((1, block_size, nkv, hd),
                         lambda s, j, tab, p, a: (tab[s, j], 0, 0, 0)),
            pl.BlockSpec((1, block_size, nkv, hd),
                         lambda s, j, tab, p, a: (tab[s, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, nkv, g, hd),
                               lambda s, j, tab, p, a: (s, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nkv, g, 1), jnp.float32),    # running max
            pltpu.VMEM((nkv, g, 1), jnp.float32),    # running denom
            pltpu.VMEM((nkv, g, hd), jnp.float32),   # output accumulator
        ])
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, nkv, g, hd), q.dtype),
        interpret=interpret,
    )(tables.astype(jnp.int32), pos.astype(jnp.int32),
      active.astype(jnp.int32), q4, kb, vb)
    return out.reshape(S, nq, hd)


# ---------------------------------------------------------------------------
# block-table flash decode attention with lane-dense heads (head_dim < 128)
# ---------------------------------------------------------------------------
def _lanes_kernel(tab_ref, pos_ref, act_ref, q_ref, k_hbm, v_hbm, out_ref,
                  kbuf, vbuf, sem, *, scale: float, block_size: int,
                  blocks: int, cols: int, window: int, softcap: float):
    s = pl.program_id(0)
    nq = q_ref.shape[1]
    tokens = blocks * block_size

    @pl.when(s == 0)
    def _zero():
        # lanes past a slot's last live block keep what an earlier copy
        # left (or this zero): their probabilities are exactly 0, and
        # 0 * finite stays 0
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)

    p = pos_ref[s]
    hi = jnp.minimum(p // block_size, cols - 1)      # last live column
    lo = jnp.maximum(p - window + 1, 0) // block_size if window > 0 else 0
    steps = jnp.where(act_ref[s] > 0, (hi - lo) // blocks + 1, 0)

    def copies(i, slot):
        """(live, K copy, V copy) for each table column of step i."""
        out = []
        for b in range(blocks):
            c = lo + i * blocks + b
            blk = tab_ref[s * cols + jnp.minimum(c, cols - 1)]
            dst = pl.ds(b * block_size, block_size)
            out.append((c <= hi,
                        pltpu.make_async_copy(k_hbm.at[blk], kbuf.at[slot, dst],
                                              sem.at[slot, 0]),
                        pltpu.make_async_copy(v_hbm.at[blk], vbuf.at[slot, dst],
                                              sem.at[slot, 1])))
        return out

    def start(i, slot):
        for live, ck, cv in copies(i, slot):
            @pl.when(live)
            def _():
                ck.start()
                cv.start()

    def wait(i, slot):
        for live, ck, cv in copies(i, slot):
            @pl.when(live)
            def _():
                ck.wait()
                cv.wait()

    @pl.when(steps > 0)
    def _first():
        start(0, 0)

    qb = q_ref[0]                                      # (nq, nkv*hd)
    lane = jax.lax.broadcasted_iota(jnp.int32, (nq, tokens), 1)

    def body(i, carry):
        m_prev, l_prev, acc = carry
        slot = i % 2

        @pl.when(i + 1 < steps)
        def _next():
            start(i + 1, 1 - slot)

        wait(i, slot)
        k = kbuf[slot]                                 # (tokens, nkv*hd)
        v = vbuf[slot]
        # q is block-diagonal over heads, so one product gives every
        # query head's scores against its own kv head's lanes
        sc = jax.lax.dot_general(qb, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        if softcap > 0:
            sc = jnp.tanh(sc / softcap) * softcap
        tok = (lo + i * blocks) * block_size + lane
        valid = tok <= p
        if window > 0:
            valid &= tok > p - window
        sc = jnp.where(valid, sc, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        pr = jnp.exp(sc - m_new)                       # (nq, tokens)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(pr, axis=1, keepdims=True)
        # row j holds head j's output in its kv head's lanes; the other
        # lanes are finite by-products, never stored
        acc = acc * alpha + jax.lax.dot_general(
            pr.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    init = (jnp.full((nq, 1), NEG_INF, jnp.float32),
            jnp.zeros((nq, 1), jnp.float32),
            jnp.zeros(qb.shape, jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, steps, body, init)
    o = acc / jnp.maximum(l, 1e-30)
    hd = out_ref.shape[2]
    g = nq // (qb.shape[1] // hd)
    for h in range(nq // g):                   # each head's own lanes
        out_ref[0, h * g:(h + 1) * g] = \
            o[h * g:(h + 1) * g, h * hd:(h + 1) * hd].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_size", "window",
                                             "softcap", "blocks",
                                             "interpret"))
def paged_decode_attn_lanes(q: jnp.ndarray, k_pool: jnp.ndarray,
                            v_pool: jnp.ndarray, tables: jnp.ndarray,
                            pos: jnp.ndarray, active: jnp.ndarray, *,
                            block_size: int, window: int = 0,
                            softcap: float = 0.0, blocks: int = 8,
                            interpret: bool = False) -> jnp.ndarray:
    """Block-table flash decode for head_dim < 128: same contract as
    :func:`paged_decode_attn`, for pools of (T, nkv*hd) rows
    (``kernels.ops.pool_row_shape``; (T, nkv, hd) is read the same way).

    A pool block is read as one ``(block_size, nkv*hd)`` tile (whole
    lanes, every kv head side by side) by a manual DMA from the pool in
    HBM, ``blocks`` table columns per inner step, and only the columns
    from the window's first to ``pos // block_size``: a slot reads
    nothing past its last live block, and an inactive slot reads
    nothing.  The scores of all query heads come from one product of a
    block-diagonal ``(nq, nkv*hd)`` query with the K tile, accumulated in
    float32; the online softmax runs in float32 per query head, and its
    probabilities meet V in the pool's dtype, as the reference path's do.
    """
    S, nq, hd = q.shape
    T = k_pool.shape[0]
    D = int(np.prod(k_pool.shape[1:]))
    MB = tables.shape[1]
    nkv = D // hd
    g = nq // nkv
    own = (jnp.arange(nq) // g)[:, None] == (jnp.arange(D) // hd)[None, :]
    qb = jnp.where(own, jnp.tile(q, (1, 1, nkv)), 0).astype(k_pool.dtype)
    kb = k_pool.reshape(T // block_size, block_size, D)
    vb = v_pool.reshape(T // block_size, block_size, D)

    kernel = functools.partial(
        _lanes_kernel, scale=1.0 / np.sqrt(hd), block_size=block_size,
        blocks=blocks, cols=MB, window=int(window or 0),
        softcap=float(softcap))
    tile = (2, blocks * block_size, D)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S,),
        in_specs=[
            pl.BlockSpec((1, nq, D), lambda s, tab, p, a: (s, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, nq, hd), lambda s, tab, p, a: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM(tile, k_pool.dtype),
            pltpu.VMEM(tile, v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ])
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, nq, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name="paged_decode_attn",
    )(tables.reshape(-1).astype(jnp.int32), pos.astype(jnp.int32),
      active.astype(jnp.int32), qb, kb, vb)


# ---------------------------------------------------------------------------
# fused packed-2:4 decode MLP: one dispatch for gate/up/down (or fc1/fc2)
# ---------------------------------------------------------------------------
def _packed_matmul(x, vals_ref, meta_ref, w_ref):
    """x (B, 2*half) float32 @ W^T for the packed (rows, half) tile in
    ``vals_ref``/``meta_ref`` -> (B, rows) float32, rebuilt and
    contracted one ``rebuild_chunk`` of rows at a time."""
    rows = vals_ref.shape[0]
    c = rebuild_chunk(rows)
    outs = []
    for r in range(0, rows, c):
        wt = rebuild24_t(vals_ref[r:r + c, :], meta_ref[r:r + c, :], w_ref)
        outs.append(jax.lax.dot_general(
            x, wt, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)


def _mlp_kernel(x_ref, *rest, act: str, gated: bool):
    if gated:
        (w1v_ref, w1m_ref, b1_ref, upv_ref, upm_ref, w2v_ref, w2m_ref,
         b2_ref, out_ref, acc_ref, w1_ref, w2_ref) = rest
    else:
        (w1v_ref, w1m_ref, b1_ref, w2v_ref, w2m_ref, b2_ref, out_ref,
         acc_ref, w1_ref, w2_ref) = rest
    f = pl.program_id(0)
    nf = pl.num_programs(0)

    @pl.when(f == 0)
    def _init():
        acc_ref[...] = jnp.broadcast_to(b2_ref[...], acc_ref.shape
                                        ).astype(jnp.float32)

    x = x_ref[...].astype(jnp.float32)                     # (B, d)
    h = _packed_matmul(x, w1v_ref, w1m_ref, w1_ref)        # (B, bf)
    h = h + b1_ref[...]
    h = jax.nn.gelu(h) if act in ("gelu", "geglu") else jax.nn.silu(h)
    if gated:
        h = h * _packed_matmul(x, upv_ref, upm_ref, w1_ref)
    acc_ref[...] += _packed_matmul(h, w2v_ref, w2m_ref, w2_ref)

    @pl.when(f == nf - 1)
    def _done():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("act", "bf", "interpret"))
def fused_mlp24(x: jnp.ndarray, w1_vals, w1_meta, b1, up_vals, up_meta,
                w2_vals, w2_meta, b2, *, act: str = "silu", bf: int = 512,
                interpret: bool = False) -> jnp.ndarray:
    """Whole decode MLP in one pallas_call over packed-2:4 operands.

    x (B, d).  ``w1`` (gate or fc1) packed (f, d); optional ``up``
    packed (f, d) (pass None for the fc1/fc2 form); ``w2`` (down or fc2)
    packed (d_out, f).  ``b1`` (f,) / ``b2`` (d_out,) may be None.
    Grid over d_ff tiles of ``bf``; the hidden activation tile lives and
    dies in VMEM — HBM traffic is x + packed weights + out.  ``bf``
    defaults to 512 so the quarter-width w2 meta tile (d_out, bf/4)
    stays 128-lane aligned.
    """
    B, d = x.shape
    f = w1_vals.shape[0]
    d_out = w2_vals.shape[0]
    gated = up_vals is not None
    bf_ = min(bf, f)
    bf_ -= bf_ % 4 or 0
    bf_ = max(bf_, 4)
    pf = -f % bf_
    b1v = jnp.zeros((f,), jnp.float32) if b1 is None else b1.astype(jnp.float32)
    b2v = jnp.zeros((d_out,), jnp.float32) if b2 is None else b2.astype(jnp.float32)
    w1v = jnp.pad(w1_vals, ((0, pf), (0, 0)))
    w1m = jnp.pad(w1_meta, ((0, pf), (0, 0)))
    w2v = jnp.pad(w2_vals, ((0, 0), (0, pf // 2)))
    w2m = jnp.pad(w2_meta, ((0, 0), (0, pf // 4)))
    b1p = jnp.pad(b1v, (0, pf)).reshape(1, f + pf)
    F = f + pf

    in_specs = [
        pl.BlockSpec((B, d), lambda i: (0, 0)),                    # x
        pl.BlockSpec((bf_, d // 2), lambda i: (i, 0)),             # w1 vals
        pl.BlockSpec((bf_, d // 4), lambda i: (i, 0)),             # w1 meta
        pl.BlockSpec((1, bf_), lambda i: (0, i)),                  # b1
    ]
    operands = [x, w1v, w1m, b1p]
    if gated:
        upv = jnp.pad(up_vals, ((0, pf), (0, 0)))
        upm = jnp.pad(up_meta, ((0, pf), (0, 0)))
        in_specs += [pl.BlockSpec((bf_, d // 2), lambda i: (i, 0)),
                     pl.BlockSpec((bf_, d // 4), lambda i: (i, 0))]
        operands += [upv, upm]
    in_specs += [
        pl.BlockSpec((d_out, bf_ // 2), lambda i: (0, i)),         # w2 vals
        pl.BlockSpec((d_out, bf_ // 4), lambda i: (0, i)),         # w2 meta
        pl.BlockSpec((1, d_out), lambda i: (0, 0)),                # b2
    ]
    operands += [w2v, w2m, b2v.reshape(1, d_out)]

    out = pl.pallas_call(
        functools.partial(_mlp_kernel, act=act, gated=gated),
        grid=(F // bf_,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((B, d_out), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, d_out), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((B, d_out), jnp.float32),
            pltpu.VMEM((d, rebuild_chunk(bf_)), jnp.float32),     # w1/up
            pltpu.VMEM((bf_, rebuild_chunk(d_out)), jnp.float32),  # w2
        ],
        interpret=interpret,
    )(*operands)
    return out
