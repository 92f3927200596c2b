"""Shared model layers: norms, rotary, GQA/SWA attention, MLPs, KV caches.

Conventions
-----------
* Params are nested dicts of jnp arrays.  Linear weights are stored
  ``(in_dim, out_dim)`` ("model layout"); the pruner transposes to the
  paper's ``(out, in)`` layout at its boundary.
* Every linear goes through :func:`dense` which optionally *captures* its
  input activation into a dict — this is how the calibration pipeline
  records X / X* for FISTAPruner without touching model code.
* Attention never materializes repeated KV heads: GQA is computed with a
  grouped einsum, which also gives GSPMD a clean head axis to shard.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig

Params = Dict[str, Any]
Captures = Optional[Dict[str, jnp.ndarray]]


def dtype_of(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32, "float16": jnp.float16}[name]


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------
def dense_init(key, d_in: int, d_out: int, dtype) -> jnp.ndarray:
    scale = 1.0 / np.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype) -> jnp.ndarray:
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# captured linear
# ---------------------------------------------------------------------------
def dense(x: jnp.ndarray, w, name: str = "", cap: Captures = None,
          bias: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """``x @ w`` with optional activation capture (input of this operator).

    ``w`` is either a dense (in, out) array or a packed-2:4 dict
    ``{"vals": (out, in/2), "meta": (out, in/4) uint8}`` produced by
    ``repro.serve.packed.pack_tree`` — the memory-bound decode path then
    runs through the spmm24 Pallas kernel with 0.625x weight traffic.
    """
    if cap is not None and name:
        cap[name] = x
    if isinstance(w, dict) and "vals" in w:
        from repro.kernels import ops as kops
        n = w["vals"].shape[-1] * 2
        lead = x.shape[:-1]
        y = kops.spmm24(x.reshape(-1, n), w["vals"], w["meta"], n)
        y = y.reshape(lead + (y.shape[-1],)).astype(x.dtype)
    else:
        y = jnp.einsum("...i,io->...o", x, w)
    if bias is not None:
        y = y + bias
    return y


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------
def rmsnorm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def layernorm(x: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def norm_apply(cfg: ModelConfig, p: Params, x: jnp.ndarray) -> jnp.ndarray:
    if cfg.norm == "layernorm":
        return layernorm(x, p["scale"], p["bias"])
    return rmsnorm(x, p["scale"])


def norm_init(cfg: ModelConfig, d: int) -> Params:
    dt = dtype_of(cfg.param_dtype)
    if cfg.norm == "layernorm":
        return {"scale": jnp.ones((d,), dt), "bias": jnp.zeros((d,), dt)}
    return {"scale": jnp.ones((d,), dt)}


# ---------------------------------------------------------------------------
# rotary embeddings (partial rotary + configurable theta)
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, partial: float, theta: float) -> jnp.ndarray:
    rot = int(head_dim * partial)
    rot -= rot % 2
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    return inv  # (rot/2,)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, inv_freq: jnp.ndarray) -> jnp.ndarray:
    """x: (..., S, H, hd) rotate first 2*len(inv_freq) dims; positions: (..., S)."""
    rot = 2 * inv_freq.shape[0]
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    ang = positions[..., :, None].astype(jnp.float32) * inv_freq[None, :]  # (..., S, rot/2)
    cos = jnp.cos(ang)[..., :, None, :]  # broadcast over heads
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = x_rot[..., 0::2], x_rot[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    y = jnp.stack([y1, y2], axis=-1).reshape(x_rot.shape)
    return jnp.concatenate([y.astype(x.dtype), x_pass], axis=-1) if x_pass.shape[-1] else y.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------
def attn_init(cfg: ModelConfig, key) -> Params:
    dt = dtype_of(cfg.param_dtype)
    d, hd = cfg.d_model, cfg.resolved_head_dim()
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    ks = jax.random.split(key, 4)
    p: Params = {
        "wq": dense_init(ks[0], d, nq * hd, dt),
        "wk": dense_init(ks[1], d, nkv * hd, dt),
        "wv": dense_init(ks[2], d, nkv * hd, dt),
        "wo": dense_init(ks[3], nq * hd, d, dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((nq * hd,), dt)
        p["bk"] = jnp.zeros((nkv * hd,), dt)
        p["bv"] = jnp.zeros((nkv * hd,), dt)
    return p


def _split_heads(x: jnp.ndarray, n: int, hd: int) -> jnp.ndarray:
    return x.reshape(x.shape[:-1] + (n, hd))


def _pool_rows(x: jnp.ndarray, pool: jnp.ndarray) -> jnp.ndarray:
    """(..., nkv, hd) K/V rows in the paged pool's row shape and dtype:
    the pool keeps ``(nkv, hd)`` or, below the lane width, ``(nkv*hd,)``
    (``kernels.ops.pool_row_shape``)."""
    return x.reshape(x.shape[:-2] + pool.shape[1:]).astype(pool.dtype)


def _causal_window_mask(q_pos: jnp.ndarray, k_pos: jnp.ndarray, window: Optional[int],
                        causal: bool = True) -> jnp.ndarray:
    """(..., Sq, Sk) boolean mask. window w => attend to (i-w, i]."""
    diff = q_pos[..., :, None] - k_pos[..., None, :]
    mask = jnp.ones(diff.shape, bool)
    if causal:
        mask &= diff >= 0
    if window is not None:
        mask &= diff < window
    return mask


def decode_window_mask(idx: jnp.ndarray, pos: jnp.ndarray,
                       window: Optional[int]) -> jnp.ndarray:
    """Decode-step length + sliding-window validity over cache slots.

    ``idx`` are slot indices in absolute-position order, ``pos`` the
    decoding position(s) (broadcast against idx): a slot is attendable
    iff it's filled (``idx <= pos``) and, when windowed, within the
    trailing window ``(pos - window, pos]``.  Shared by the contiguous
    (:func:`mha_decode`, non-ring branch) and paged
    (:func:`mha_decode_paged`) decode paths so the two can't drift —
    equivalence pinned in tests/test_decode_consistency.py.
    """
    valid = idx <= pos
    if window is not None:
        valid &= idx > pos - window
    return valid


def _flash_sharded(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   causal: bool, window: int) -> jnp.ndarray:
    """Flash attention behind an explicit shard_map boundary.

    GSPMD cannot partition through the kernel's grid loop (measured: it
    all-gathers q/k/v per layer — 5.5x the baseline collective bytes on
    granite prefill).  shard_map pins batch to the DP axes and query
    heads to "model"; each device runs a fully local pallas_call.  KV
    heads replicate over "model" when they don't divide (MQA) — AD
    through shard_map inserts the dk/dv psum automatically.  Without an
    ambient mesh (single-device tests) this is a plain local call.
    """
    from repro.kernels import ops as kops

    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names:
        return kops.flash_mha(q, k, v, causal, window)
    from jax.sharding import PartitionSpec as P

    B, Hq, S, D = q.shape
    Hkv = k.shape[1]
    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    bspec = dp if (dp and B % dp_size == 0 and B >= dp_size) else None
    m_ax = "model" if "model" in mesh.axis_names else None
    msize = mesh.shape[m_ax] if m_ax else 1
    hq_spec = m_ax if (m_ax and Hq % msize == 0 and Hq >= msize) else None
    hkv_spec = m_ax if (hq_spec and Hkv % msize == 0 and Hkv >= msize) else None
    g_global = Hq // Hkv
    hq_local = Hq // msize if hq_spec else Hq
    # GQA with kv heads that don't divide the axis: each q-head shard must
    # see ITS kv head, not all of them — slice by axis index inside the
    # region (requires each shard's q heads to fall within one kv group).
    slice_kv = (hq_spec is not None and hkv_spec is None and Hkv > 1)
    if slice_kv and (hq_local > g_global or g_global % hq_local != 0):
        hq_spec = None            # misaligned groups: replicate heads
        slice_kv = False
        hq_local = Hq

    def local(q_, k_, v_):
        if slice_kv:
            idx = jax.lax.axis_index(m_ax)
            kv_head = idx * hq_local // g_global
            k_ = jax.lax.dynamic_slice_in_dim(k_, kv_head, 1, axis=1)
            v_ = jax.lax.dynamic_slice_in_dim(v_, kv_head, 1, axis=1)
        return kops.flash_mha(q_, k_, v_, causal, window)

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(bspec, hq_spec, None, None),
                  P(bspec, hkv_spec, None, None),
                  P(bspec, hkv_spec, None, None)),
        out_specs=P(bspec, hq_spec, None, None),
        check_vma=False)  # pallas out_shape carries no vma annotations
    return fn(q, k, v)


def mha(cfg: ModelConfig, p: Params, x: jnp.ndarray, positions: jnp.ndarray,
        cap: Captures = None, prefix: str = "", kv_x: Optional[jnp.ndarray] = None,
        causal: bool = True, window: Optional[int] = None,
        kv_positions: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Full-sequence attention (training / prefill).  kv_x != None => cross-attn."""
    hd = cfg.resolved_head_dim()
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    g = nq // nkv
    src = x if kv_x is None else kv_x
    q = dense(x, p["wq"], prefix + "wq", cap, p.get("bq"))
    k = dense(src, p["wk"], prefix + "wk", cap, p.get("bk"))
    v = dense(src, p["wv"], prefix + "wv", cap, p.get("bv"))
    q = _split_heads(q, nq, hd)              # (B,Sq,nq,hd)
    k = _split_heads(k, nkv, hd)             # (B,Sk,nkv,hd)
    v = _split_heads(v, nkv, hd)
    if kv_x is None:  # self-attention gets RoPE
        inv = rope_freqs(hd, cfg.partial_rotary, cfg.rope_theta)
        if cfg.partial_rotary > 0:
            q = apply_rope(q, positions, inv)
            kv_pos = positions if kv_positions is None else kv_positions
            k = apply_rope(k, kv_pos, inv)
    if (cfg.attn_impl == "flash" and kv_x is None and causal
            and cfg.attn_logit_softcap == 0 and kv_positions is None):
        # Pallas flash attention (§Perf iteration 3): no (S, S) HBM tensor
        o = _flash_sharded(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                           v.transpose(0, 2, 1, 3), causal, int(window or 0))
        o = o.transpose(0, 2, 1, 3).reshape(x.shape[:2] + (nq * hd,))
        return dense(o.astype(x.dtype), p["wo"], prefix + "wo", cap)
    qg = q.reshape(q.shape[:2] + (nkv, g, hd))
    # grouped-query attention without materializing repeated KV heads
    scores = jnp.einsum("bqngh,bknh->bngqk", qg, k).astype(jnp.float32) / np.sqrt(hd)
    if cfg.attn_logit_softcap > 0:
        c = cfg.attn_logit_softcap
        scores = jnp.tanh(scores / c) * c
    if kv_x is not None:  # cross-attention: attend everywhere
        mask = jnp.ones((x.shape[0], q.shape[1], k.shape[1]), bool)
    else:
        kv_pos = positions if kv_positions is None else kv_positions
        mask = _causal_window_mask(positions, kv_pos, window, causal)
    scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = jnp.einsum("bngqk,bknh->bqngh", probs, v)
    out = out.reshape(out.shape[:2] + (nq * hd,))
    return dense(out, p["wo"], prefix + "wo", cap)


@dataclasses.dataclass
class KVCache:
    """Fixed-capacity per-layer KV cache.  ``cache_len`` = min(window, seq)."""
    k: jnp.ndarray  # (B, cache_len, nkv, hd)
    v: jnp.ndarray


def kv_cache_init(cfg: ModelConfig, batch: int, cache_len: int, dtype) -> Dict[str, jnp.ndarray]:
    hd = cfg.resolved_head_dim()
    shape = (batch, cache_len, cfg.num_kv_heads, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def mha_decode(cfg: ModelConfig, p: Params, x: jnp.ndarray, pos: jnp.ndarray,
               cache: Dict[str, jnp.ndarray], window: Optional[int] = None,
               cross_kv: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
               ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """One-token decode. x: (B,1,D); pos scalar int32 (same for the batch).

    Self-attn path appends K/V into the (ring-buffered when windowed) cache.
    ``cross_kv`` short-circuits to cross attention against fixed K/V.
    """
    hd = cfg.resolved_head_dim()
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    g = nq // nkv
    q = dense(x, p["wq"], bias=p.get("bq"))
    q = _split_heads(q, nq, hd)  # (B,1,nq,hd)
    if cross_kv is not None:
        k, v = cross_kv
        new_cache = cache
        valid = jnp.ones((k.shape[1],), bool)
    else:
        k_new = _split_heads(dense(x, p["wk"], bias=p.get("bk")), nkv, hd)
        v_new = _split_heads(dense(x, p["wv"], bias=p.get("bv")), nkv, hd)
        inv = rope_freqs(hd, cfg.partial_rotary, cfg.rope_theta)
        pos_b = jnp.full((x.shape[0], 1), pos, jnp.int32)
        if cfg.partial_rotary > 0:
            q = apply_rope(q, pos_b, inv)
            k_new = apply_rope(k_new, pos_b, inv)
        cache_len = cache["k"].shape[1]
        slot = jnp.mod(pos, cache_len)  # ring buffer when windowed
        k = jax.lax.dynamic_update_slice(cache["k"], k_new.astype(cache["k"].dtype), (0, slot, 0, 0))
        v = jax.lax.dynamic_update_slice(cache["v"], v_new.astype(cache["v"].dtype), (0, slot, 0, 0))
        new_cache = {"k": k, "v": v}
        idx = jnp.arange(cache_len)
        if window is not None and cache_len <= window:
            # ring: every slot valid once pos >= cache_len, else slots <= pos
            valid = (idx <= slot) | (pos >= cache_len)
        else:
            # non-ring: slot == absolute position, so the shared decode
            # mask applies directly (window cut matches the windowed full
            # forward and the paged decode path)
            valid = decode_window_mask(idx, slot, window)
    qg = q.reshape(q.shape[0], 1, nkv, g, hd)
    scores = jnp.einsum("bqngh,bknh->bngqk", qg, k).astype(jnp.float32) / np.sqrt(hd)
    if cfg.attn_logit_softcap > 0:
        c = cfg.attn_logit_softcap
        scores = jnp.tanh(scores / c) * c
    scores = jnp.where(valid[None, None, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = jnp.einsum("bngqk,bknh->bqngh", probs, v)
    out = out.reshape(out.shape[0], 1, nq * hd)
    return dense(out, p["wo"]), new_cache


def _paged_attn_sharded(q: jnp.ndarray, k_pool: jnp.ndarray,
                        v_pool: jnp.ndarray, tables: jnp.ndarray,
                        pos: jnp.ndarray, active: jnp.ndarray,
                        block_size: int, window: int, softcap: float
                        ) -> jnp.ndarray:
    """Block-table decode attention behind an optional shard_map boundary.

    Mirrors :func:`_flash_sharded`: under an ambient mesh the kv-head
    axis of the pools (and the group-aligned q heads) maps onto "model",
    so each device runs the kernel over its local heads.  The
    scalar-prefetch operands (tables/pos/active) replicate.  Without an
    ambient mesh this is a plain local dispatch.
    """
    from repro.kernels import ops as kops

    def local(q_, k_, v_, tab_, pos_, act_):
        return kops.paged_decode_attn(
            q_, k_, v_, tab_, pos_, act_, block_size=block_size,
            window=window, softcap=softcap)

    mesh = jax.sharding.get_abstract_mesh()
    nkv = int(np.prod(k_pool.shape[1:])) // q.shape[-1]
    if ("model" not in mesh.axis_names
            or nkv % mesh.shape["model"] != 0):
        return local(q, k_pool, v_pool, tables, pos, active)
    from jax.sharding import PartitionSpec as P

    # q heads shard group-aligned with kv heads: nkv % msize == 0 makes
    # every "model" shard's contiguous q chunk a whole set of kv groups,
    # and its slice of a (T, nkv*hd) pool row whole heads
    pool_spec = P(None, "model", *([None] * (k_pool.ndim - 2)))
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(None, "model", None), pool_spec, pool_spec,
                  P(None, None), P(None), P(None)),
        out_specs=P(None, "model", None),
        check_vma=False)
    return fn(q, k_pool, v_pool, tables, pos, active)


def mha_decode_paged(cfg: ModelConfig, p: Params, x: jnp.ndarray,
                     pos: jnp.ndarray, cache: Dict[str, jnp.ndarray],
                     write_idx: jnp.ndarray, gather_idx: Optional[jnp.ndarray],
                     active: jnp.ndarray, window: Optional[int] = None,
                     *, tables: Optional[jnp.ndarray] = None,
                     block_size: Optional[int] = None,
                     impl: str = "reference",
                     ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """One-token decode against a paged (block-pooled) KV cache.

    x: (S, 1, D) one token per serving slot; pos: (S,) per-slot absolute
    positions (unlike :func:`mha_decode`, slots decode at independent
    positions); cache: ``{"k", "v"}`` flat block pool for this layer,
    shape (T, nkv, hd) or (T, nkv*hd) (``kernels.ops.pool_row_shape``)
    with T = num_blocks * block_size; write_idx: (S,)
    flat pool slot receiving this token's K/V; gather_idx: (S, W) flat
    pool slots of each slot's context *in position order*; active: (S,)
    bool — inactive slots write to the trash block and attend to
    nothing (their output is garbage the caller discards).

    The attention math is element-for-element that of :func:`mha_decode`
    on a contiguous (B, W, nkv, hd) cache: the paged read gathers the
    pages into position order first, masked tail entries underflow to
    exactly 0 after softmax, and the reductions run over the same axis
    widths — so the outputs are bitwise-equal to the contiguous path
    (pinned in tests/test_kv_pool.py).

    ``impl="fused"`` (with ``tables``/``block_size`` in place of
    ``gather_idx``) routes the attention through the block-table flash
    kernel (kernels/paged_attention.py): the kernel walks the table via
    scalar prefetch instead of materializing the (S, W, nkv, hd) gather.
    On CPU / kernel-unfriendly shapes the fused route falls back to an
    oracle that repeats this function's exact math, so the two impls
    stay token-identical (DESIGN.md §11).
    """
    hd = cfg.resolved_head_dim()
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    g = nq // nkv
    q = dense(x, p["wq"], bias=p.get("bq"))
    q = _split_heads(q, nq, hd)                                   # (S,1,nq,hd)
    k_new = _split_heads(dense(x, p["wk"], bias=p.get("bk")), nkv, hd)
    v_new = _split_heads(dense(x, p["wv"], bias=p.get("bv")), nkv, hd)
    inv = rope_freqs(hd, cfg.partial_rotary, cfg.rope_theta)
    pos_b = pos[:, None]                                          # (S,1)
    if cfg.partial_rotary > 0:
        q = apply_rope(q, pos_b, inv)
        k_new = apply_rope(k_new, pos_b, inv)
    k = cache["k"].at[write_idx].set(_pool_rows(k_new[:, 0], cache["k"]))
    v = cache["v"].at[write_idx].set(_pool_rows(v_new[:, 0], cache["v"]))
    new_cache = {"k": k, "v": v}
    if impl == "fused" and tables is not None:
        o = _paged_attn_sharded(q[:, 0], k, v, tables, pos, active,
                                block_size, int(window or 0),
                                float(cfg.attn_logit_softcap))
        out = o.reshape(o.shape[0], 1, nq * hd)
        return dense(out, p["wo"]), new_cache
    heads = gather_idx.shape + (nkv, hd)
    kg = jnp.take(k, gather_idx, axis=0).reshape(heads)          # (S,W,nkv,hd)
    vg = jnp.take(v, gather_idx, axis=0).reshape(heads)
    idx = jnp.arange(gather_idx.shape[1], dtype=jnp.int32)
    valid = decode_window_mask(idx[None, :], pos[:, None], window) \
        & active[:, None]
    qg = q.reshape(q.shape[0], 1, nkv, g, hd)
    scores = jnp.einsum("bqngh,bknh->bngqk", qg, kg).astype(jnp.float32) / np.sqrt(hd)
    if cfg.attn_logit_softcap > 0:
        c = cfg.attn_logit_softcap
        scores = jnp.tanh(scores / c) * c
    scores = jnp.where(valid[:, None, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = jnp.einsum("bngqk,bknh->bqngh", probs, vg)
    out = out.reshape(out.shape[0], 1, nq * hd)
    return dense(out, p["wo"]), new_cache


def mha_prefill_paged(cfg: ModelConfig, p: Params, x: jnp.ndarray,
                      pos: jnp.ndarray, cache: Dict[str, jnp.ndarray],
                      write_idx: jnp.ndarray, gather_idx: jnp.ndarray,
                      window: Optional[int] = None,
                      ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """One fixed-width prefill *chunk* against the paged KV cache.

    x: (1, C, D) post-ln1 hidden of one prompt chunk for a single
    request; pos: (C,) absolute positions of the chunk rows; cache:
    this layer's flat block pool, (T, nkv, hd) or (T, nkv*hd); write_idx:
    (C,) flat pool slot per row — padded rows (beyond the caller's ``n_valid``)
    point into the trash block; gather_idx: (W,) flat slots of the
    request's full fixed-width context in position order, W = table
    width * block_size.

    Every chunk row gathers the *same* fixed-width context and masks it
    with :func:`decode_window_mask`, so the reductions run over
    identical axis widths regardless of chunk size, chunk offset, or
    how positions are grouped into chunks.  That makes the chunked
    prefill bitwise self-consistent across chunk groupings — the
    property the prefix cache's hit path (which resumes mid-prompt at a
    block boundary) relies on for bitwise-identical outputs
    (DESIGN.md §15, pinned in tests/test_serve_stack.py).
    """
    hd = cfg.resolved_head_dim()
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    g = nq // nkv
    C = x.shape[1]
    q = _split_heads(dense(x, p["wq"], bias=p.get("bq")), nq, hd)  # (1,C,nq,hd)
    k_new = _split_heads(dense(x, p["wk"], bias=p.get("bk")), nkv, hd)
    v_new = _split_heads(dense(x, p["wv"], bias=p.get("bv")), nkv, hd)
    if cfg.partial_rotary > 0:
        inv = rope_freqs(hd, cfg.partial_rotary, cfg.rope_theta)
        pos_b = pos[None, :]                                      # (1,C)
        q = apply_rope(q, pos_b, inv)
        k_new = apply_rope(k_new, pos_b, inv)
    k = cache["k"].at[write_idx].set(_pool_rows(k_new[0], cache["k"]))
    v = cache["v"].at[write_idx].set(_pool_rows(v_new[0], cache["v"]))
    new_cache = {"k": k, "v": v}
    heads = gather_idx.shape + (nkv, hd)
    kg = jnp.take(k, gather_idx, axis=0).reshape(heads)          # (W,nkv,hd)
    vg = jnp.take(v, gather_idx, axis=0).reshape(heads)
    idx = jnp.arange(gather_idx.shape[0], dtype=jnp.int32)
    valid = decode_window_mask(idx[None, :], pos[:, None], window)  # (C,W)
    qg = q.reshape(1, C, nkv, g, hd)
    scores = jnp.einsum("bqngh,knh->bngqk", qg, kg).astype(jnp.float32) / np.sqrt(hd)
    if cfg.attn_logit_softcap > 0:
        c = cfg.attn_logit_softcap
        scores = jnp.tanh(scores / c) * c
    scores = jnp.where(valid[None, None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = jnp.einsum("bngqk,knh->bqngh", probs, vg)
    out = out.reshape(1, C, nq * hd)
    return dense(out, p["wo"]), new_cache


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------
def mlp_init(cfg: ModelConfig, key, d_ff: Optional[int] = None) -> Params:
    dt = dtype_of(cfg.param_dtype)
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.act in ("silu", "geglu"):
        return {
            "gate": dense_init(ks[0], d, f, dt),
            "up": dense_init(ks[1], d, f, dt),
            "down": dense_init(ks[2], f, d, dt),
        }
    return {"fc1": dense_init(ks[0], d, f, dt), "b1": jnp.zeros((f,), dt),
            "fc2": dense_init(ks[1], f, d, dt), "b2": jnp.zeros((d,), dt)}


def mlp(cfg: ModelConfig, p: Params, x: jnp.ndarray, cap: Captures = None,
        prefix: str = "") -> jnp.ndarray:
    if "gate" in p:
        act = jax.nn.gelu if cfg.act == "geglu" else jax.nn.silu
        g = dense(x, p["gate"], prefix + "gate", cap)
        u = dense(x, p["up"], prefix + "up", cap)
        h = act(g.astype(jnp.float32)).astype(x.dtype) * u
        return dense(h, p["down"], prefix + "down", cap)
    h = dense(x, p["fc1"], prefix + "fc1", cap, p.get("b1"))
    h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    return dense(h, p["fc2"], prefix + "fc2", cap, p.get("b2"))


def mlp_decode(cfg: ModelConfig, p: Params, x: jnp.ndarray,
               impl: str = "reference") -> jnp.ndarray:
    """Decode-step MLP: ONE fused kernel dispatch for the whole layer
    when ``impl="fused"`` and every matmul operand is 2:4-packed and
    kernel-compilable (kernels/paged_attention.py ``fused_mlp24`` — the
    hidden activation never leaves VMEM); otherwise the reference
    per-matmul :func:`mlp`.  On CPU the fused route always takes the
    reference path, so the decode impls stay bitwise-identical there.
    """
    if impl == "fused":
        from repro.kernels import ops as kops
        gated = "gate" in p
        keys = ("gate", "up", "down") if gated else ("fc1", "fc2")
        packed = all(isinstance(p.get(kk), dict) and "vals" in p[kk]
                     for kk in keys)
        if packed:
            d = x.shape[-1]
            f = p[keys[0]]["vals"].shape[0]
            if kops.use_fused_mlp(d, f):
                lead = x.shape[:-1]
                x2 = x.reshape(-1, d)
                if gated:
                    y = kops.fused_mlp24(
                        x2, p["gate"]["vals"], p["gate"]["meta"], None,
                        p["up"]["vals"], p["up"]["meta"],
                        p["down"]["vals"], p["down"]["meta"], None,
                        act=cfg.act)
                else:
                    y = kops.fused_mlp24(
                        x2, p["fc1"]["vals"], p["fc1"]["meta"], p.get("b1"),
                        None, None,
                        p["fc2"]["vals"], p["fc2"]["meta"], p.get("b2"),
                        act="gelu")
                return y.reshape(lead + (y.shape[-1],)).astype(x.dtype)
    return mlp(cfg, p, x)


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------
def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Mean CE over labels >= 0 (labels==-1 masked).  logits (..., V)."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    ll = jnp.take_along_axis(lf, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum((lse - ll) * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def chunked_cross_entropy(hidden: jnp.ndarray, emb: jnp.ndarray, labels: jnp.ndarray,
                          chunk: int, softcap: float = 0.0) -> jnp.ndarray:
    """CE computed per sequence-chunk so the (B,S,V) logits tensor is never
    materialized.  hidden (B,S,D), emb (V,D) [tied head], labels (B,S)."""
    B, S, D = hidden.shape
    if chunk <= 0 or S % chunk != 0 or S == chunk:
        logits = jnp.einsum("bsd,vd->bsv", hidden, emb)
        if softcap > 0:
            logits = jnp.tanh(logits / softcap) * softcap
        return cross_entropy(logits, labels)
    n = S // chunk
    h = hidden.reshape(B, n, chunk, D).swapaxes(0, 1)      # (n,B,c,D)
    y = labels.reshape(B, n, chunk).swapaxes(0, 1)         # (n,B,c)

    def body(carry, xs):
        hc, yc = xs
        logits = jnp.einsum("bsd,vd->bsv", hc, emb)
        if softcap > 0:
            logits = jnp.tanh(logits / softcap) * softcap
        lf = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(lf, axis=-1)
        ll = jnp.take_along_axis(lf, jnp.maximum(yc, 0)[..., None], axis=-1)[..., 0]
        m = (yc >= 0).astype(jnp.float32)
        return (carry[0] + jnp.sum((lse - ll) * m), carry[1] + jnp.sum(m)), None

    (tot, cnt), _ = jax.lax.scan(body, (jnp.float32(0), jnp.float32(0)), (h, y))
    return tot / jnp.maximum(cnt, 1.0)
