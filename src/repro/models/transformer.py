"""Decoder-only transformer (dense GQA / MQA / SWA / MoE variants).

Covers stablelm-1.6b, minicpm-2b, internlm2-20b, granite-20b, the
internvl2-2b LLM backbone, qwen2-moe and mixtral.  Two execution paths
share the same per-layer code:

* fast path — ``loss`` / ``forward_logits`` / ``serve_step`` scan over
  layer-stacked params (HLO size independent of depth, per-layer remat);
* unit path — ``unit_apply`` applies one decoder layer with activation
  capture; this is what the calibration/pruning relay drives.

The pruning-unit protocol (used by core/sequential.py):
    state  : dict of arrays  ({"x": hidden, "positions": pos, ...})
    embed(cfg, params, batch)            -> state
    units(cfg)                           -> [UnitSpec, ...]
    unit_apply(cfg, unit_params, i, state, cap=None) -> state
    head(cfg, params, state)             -> logits
"""
from __future__ import annotations

from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import common, moe as moe_lib
from repro.models.common import (Captures, Params, chunked_cross_entropy, dense,
                                 dense_init, dtype_of, embed_init, mha,
                                 mha_decode, mlp, mlp_init, norm_apply,
                                 norm_init)
from repro.utils import tree as tree_lib


class UnitSpec(NamedTuple):
    name: str
    param_path: str                       # e.g. "layers" (stacked) or "layers/3"
    layer_index: int
    groups: Tuple[Tuple[str, ...], ...]   # sequential capture-key groups
    stacked: bool = True                  # params stacked on a leading L axis?


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def layer_init(cfg: ModelConfig, key) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    p: Params = {
        "ln1": norm_init(cfg, cfg.d_model),
        "attn": common.attn_init(cfg, k1),
        "ln2": norm_init(cfg, cfg.d_model),
    }
    if cfg.moe is not None:
        p["moe"] = moe_lib.moe_init(cfg, k2)
    else:
        p["mlp"] = mlp_init(cfg, k2)
    return p


def init(cfg: ModelConfig, key) -> Params:
    ks = jax.random.split(key, cfg.num_layers + 3)
    layers = tree_lib.tree_stack([layer_init(cfg, ks[i]) for i in range(cfg.num_layers)])
    p: Params = {
        "embed": embed_init(ks[-1], cfg.vocab, cfg.d_model, dtype_of(cfg.param_dtype)),
        "layers": layers,
        "final_norm": norm_init(cfg, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        p["head"] = dense_init(ks[-2], cfg.d_model, cfg.vocab, dtype_of(cfg.param_dtype))
    return p


# ---------------------------------------------------------------------------
# per-layer forward (shared by both paths)
# ---------------------------------------------------------------------------
def _layer_window(cfg: ModelConfig, i: int) -> Optional[int]:
    return cfg.window


def layer_apply(cfg: ModelConfig, p: Params, x: jnp.ndarray, positions: jnp.ndarray,
                cap: Captures = None, window: Optional[int] = None
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One decoder layer; returns (x, moe_aux_loss)."""
    rs = cfg.residual_scale
    h = norm_apply(cfg, p["ln1"], x)
    a = mha(cfg, p["attn"], h, positions, cap, "attn/", window=window)
    x = x + a.astype(x.dtype) * rs
    h = norm_apply(cfg, p["ln2"], x)
    if cfg.moe is not None:
        f, aux = moe_lib.moe_apply(cfg, p["moe"], h, cap, "moe/")
    else:
        f, aux = mlp(cfg, p["mlp"], h, cap, "mlp/"), jnp.float32(0.0)
    x = x + f.astype(x.dtype) * rs
    return x, aux


# ---------------------------------------------------------------------------
# fast path: scan over stacked layers
# ---------------------------------------------------------------------------
def hidden_states(cfg: ModelConfig, params: Params, tokens: jnp.ndarray,
                  extra_embeddings: Optional[jnp.ndarray] = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Embed + all layers (scan).  Returns (hidden (B,S,D), moe aux loss)."""
    x = params["embed"][tokens] * cfg.emb_scale
    if extra_embeddings is not None:  # VLM: prepend patch embeddings
        x = jnp.concatenate([extra_embeddings.astype(x.dtype), x], axis=1)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))

    def body(carry, lp):
        h, aux = carry
        h2, a = layer_apply(cfg, lp, h, positions, window=cfg.window)
        return (h2, aux + a), None

    body_fn = jax.checkpoint(body) if cfg.remat else body
    if cfg.scan_layers:
        (x, aux), _ = jax.lax.scan(body_fn, (x, jnp.float32(0.0)), params["layers"])
    else:  # unrolled: accurate per-layer HLO cost accounting (dry-run)
        carry = (x, jnp.float32(0.0))
        for i in range(cfg.num_layers):
            carry, _ = body_fn(carry, tree_lib.tree_index(params["layers"], i))
        x, aux = carry
    return norm_apply(cfg, params["final_norm"], x), aux


def unembed(cfg: ModelConfig, params: Params, h: jnp.ndarray) -> jnp.ndarray:
    if cfg.tie_embeddings:
        logits = jnp.einsum("...d,vd->...v", h, params["embed"]) * cfg.logit_scale
    else:
        logits = dense(h, params["head"]) * cfg.logit_scale
    if cfg.logit_softcap > 0:
        logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    return logits


def forward_logits(cfg: ModelConfig, params: Params, tokens: jnp.ndarray,
                   extra_embeddings: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    h, _ = hidden_states(cfg, params, tokens, extra_embeddings)
    return unembed(cfg, params, h)


def loss(cfg: ModelConfig, params: Params, batch: Dict[str, jnp.ndarray]
         ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """batch: {"tokens": (B,S), "labels": (B,S)} (+"patches" for VLM)."""
    h, aux = hidden_states(cfg, params, batch["tokens"], batch.get("patches"))
    labels = batch["labels"]
    if batch.get("patches") is not None:
        pad = jnp.full(batch["patches"].shape[:2], -1, labels.dtype)
        labels = jnp.concatenate([pad, labels], axis=1)
    if cfg.tie_embeddings or cfg.ce_chunk:
        emb = params["embed"] if cfg.tie_embeddings else params["head"].T
        ce = chunked_cross_entropy(h * cfg.logit_scale, emb, labels,
                                   cfg.ce_chunk, cfg.logit_softcap)
    else:
        ce = common.cross_entropy(unembed(cfg, params, h), labels)
    aux_coef = cfg.moe.router_aux_coef if cfg.moe is not None else 0.0
    total = ce + aux_coef * aux
    return total, {"ce": ce, "moe_aux": aux}


# ---------------------------------------------------------------------------
# serving path: prefill + single-token decode with per-layer KV caches
# ---------------------------------------------------------------------------
def init_kv_caches(cfg: ModelConfig, batch: int, cache_len: int) -> Dict[str, jnp.ndarray]:
    hd = cfg.resolved_head_dim()
    shape = (cfg.num_layers, batch, cache_len, cfg.num_kv_heads, hd)
    dt = dtype_of(cfg.compute_dtype)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def serve_step(cfg: ModelConfig, params: Params, caches: Dict[str, jnp.ndarray],
               token: jnp.ndarray, pos: jnp.ndarray
               ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """One decode step.  token (B,1) int32, pos scalar int32.
    Returns (logits (B,1,V), new caches)."""
    x = params["embed"][token] * cfg.emb_scale

    def body(h, xs):
        lp, cache = xs
        rs = cfg.residual_scale
        hn = norm_apply(cfg, lp["ln1"], h)
        a, new_cache = mha_decode(cfg, lp["attn"], hn, pos, cache, window=cfg.window)
        h = h + a.astype(h.dtype) * rs
        hn = norm_apply(cfg, lp["ln2"], h)
        if cfg.moe is not None:
            f, _ = moe_lib.moe_apply(cfg, lp["moe"], hn)
        else:
            f = mlp(cfg, lp["mlp"], hn)
        return h + f.astype(h.dtype) * rs, new_cache

    if cfg.scan_layers:
        x, new_caches = jax.lax.scan(body, x, (params["layers"], caches))
    else:
        outs = []
        for i in range(cfg.num_layers):
            lp = tree_lib.tree_index(params["layers"], i)
            ci = jax.tree_util.tree_map(lambda c: c[i], caches)
            x, co = body(x, (lp, ci))
            outs.append(co)
        new_caches = tree_lib.tree_stack(outs)
    h = norm_apply(cfg, params["final_norm"], x)
    return unembed(cfg, params, h), new_caches


def prefill(cfg: ModelConfig, params: Params, tokens: jnp.ndarray,
            cache_len: int, extra_embeddings: Optional[jnp.ndarray] = None,
            last_only: bool = False) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Full-sequence prefill; fills KV caches (last ``cache_len`` positions)
    and returns (logits, caches).  ``extra_embeddings`` prepends modality
    embeddings (VLM patches) to the token stream.

    ``last_only`` unembeds ONLY the final position (§Perf iteration 2):
    prefill needs the next-token logits + caches, and materializing the
    full (B, S, V) logits tensor dominated the memory roofline term for
    large-vocab archs (minicpm: 122k vocab x 32k seq)."""
    B, S = tokens.shape
    x = params["embed"][tokens] * cfg.emb_scale
    if extra_embeddings is not None:
        x = jnp.concatenate([extra_embeddings.astype(x.dtype), x], axis=1)
        S = x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))
    hd = cfg.resolved_head_dim()

    def body(carry, lp):
        h = carry
        rs = cfg.residual_scale
        hn = norm_apply(cfg, lp["ln1"], h)
        # capture K/V of the last cache_len positions for the cache
        src = hn
        k = common._split_heads(dense(src, lp["attn"]["wk"], bias=lp["attn"].get("bk")),
                                cfg.num_kv_heads, hd)
        v = common._split_heads(dense(src, lp["attn"]["wv"], bias=lp["attn"].get("bv")),
                                cfg.num_kv_heads, hd)
        if cfg.partial_rotary > 0:
            inv = common.rope_freqs(hd, cfg.partial_rotary, cfg.rope_theta)
            k = common.apply_rope(k, positions, inv)
        a = mha(cfg, lp["attn"], hn, positions, window=cfg.window)
        h = h + a.astype(h.dtype) * rs
        hn = norm_apply(cfg, lp["ln2"], h)
        if cfg.moe is not None:
            f, _ = moe_lib.moe_apply(cfg, lp["moe"], hn)
        else:
            f = mlp(cfg, lp["mlp"], hn)
        h = h + f.astype(h.dtype) * rs
        dt = dtype_of(cfg.compute_dtype)
        # place the last min(S, cache_len) positions at slot (pos % cache_len)
        # so decode's ring indexing lines up with absolute positions
        t = min(S, cache_len)
        slots = (jnp.arange(S - t, S) % cache_len).astype(jnp.int32)
        kf = jnp.zeros((B, cache_len) + k.shape[2:], dt).at[:, slots].set(
            k[:, -t:].astype(dt))
        vf = jnp.zeros((B, cache_len) + v.shape[2:], dt).at[:, slots].set(
            v[:, -t:].astype(dt))
        return h, {"k": kf, "v": vf}

    if cfg.scan_layers:
        x, caches = jax.lax.scan(body, x, params["layers"])
    else:
        outs = []
        for i in range(cfg.num_layers):
            x, co = body(x, tree_lib.tree_index(params["layers"], i))
            outs.append(co)
        caches = tree_lib.tree_stack(outs)
    h = norm_apply(cfg, params["final_norm"], x)
    if last_only:
        h = h[:, -1:, :]
    return unembed(cfg, params, h), caches


# ---------------------------------------------------------------------------
# paged serving path: slot-batched decode against a block-pooled KV cache
# ---------------------------------------------------------------------------
def init_paged_caches(cfg: ModelConfig, num_blocks: int, block_size: int
                      ) -> Dict[str, jnp.ndarray]:
    """Paged KV pool: one flat (L, num_blocks*block_size, *row) tensor
    per K/V, a token's row being ``(nkv, hd)``, or ``(nkv*hd,)`` for
    heads narrower than a lane row (``kernels.ops.pool_row_shape``).
    Block ``b``, offset ``s`` lives at flat slot ``b*block_size + s``;
    block 0 is the serving stack's reserved trash block
    (``serve/kv_cache.py``) — inactive slots write there."""
    from repro.kernels.ops import pool_row_shape
    row = pool_row_shape(cfg.num_kv_heads, cfg.resolved_head_dim())
    shape = (cfg.num_layers, num_blocks * block_size) + row
    dt = dtype_of(cfg.compute_dtype)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def paged_serve_step(cfg: ModelConfig, params: Params,
                     caches: Dict[str, jnp.ndarray], tables: jnp.ndarray,
                     token: jnp.ndarray, pos: jnp.ndarray,
                     active: jnp.ndarray, block_size: int,
                     impl: str = "reference"
                     ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """One decode step over the serving slots, slot-indexed into the
    paged KV pool.  token (S,1) int32; pos (S,) per-slot absolute
    positions; tables (S, MB) int32 block tables; active (S,) bool.
    Returns (logits (S,1,V), new caches).

    The step is shape-stable in everything but the params: the
    continuous batcher jits it once per slot count, and requests join or
    retire by flipping ``active`` / rewriting table rows — never by
    reshaping.  Inactive slots compute masked garbage (writes land in
    the trash block, reads attend to nothing) that the caller discards.

    ``impl="fused"`` skips materializing the (S, W) position-order
    ``gather_idx`` and hands the block tables straight to the fused
    decode fast path (block-table flash attention + the packed MLP,
    kernels/paged_attention.py); ``"reference"`` is the
    gather path that anchors it bitwise.
    """
    S, MB = tables.shape
    fused = impl == "fused"
    write_block = jnp.take_along_axis(tables, pos[:, None] // block_size,
                                      axis=1)[:, 0]
    write_idx = write_block * block_size + pos % block_size          # (S,)
    if fused:
        gather_idx = None
    else:
        j = jnp.arange(MB * block_size, dtype=jnp.int32)
        gather_blocks = jnp.take_along_axis(
            tables, jnp.broadcast_to(j[None, :] // block_size,
                                     (S, j.shape[0])), axis=1)
        gather_idx = gather_blocks * block_size + (j % block_size)[None, :]

    x = params["embed"][token] * cfg.emb_scale

    def body(h, xs):
        lp, cache = xs
        rs = cfg.residual_scale
        hn = norm_apply(cfg, lp["ln1"], h)
        a, new_cache = common.mha_decode_paged(
            cfg, lp["attn"], hn, pos, cache, write_idx, gather_idx, active,
            window=cfg.window, tables=tables if fused else None,
            block_size=block_size, impl=impl)
        h = h + a.astype(h.dtype) * rs
        hn = norm_apply(cfg, lp["ln2"], h)
        if cfg.moe is not None:
            f, _ = moe_lib.moe_apply(cfg, lp["moe"], hn)
        else:
            f = common.mlp_decode(cfg, lp["mlp"], hn, impl=impl)
        return h + f.astype(h.dtype) * rs, new_cache

    if cfg.scan_layers:
        x, new_caches = jax.lax.scan(body, x, (params["layers"], caches))
    else:
        outs = []
        for i in range(cfg.num_layers):
            lp = tree_lib.tree_index(params["layers"], i)
            ci = jax.tree_util.tree_map(lambda c: c[i], caches)
            x, co = body(x, (lp, ci))
            outs.append(co)
        new_caches = tree_lib.tree_stack(outs)
    h = norm_apply(cfg, params["final_norm"], x)
    return unembed(cfg, params, h), new_caches


def paged_prefill_chunk(cfg: ModelConfig, params: Params,
                        caches: Dict[str, jnp.ndarray], table: jnp.ndarray,
                        tokens: jnp.ndarray, pos0: jnp.ndarray,
                        n_valid: jnp.ndarray, block_size: int
                        ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """One fixed-width prefill chunk for a single request's block table.

    table (MB,) int32 block table (trash-padded past the prompt);
    tokens (1, C) int32 chunk (rows past ``n_valid`` are padding);
    pos0 / n_valid traced scalars — the chunk covers absolute positions
    ``pos0 .. pos0 + n_valid - 1``.  Returns ``(logits (1,1,V), new
    caches)``: the logits of the chunk's *last valid* row only (all a
    prefill needs — the first sampled token), sliced before unembedding
    so the (C, V) logits tensor is never materialized.

    The chunk is shape-stable in everything but the scalars: the
    batcher and the solo engine jit it once (declared in
    ``TRACE_BUDGETS``) and drive any prompt length / chunk offset
    through the same executable.  Attention gathers the full
    fixed-width context per row (``common.mha_prefill_paged``), which
    keeps the chunked prefill bitwise self-consistent across chunk
    groupings — the prefix cache's hit path resumes mid-prompt through
    this very executable.  Padded rows write their K/V into the trash
    block and their outputs are discarded.
    """
    MB = table.shape[0]
    C = tokens.shape[1]
    pos = pos0 + jnp.arange(C, dtype=jnp.int32)                   # (C,)
    valid_q = jnp.arange(C, dtype=jnp.int32) < n_valid
    blk = jnp.take(table, jnp.clip(pos // block_size, 0, MB - 1))
    write_idx = jnp.where(valid_q, blk * block_size + pos % block_size,
                          pos % block_size)
    j = jnp.arange(MB * block_size, dtype=jnp.int32)
    gather_idx = jnp.take(table, j // block_size) * block_size + j % block_size

    x = params["embed"][tokens] * cfg.emb_scale

    def body(h, xs):
        lp, cache = xs
        rs = cfg.residual_scale
        hn = norm_apply(cfg, lp["ln1"], h)
        a, new_cache = common.mha_prefill_paged(
            cfg, lp["attn"], hn, pos, cache, write_idx, gather_idx,
            window=cfg.window)
        h = h + a.astype(h.dtype) * rs
        hn = norm_apply(cfg, lp["ln2"], h)
        if cfg.moe is not None:
            f, _ = moe_lib.moe_apply(cfg, lp["moe"], hn)
        else:
            f = mlp(cfg, lp["mlp"], hn)
        return h + f.astype(h.dtype) * rs, new_cache

    if cfg.scan_layers:
        x, new_caches = jax.lax.scan(body, x, (params["layers"], caches))
    else:
        outs = []
        for i in range(cfg.num_layers):
            lp = tree_lib.tree_index(params["layers"], i)
            ci = jax.tree_util.tree_map(lambda c: c[i], caches)
            x, co = body(x, (lp, ci))
            outs.append(co)
        new_caches = tree_lib.tree_stack(outs)
    h = norm_apply(cfg, params["final_norm"], x)
    h_last = jax.lax.dynamic_slice_in_dim(h, n_valid - 1, 1, axis=1)
    return unembed(cfg, params, h_last), new_caches


# ---------------------------------------------------------------------------
# unit path (pruning relay)
# ---------------------------------------------------------------------------
def attn_groups(cfg: ModelConfig) -> List[List[str]]:
    return [["attn/wq", "attn/wk", "attn/wv"], ["attn/wo"]]


def ffn_groups(cfg: ModelConfig) -> List[List[str]]:
    if cfg.moe is not None:
        return moe_lib.moe_operator_groups(cfg, "moe/")
    if cfg.act == "silu":
        return [["mlp/gate", "mlp/up"], ["mlp/down"]]
    return [["mlp/fc1"], ["mlp/fc2"]]


def units(cfg: ModelConfig) -> List[UnitSpec]:
    groups = tuple(tuple(g) for g in attn_groups(cfg) + ffn_groups(cfg))
    return [UnitSpec(f"layer{i:03d}", "layers", i, groups)
            for i in range(cfg.num_layers)]


def embed(cfg: ModelConfig, params: Params, batch: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
    x = params["embed"][batch["tokens"]] * cfg.emb_scale
    if batch.get("patches") is not None:
        x = jnp.concatenate([batch["patches"].astype(x.dtype), x], axis=1)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))
    return {"x": x, "positions": positions}


def unit_apply(cfg: ModelConfig, unit_params: Params, i: int,
               state: Dict[str, jnp.ndarray], cap: Captures = None
               ) -> Dict[str, jnp.ndarray]:
    x, aux = layer_apply(cfg, unit_params, state["x"], state["positions"],
                         cap, window=_layer_window(cfg, i))
    return dict(state, x=x)


def head(cfg: ModelConfig, params: Params, state: Dict[str, jnp.ndarray]) -> jnp.ndarray:
    return unembed(cfg, params, norm_apply(cfg, params["final_norm"], state["x"]))
