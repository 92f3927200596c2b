"""Batched serving engine: prefill + autoregressive decode.

Drives any ModelDef through its ``prefill``/``init_serve_state``/
``serve_step`` protocol; greedy or temperature sampling; the decode loop
is jitted once per (batch, cache) shape.

**Sampling determinism**: the PRNG is folded per *request id* and
generated-token index (``serve/sampling.py``), never per engine call —
a temperature-sampled request decodes identically regardless of batch
composition, which is what lets the continuous batcher
(``serve/batcher.py``) pin token identity against this engine.
``request_ids`` defaults to ``arange(B)``.

**Sparse fast path** (``ServeConfig.sparse``): a 2:4-pruned checkpoint
is detected at engine construction and its eligible weights are packed
into the compressed ``{"vals", "meta"}`` form, so every decode matmul of
those operators dispatches through the ``kernels/spmm24`` path (0.625x
weight traffic, the batch-1 decode roofline bound — DESIGN.md §2).
Packing preserves the weight dtype, so packed logits are bitwise-equal
to the dense matmul of the same masked weights.  ``sparse="dense"`` is
the fallback flag: packed checkpoints are unpacked and everything runs
through plain dense matmuls.

The packed tree is what the engine *accounts* with (``self.params``,
``sparse_stats``); what it *computes* with is ``packed.decode_view`` of
it — identity on TPU (spmm24 kernel path), the cached bitwise-lossless
dense view on CPU, where per-step unpacking made packed serving slower
than dense (see serve/packed.py).

``ServeConfig.decode_impl`` selects the decode fast path ("fused", the
default: block-table flash attention + the fused packed MLP in the
*paged* step) vs the reference gather path that anchors it bitwise.
The contiguous-cache engine here has no paged step, so it serves via
the reference path either way — the flag is validated and forwarded for
config symmetry with ``BatchConfig`` (DESIGN.md §11 fallback rules).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.registry import ModelDef
from repro.serve import packed as packed_lib
from repro.serve import sampling
from repro.utils import get_logger

log = get_logger("serve")

_SPARSE_MODES = ("auto", "packed", "dense")
DECODE_IMPLS = ("fused", "reference")


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    max_new_tokens: int = 32
    temperature: float = 0.0       # 0 => greedy
    cache_len: int = 256
    seed: int = 0
    sparse: str = "auto"           # auto | packed | dense (fallback flag)
    decode_impl: str = "fused"     # fused | reference (bitwise oracle)
    prefill_chunk: Optional[int] = None  # tokens per prefill chunk: route
                                         # the prefill through the same
                                         # fixed-width paged chunk
                                         # executable the batcher uses, so
                                         # solo outputs anchor the chunked
                                         # batcher bitwise (DESIGN.md §15)
    block_size: int = 16           # chunked-prefill block/table granularity
                                   # (must match BatchConfig.block_size for
                                   # the token-identity anchor)


def prepare_serving_params(params: Any, sparse: str
                           ) -> Tuple[Any, Dict[str, Any]]:
    """Route params onto the requested weight representation.

    auto   — pack when the checkpoint's weights satisfy 2:4 (lossless,
             weight dtype kept); otherwise serve dense.
    packed — require a 2:4 checkpoint (already packed or packable).
    dense  — force dense matmuls (unpacks a packed checkpoint).

    Shared by :class:`Engine` and the continuous batcher so both serving
    surfaces make identical packing decisions.
    """
    if sparse not in _SPARSE_MODES:
        raise ValueError(f"unknown sparse mode {sparse!r}; "
                         f"choices: {_SPARSE_MODES}")
    pre_packed = packed_lib.count_packed(params)
    if sparse == "dense":
        if pre_packed:
            log.info("sparse=dense: unpacking %d packed operators", pre_packed)
            params = packed_lib.unpack_tree(params)
        return params, {"mode": "dense", "packed_ops": 0}
    if pre_packed:      # caller packed explicitly (e.g. bf16 storage)
        return params, {"mode": "packed", "packed_ops": pre_packed}
    packed, stats = packed_lib.pack_tree(params, dtype=None)
    if stats["packed_ops"] == 0:
        if sparse == "packed":
            raise ValueError(
                "sparse='packed' but no operator satisfies 2:4 — prune "
                "the checkpoint to 2:4 first, or serve with sparse='auto'")
        return params, {"mode": "dense", "packed_ops": 0}
    log.info("2:4 checkpoint detected: packed %d operators "
             "(%.2f MB -> %.2f MB weight traffic)", stats["packed_ops"],
             stats["dense_bytes"] / 1e6, stats["packed_bytes"] / 1e6)
    return packed, {"mode": "packed", **stats}


class Engine:
    def __init__(self, model: ModelDef, params: Any, cfg: ServeConfig = ServeConfig(),
                 executor: Optional[Any] = None):
        """``executor`` (distributed/executor.py) places the serving
        params on its mesh per the Megatron column/row rules — decode
        runs tensor-parallel over "model" with one all-reduce per block
        (GSPMD inserts it), token-identical to the single-device path."""
        if cfg.decode_impl not in DECODE_IMPLS:
            raise ValueError(f"unknown decode_impl {cfg.decode_impl!r}; "
                             f"choices: {DECODE_IMPLS}")
        if cfg.prefill_chunk is not None:
            if cfg.prefill_chunk < 1:
                raise ValueError(f"prefill_chunk must be >= 1, got "
                                 f"{cfg.prefill_chunk}")
            if cfg.block_size < 1:
                raise ValueError(f"block_size must be >= 1, got "
                                 f"{cfg.block_size}")
            if model.paged_prefill_chunk is None:
                raise ValueError(
                    f"family {model.cfg.family!r} has no chunked prefill "
                    f"path (paged_prefill_chunk)")
        self.model, self.cfg = model, cfg
        self.executor = executor
        self.params, self.sparse_stats = prepare_serving_params(params, cfg.sparse)
        if cfg.decode_impl == "fused" and model.paged_step is None:
            log.debug("decode_impl='fused' on a family without a paged "
                      "step: serving via the reference decode path")
        # accounting tree (self.params, may stay packed) vs compute tree
        # (the decode view: identity on one TPU, cached dense unpack on
        # CPU or under a mesh)
        exec_params = packed_lib.decode_view(self.params,
                                             sharded=executor is not None)
        if executor is not None:
            same = exec_params is self.params
            self.params = executor.shard_params(self.params)
            exec_params = self.params if same else \
                executor.shard_params(exec_params)
        self._exec_params = exec_params
        self._decode_fn = jax.jit(self._decode_step)
        if cfg.prefill_chunk is not None:
            self._chunk_fn = jax.jit(self._chunk_step, donate_argnums=(1,))

    def _chunk_step(self, params, pool, table, tokens, pos0, n_valid):
        return self.model.paged_prefill_chunk(params, pool, table, tokens,
                                              pos0, n_valid,
                                              self.cfg.block_size)

    def _chunked_prefill(self, prompt: jnp.ndarray, cache_len: int,
                         req_keys: jnp.ndarray
                         ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        """Prefill via the fixed-width paged chunk executable, then fold
        the paged rows into the contiguous serve cache.

        This is the batcher's chunked-prefill machinery run solo: same
        ``paged_prefill_chunk`` function, same fixed context width
        (``cache_len``), so the resulting K/V rows and first-token logits
        are bitwise those of the batcher — which is what lets the
        chunked batcher anchor token identity against this engine.  The
        gather into the contiguous cache is a pure data movement (the
        pool and cache share a dtype), and the contiguous decode read is
        pinned bitwise-equal to the paged one (tests/test_kv_pool.py).
        """
        from repro.serve import kv_cache
        cfg = self.cfg
        B, P = prompt.shape
        bs, C = cfg.block_size, cfg.prefill_chunk
        MB = cache_len // bs
        table = jnp.arange(1, MB + 1, dtype=jnp.int32)
        state = self.model.init_serve_state(self._exec_params, B, cache_len,
                                            None)
        if self.executor is not None:
            state = self.executor.shard_serve_state(state)
        flat = kv_cache.flat_slots(list(range(1, MB + 1)), P, bs)
        prompt_np = np.asarray(prompt)
        firsts, rows = [], {k: [] for k in state}
        for b in range(B):
            pool = self.model.init_paged_state(MB + 1, bs)
            o, last = 0, None
            while o < P:
                n_valid = min(C, P - o)
                toks = np.zeros((1, C), np.int32)
                toks[0, :n_valid] = prompt_np[b, o:o + n_valid]
                last, pool = self._chunk_fn(self._exec_params, pool, table,
                                            jnp.asarray(toks), jnp.int32(o),
                                            jnp.int32(n_valid))
                o += n_valid
            firsts.append(last[:, -1, :])
            for k in state:
                rows[k].append(pool[k][:, flat].reshape(
                    (-1, len(flat)) + state[k].shape[3:]))
        # cache_len >= P, so decode's non-ring slots are the absolute
        # positions: rows land at 0..P-1, the tail stays zero (masked)
        state = {k: state[k].at[:, :, :P].set(jnp.stack(rows[k], axis=1))
                 for k in state}
        first_logits = jnp.concatenate(firsts, axis=0).astype(jnp.float32)
        if self.executor is not None:
            first_logits = self.executor.replicate_logits(first_logits)
        token = sampling.sample(first_logits, sampling.step_keys(req_keys, 0),
                                cfg.temperature)[:, None]
        return token, state

    def _decode_step(self, params, state, token, pos, keys):
        logits, state = self.model.serve_step(params, state, token, pos)
        logits = logits[:, -1, :].astype(jnp.float32)
        if self.executor is not None:
            # sampling needs replicated logits (MeshExecutor.replicate_logits)
            logits = self.executor.replicate_logits(logits)
        nxt = sampling.sample(logits, keys, self.cfg.temperature)
        return nxt[:, None], state

    def _check_capacity(self, prompt_len: int, n_new: int) -> None:
        """Positions ``0..prompt_len+n_new-1`` must exist for the model.

        Without this check the engine silently wrapped or overran
        positions past the model's trained range (whisper's learned
        ``pos_embed`` lookup clamps out-of-range indices; RoPE models
        run past ``max_seq``) and decoded garbage.
        """
        if n_new < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {n_new}")
        if prompt_len < 1:
            raise ValueError("prompt must hold at least one token")
        total, limit = prompt_len + n_new, self.model.cfg.max_seq
        if total > limit:
            raise ValueError(
                f"prompt_len + max_new_tokens = {total} exceeds the model's "
                f"max_seq ({limit}): positions would silently wrap or "
                f"overrun the cache — shorten the prompt or lower "
                f"max_new_tokens")

    def generate(self, prompt: jnp.ndarray,
                 extras: Optional[Dict[str, jnp.ndarray]] = None,
                 max_new_tokens: Optional[int] = None,
                 request_ids: Optional[Any] = None) -> np.ndarray:
        """prompt (B, P) int32 -> generated tokens (B, new).

        ``request_ids`` (B,) int seeds the per-request sampling PRNG
        (default ``arange(B)``); pass each request's stable id to make
        temperature-sampled outputs independent of batch composition.
        """
        cfg = self.cfg
        B, P = prompt.shape
        n_new = cfg.max_new_tokens if max_new_tokens is None else max_new_tokens
        # VLM prefill prepends patch embeddings: they occupy positions too
        n_extra = 0
        if extras is not None and extras.get("patches") is not None:
            n_extra = extras["patches"].shape[1]
        p_eff = P + n_extra
        self._check_capacity(p_eff, n_new)
        cache_len = max(cfg.cache_len, p_eff + n_new)
        if request_ids is None:
            request_ids = np.arange(B)
        req_keys = sampling.request_keys(cfg.seed,
                                         jnp.asarray(request_ids, jnp.int32))

        if cfg.prefill_chunk is not None:
            if extras is not None:
                raise ValueError(
                    "chunked prefill takes token prompts only — serve "
                    "extras-carrying requests (VLM patches) with "
                    "prefill_chunk=None")
            # round the context up to whole blocks for the paged chunk path
            cache_len = -(-cache_len // cfg.block_size) * cfg.block_size
            token, state = self._chunked_prefill(prompt, cache_len, req_keys)
            pos0 = P
        elif self.model.prefill is not None:
            logits, state = self.model.prefill(self._exec_params, prompt,
                                               cache_len, extras)
            first_logits = logits[:, -1, :].astype(jnp.float32)
            if self.executor is not None:
                first_logits = self.executor.replicate_logits(first_logits)
            token = sampling.sample(first_logits,
                                    sampling.step_keys(req_keys, 0),
                                    cfg.temperature)[:, None]
            pos0 = p_eff
        else:
            # recurrent families: feed the prompt token-by-token (sampled
            # outputs are discarded until the last prompt token, whose
            # sample is generated-token 0 — hence the index-0 keys)
            state = self.model.init_serve_state(self._exec_params, B,
                                                cache_len, extras)
            if self.executor is not None:
                state = self.executor.shard_serve_state(state)
            keys0 = sampling.step_keys(req_keys, 0)
            for t in range(P):
                nxt, state = self._decode_fn(self._exec_params, state,
                                             prompt[:, t:t + 1], jnp.int32(t),
                                             keys0)
            token = nxt
            pos0 = P

        # tokens stay on device through the decode loop — a per-step
        # np.asarray would block the dispatch pipeline every token
        # (JAX003); one transfer after the loop
        out = [token]
        for t in range(n_new - 1):
            keys = sampling.step_keys(req_keys, t + 1)
            token, state = self._decode_fn(self._exec_params, state, token,
                                           jnp.int32(pos0 + t), keys)
            out.append(token)
        return np.asarray(jnp.concatenate(out, axis=1))
