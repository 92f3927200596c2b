"""Continuous-batching request scheduler over the paged KV pool.

The batcher turns the single-shot ``Engine`` into a request-level
serving loop: an admission queue of :class:`Request`, a fixed number of
serving *slots*, and **one** jitted decode step
(``ModelDef.paged_step``) over those slots.  Requests join mid-flight —
their prompt K/V lands in freshly allocated blocks and their slot goes
active — and retire on EOS or length by flipping the active mask and
freeing their blocks.  The decode step never re-specializes: slot
count, block-table width, and pool shape are fixed at construction, so
joining/retiring costs zero recompilation (tests pin
``_step_fn._cache_size() == 1``).

Three serving features layer on top of that core (DESIGN.md §15):

* **Chunked prefill** (``BatchConfig.prefill_chunk``): prompts prefill
  through one fixed-width jitted chunk executable
  (``ModelDef.paged_prefill_chunk``), at most one chunk per scheduler
  tick, interleaved with decode — a long prompt no longer stalls every
  in-flight decode, bounding inter-token latency.  The chunk path is
  bitwise self-consistent across chunk sizes/offsets, and the solo
  ``Engine`` runs the same executable in its chunked mode, so the
  token-identity anchor holds end to end.
* **Prefix cache** (``BatchConfig.prefix_cache``, requires chunked
  prefill): full prompt blocks are cached in a radix trie
  (``serve/prefix_cache.py``) and shared block-refcounted across
  requests; a hit skips the matched chunks entirely and resumes the
  chunk executable mid-prompt — bitwise-identical to a cold prefill.
* **SLA-aware admission**: the queue orders by ``(priority, deadline,
  arrival, id)`` with strict head-of-line (no bypass — deterministic);
  admission charges a request its *actual* block need (prefix-cache
  hits are discounted) and, when the pool or slots are exhausted, a
  strictly-lower-priority active request is **preempted** — its
  written K/V swapped to the host, blocks freed, request re-queued —
  and later resumed bitwise-exactly via the ``scatter_prefill`` path.

Correctness anchor: every request's output is **token-identical** to a
solo ``Engine.generate(prompt, request_ids=[id])`` with
``cache_len == BatchConfig.context_len`` (and the same
``prefill_chunk`` when chunked) — on dense and 2:4-packed checkpoints,
greedy and temperature sampling (see DESIGN.md §9/§15 for why the paged
read, the fixed-width chunked prefill, and the per-request PRNG folding
make this exact).

Block accounting: blocks are allocated lazily as a request's context
grows, but admission *reserves* the request's worst-case block count
(``ceil((P + max_new) / block_size)`` minus prefix-cache-matched
blocks) against the pool, so an active request can never hit
``PoolExhausted`` mid-flight — pressure shows up as queueing delay or
preemption of lower-priority work, never as a mid-generation failure.
"""
from __future__ import annotations

import dataclasses
import math
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.models.registry import ModelDef
from repro.serve import kv_cache, sampling
from repro.serve import packed as packed_lib
from repro.serve.engine import prepare_serving_params
from repro.serve.prefix_cache import PrefixCache
from repro.utils import get_logger

log = get_logger("serve.batcher")


@dataclasses.dataclass
class Request:
    id: int
    prompt: np.ndarray                 # (P,) int32
    max_new_tokens: int = 16
    temperature: float = 0.0
    eos_id: Optional[int] = None       # None: run to max_new_tokens
    arrival: float = 0.0               # seconds from trace start
    priority: int = 0                  # lower = more urgent
    deadline: Optional[float] = None   # seconds from trace start; tie-break


@dataclasses.dataclass
class RequestResult:
    id: int
    tokens: np.ndarray                 # generated tokens (includes EOS if hit)
    reason: str                        # "length" | "eos"
    prompt_len: int
    arrival: float                     # seconds from run start
    admitted: float
    first_token: float
    finished: float
    admitted_step: int                 # decode-step counter at admission
    finished_step: int
    priority: int = 0
    prefix_hit_tokens: int = 0         # prompt tokens served from the cache
    preemptions: int = 0               # times this request was preempted
    token_times: Optional[np.ndarray] = None  # per-token emission times (s)
    # per-token host-receipt times (s): when the host first held the token
    recv_times: Optional[np.ndarray] = None

    @property
    def latency(self) -> float:
        return self.finished - self.arrival


@dataclasses.dataclass(frozen=True)
class BatchConfig:
    slots: int = 4
    block_size: int = 16
    max_blocks_per_request: int = 4    # context width = block_size * this
    num_blocks: int = 64               # pool size incl. reserved trash block
    seed: int = 0                      # sampling PRNG seed (Engine's cfg.seed)
    sparse: str = "auto"               # auto | packed | dense
    max_prefills_per_tick: int = 1     # admission rate per scheduler tick
    decode_impl: str = "fused"         # fused (block-table flash kernel)
                                       # | reference (gather path, the
                                       #   bitwise oracle — DESIGN.md §11)
    prefill_chunk: Optional[int] = None  # tokens per prefill chunk; None =
                                         # eager one-shot prefill
    prefix_cache: bool = False         # radix prompt-prefix cache (requires
                                       # prefill_chunk — hits resume the
                                       # chunk executable mid-prompt)
    prefix_cache_blocks: Optional[int] = None  # cap on cached blocks

    @property
    def context_len(self) -> int:
        """Per-request context capacity (== the solo engine ``cache_len``
        that the token-identity anchor compares against)."""
        return self.block_size * self.max_blocks_per_request


class ContinuousBatcher:
    def __init__(self, model: ModelDef, params: Any,
                 cfg: BatchConfig = BatchConfig(),
                 executor: Optional[Any] = None):
        """``executor`` (distributed/executor.py) makes the batcher
        tensor-parallel: params place per the Megatron column/row rules
        and the paged KV pool takes its heads-sharded device layout (each
        "model" shard owns its attention heads' pages; the one all-reduce
        per block lands after wo/down — GSPMD inserts it from the
        shardings).  Host-side scheduling (admission, block tables,
        retirement) is unchanged, and the decoded tokens are pinned
        token-identical to the single-device batcher in
        tests/distributed_cases.py."""
        if model.paged_step is None or model.prefill is None:
            raise ValueError(
                f"family {model.cfg.family!r} has no paged serving path "
                f"(paged_step/prefill); the continuous batcher covers the "
                f"transformer families")
        if model.cfg.family == "vlm":
            raise ValueError(
                "vlm prefill needs per-request patch embeddings and Request "
                "carries none — serve VLMs through Engine.generate(extras=...)")
        if cfg.num_blocks < 2:
            raise ValueError("num_blocks must be >= 2 (block 0 is trash)")
        from repro.serve.engine import DECODE_IMPLS
        if cfg.decode_impl not in DECODE_IMPLS:
            raise ValueError(f"unknown decode_impl {cfg.decode_impl!r}; "
                             f"choices: {DECODE_IMPLS}")
        if cfg.prefill_chunk is not None:
            if cfg.prefill_chunk < 1:
                raise ValueError(f"prefill_chunk must be >= 1, got "
                                 f"{cfg.prefill_chunk}")
            if model.paged_prefill_chunk is None:
                raise ValueError(
                    f"family {model.cfg.family!r} has no chunked prefill "
                    f"path (paged_prefill_chunk)")
        if cfg.prefix_cache and cfg.prefill_chunk is None:
            raise ValueError(
                "prefix_cache requires prefill_chunk: cache hits resume the "
                "fixed-width chunk executable mid-prompt, and the eager "
                "prefill's numerics differ from the chunked path's")
        self.model, self.cfg = model, cfg
        self.executor = executor
        self.params, self.sparse_stats = prepare_serving_params(params, cfg.sparse)
        # accounting tree (self.params, may stay packed — serve_bench
        # meters its bytes) vs compute tree (packed.decode_view: identity
        # on one TPU, cached dense unpack on CPU or under a mesh)
        exec_params = packed_lib.decode_view(self.params,
                                             sharded=executor is not None)
        self.pool = kv_cache.BlockPool(cfg.num_blocks, cfg.block_size)
        self.pool_state = model.init_paged_state(cfg.num_blocks, cfg.block_size)
        if executor is not None:
            same = exec_params is self.params
            self.params = executor.shard_params(self.params)
            exec_params = self.params if same else \
                executor.shard_params(exec_params)
            self.pool_state = executor.shard_paged_pool(
                self.pool_state, model.cfg.num_kv_heads)
        self._exec_params = exec_params
        self._cache: Optional[PrefixCache] = (
            PrefixCache(self.pool, cfg.prefix_cache_blocks)
            if cfg.prefix_cache else None)

        S = cfg.slots
        self._tables = np.zeros((S, cfg.max_blocks_per_request), np.int32)
        self._pos = np.zeros((S,), np.int32)       # next write position
        self._token = np.zeros((S, 1), np.int32)   # last sampled token
        self._req_ids = np.zeros((S,), np.int32)
        self._tok_idx = np.zeros((S,), np.int32)   # sample index of next token
        self._temps = np.zeros((S,), np.float32)
        self._active = np.zeros((S,), bool)
        self._slot_req: List[Optional[Request]] = [None] * S
        self._emitted: List[List[int]] = [[] for _ in range(S)]
        self._emit_times: List[List[float]] = [[] for _ in range(S)]
        self._recv_times: List[List[float]] = [[] for _ in range(S)]
        self._meta: List[Dict[str, Any]] = [{} for _ in range(S)]
        # per-slot in-progress chunked prefill: {"table", "blocks", "done"}
        self._prefill: List[Optional[Dict[str, Any]]] = [None] * S
        self._reserved = 0                         # promised, unallocated blocks
        self._preempted: Dict[int, Dict[str, Any]] = {}  # rid -> saved state

        self.queue: Deque[Request] = deque()
        self.results: Dict[int, RequestResult] = {}
        self.stats = {"steps": 0, "prefills": 0, "prefill_tokens": 0,
                      "prefill_chunks": 0, "preemptions": 0, "resumes": 0,
                      "active_slot_steps": 0, "context_tokens": 0,
                      "step_walls": [],   # measured per-tick decode seconds
                      # host seconds by scheduler phase, each a disjoint
                      # share of run(): admission (first-token sampling
                      # left out), first-token sampling, prefill-chunk
                      # dispatch, and the decode tick's three phases;
                      # sample_first_wait_s is the part of sample_first_s
                      # spent blocked on the device (the last chunk and
                      # the sample), as tick_wait_s is the tick's
                      "admit_s": 0.0, "sample_first_s": 0.0,
                      "sample_first_wait_s": 0.0,
                      "prefill_dispatch_s": 0.0, "tick_prepare_s": 0.0,
                      "tick_wait_s": 0.0, "tick_emit_s": 0.0}
        self._t0 = time.monotonic()   # run()'s clock origin

        # serve-side SLO metrics (repro.obs): instruments are fetched ONCE
        # here behind enabled(), so the per-tick cost while disabled is a
        # single attribute check; recording only touches values the loop
        # already holds on the host (no extra device syncs — OBS001)
        self._obs = obs.enabled()
        if self._obs:
            reg = obs.registry()
            self._m_ttft = reg.histogram("serve.ttft_s",
                                         obs.LATENCY_BUCKETS_S)
            self._m_itl = reg.histogram("serve.inter_token_s",
                                        obs.LATENCY_BUCKETS_S)
            self._m_wait = reg.histogram("serve.admission_wait_s",
                                         obs.LATENCY_BUCKETS_S)
            self._m_step = reg.histogram("serve.step_s",
                                         obs.LATENCY_BUCKETS_S)
            self._m_queue = reg.histogram("serve.queue_depth",
                                          obs.COUNT_BUCKETS)
            self._m_occ = reg.histogram("serve.pool_occupancy",
                                        obs.FRACTION_BUCKETS)
            self._c_decode_steps = reg.counter("serve.decode_steps")
            self._c_prefills = reg.counter("serve.prefills")
            self._c_prefill_tokens = reg.counter("serve.prefill_tokens")
            self._c_prefill_chunks = reg.counter("serve.prefill_chunks")
            self._c_decode_tokens = reg.counter("serve.decode_tokens")
            self._c_defrags = reg.counter("serve.defrags")
            self._c_defrag_blocks = reg.counter("serve.defrag_blocks_moved")
            self._c_preemptions = reg.counter("serve.preemptions")
            self._c_prefix_hits = reg.counter("serve.prefix_hits")
            self._c_prefix_misses = reg.counter("serve.prefix_misses")
            self._c_prefix_hit_tokens = reg.counter("serve.prefix_hit_tokens")
            self._c_prefix_evicted = reg.counter("serve.prefix_evicted_blocks")
            # per-priority admission-wait histograms bind lazily (one per
            # priority class ever seen) in _wait_hist; buffered waits are
            # flushed once per tick from _record_tick_obs
            self._m_wait_prio: Dict[int, Any] = {}
            self._obs_flushed = {"prefill_chunks": 0, "preemptions": 0,
                                 "hits": 0, "misses": 0, "hit_tokens": 0,
                                 "evicted": 0}
            self._pend_waits: List[Tuple[int, float]] = []
            self._pend_itl: List[float] = []   # gaps of recv_times

        def step(params, pool, tables, pos, token, req_ids, tok_idx, active,
                 temps):
            logits, pool = model.paged_step(params, pool, tables, token, pos,
                                            active, cfg.block_size,
                                            impl=cfg.decode_impl)
            logits = logits[:, -1, :].astype(jnp.float32)
            if executor is not None:
                # sampling must see replicated logits (see
                # MeshExecutor.replicate_logits) or TP temperature draws
                # diverge from the single-device path
                logits = executor.replicate_logits(logits)
            keys = sampling.step_keys(sampling.request_keys(cfg.seed, req_ids),
                                      tok_idx)
            return sampling.sample(logits, keys, temps)[:, None], pool

        self._step_fn = jax.jit(step, donate_argnums=(1,))

        if cfg.prefill_chunk is not None:
            def chunk_step(params, pool, table, tokens, pos0, n_valid):
                return model.paged_prefill_chunk(params, pool, table, tokens,
                                                 pos0, n_valid, cfg.block_size)

            # one executable for every chunk of every prompt: chunk width,
            # table width, and pool shape are fixed; offset/valid-count are
            # traced scalars (tests pin _chunk_fn._cache_size() == 1)
            self._chunk_fn = jax.jit(chunk_step, donate_argnums=(1,))

    # ------------------------------------------------------------------
    # submission / admission
    # ------------------------------------------------------------------
    def _blocks_needed(self, r: Request) -> int:
        return -(-(len(r.prompt) + r.max_new_tokens) // self.cfg.block_size)

    @staticmethod
    def _prio_key(r: Request) -> Tuple[float, float, float, int]:
        return (r.priority,
                r.deadline if r.deadline is not None else math.inf,
                r.arrival, r.id)

    def submit(self, request: Request) -> None:
        P, n = len(request.prompt), request.max_new_tokens
        if P < 1:
            raise ValueError(f"request {request.id}: empty prompt")
        if n < 1:
            raise ValueError(f"request {request.id}: max_new_tokens must be "
                             f">= 1, got {n}")
        limit = min(self.cfg.context_len, self.model.cfg.max_seq)
        if P + n > limit:
            raise ValueError(
                f"request {request.id}: prompt_len + max_new_tokens = {P + n} "
                f"exceeds the serving context ({self.cfg.context_len}) or the "
                f"model's max_seq ({self.model.cfg.max_seq})")
        if self._blocks_needed(request) > self.cfg.num_blocks - 1:
            raise kv_cache.PoolExhausted(
                f"request {request.id} needs {self._blocks_needed(request)} "
                f"blocks; the pool only has {self.cfg.num_blocks - 1}")
        if request.id in self.results or any(
                q.id == request.id for q in self.queue) or any(
                r is not None and r.id == request.id for r in self._slot_req):
            raise ValueError(f"duplicate request id {request.id}")
        self.queue.append(request)

    def _free_slot(self) -> Optional[int]:
        for s in range(self.cfg.slots):
            if self._slot_req[s] is None:
                return s
        return None

    def _head(self, now: float) -> Optional[Request]:
        """Most urgent arrived request: min (priority, deadline, arrival,
        id).  Strict head-of-line — nothing bypasses it."""
        best = None
        for r in self.queue:
            if r.arrival > now:
                continue
            if best is None or self._prio_key(r) < self._prio_key(best):
                best = r
        return best

    def _admit(self, now: float) -> int:
        """SLA-aware admission: prefill (or resume) the most urgent
        arrived request while a slot and its actual block need — the
        worst case minus prefix-cache-matched blocks — are available,
        evicting cache blocks and preempting strictly-lower-priority
        actives to make room."""
        t0, sampled0 = time.perf_counter(), self.stats["sample_first_s"]
        admitted = 0
        with obs.span("serve.admit"):
            while admitted < self.cfg.max_prefills_per_tick:
                r = self._head(now)
                if r is None:
                    break
                need = self._blocks_needed(r)
                saved = self._preempted.get(r.id)
                # resume copies its saved K/V into fresh blocks, so it
                # draws its full need from the free list; a fresh request
                # re-uses matched prefix blocks in place
                matched_blocks = 0
                if saved is None and self._cache is not None:
                    matched_blocks = (self._cache.match_tokens(r.prompt)
                                      // self.cfg.block_size)
                need_free = need - matched_blocks
                if not self._make_room(r, need_free, now):
                    break                  # head-of-line waits for room
                slot = self._free_slot()
                self.queue.remove(r)
                if saved is not None:
                    del self._preempted[r.id]
                    self._resume_into(slot, r, saved, need, now)
                elif self.cfg.prefill_chunk is not None:
                    self._begin_chunked_prefill(slot, r, need, now)
                else:
                    self._prefill_into(slot, r, need, now)
                admitted += 1
        # the eager path samples first tokens here; sample_first_s has them
        self.stats["admit_s"] += time.perf_counter() - t0 - (
            self.stats["sample_first_s"] - sampled0)
        return admitted

    def _make_room(self, r: Request, need_free: int, now: float) -> bool:
        """Free a slot + ``need_free`` blocks for ``r``: LRU-evict
        cache-only blocks first, then preempt active requests of
        strictly lower priority (worst first).  Returns True iff ``r``
        can be admitted now."""
        while True:
            short = need_free - (self.pool.num_free - self._reserved)
            if short > 0 and self._cache is not None \
                    and self._cache.evict(short) > 0:
                continue
            if self._free_slot() is not None and \
                    self.pool.num_free - self._reserved >= need_free:
                return True
            victim = self._preemption_victim(r)
            if victim is None:
                return False
            self._preempt(victim, now)

    def _preemption_victim(self, r: Request) -> Optional[int]:
        """Least-urgent *active* slot whose priority is strictly worse
        than ``r``'s (prefilling slots finish; equal priority never
        preempts — no livelock)."""
        worst = None
        for s in range(self.cfg.slots):
            q = self._slot_req[s]
            if q is None or not self._active[s] or q.priority <= r.priority:
                continue
            if worst is None or \
                    self._prio_key(q) > self._prio_key(self._slot_req[worst]):
                worst = s
        return worst

    def _prefill_into(self, slot: int, r: Request, need: int, now: float) -> None:
        cfg, P = self.cfg, len(r.prompt)
        n0 = max(1, -(-P // cfg.block_size))
        blocks = self.pool.alloc(r.id, n0)
        self._reserved += need - n0
        prompt = jnp.asarray(np.asarray(r.prompt, np.int32)[None, :])
        # eager, exact-length prefill: identical values to the solo
        # engine's (prefill K/V and logits do not depend on cache width)
        with obs.span("serve.prefill", req=r.id, tokens=P):
            logits, kv = self.model.prefill(self._exec_params, prompt, P, None)
        flat = kv_cache.flat_slots(blocks, P, cfg.block_size)
        self.pool_state = kv_cache.scatter_prefill(
            self.pool_state, {k: v[:, 0] for k, v in kv.items()}, flat)
        first = self._sample_first(logits, r)
        recv = self._clock()
        self.stats["prefills"] += 1
        self.stats["prefill_tokens"] += P
        if self._obs:
            self._m_wait.observe(max(now - r.arrival, 0.0))
            self._m_ttft.observe(max(recv - r.arrival, 0.0))
            self._pend_waits.append((r.priority, max(now - r.arrival, 0.0)))
            self._c_prefills.inc()
            self._c_prefill_tokens.inc(P)

        self._tables[slot] = kv_cache.table_row(blocks,
                                                cfg.max_blocks_per_request)
        self._pos[slot] = P
        self._token[slot, 0] = int(first[0])
        self._req_ids[slot] = r.id
        self._tok_idx[slot] = 1
        self._temps[slot] = r.temperature
        self._active[slot] = True
        self._slot_req[slot] = r
        self._emitted[slot] = [int(first[0])]
        self._emit_times[slot] = [now]
        self._recv_times[slot] = [recv]
        self._meta[slot] = {"admitted": now, "first_token": now,
                            "admitted_step": self.stats["steps"],
                            "need": need, "hit_tokens": 0, "preemptions": 0}
        self._maybe_finish(slot, now)

    def _sample_first(self, logits: jnp.ndarray, r: Request) -> np.ndarray:
        """Sample a request's first token from its prefill logits with the
        same folded key the decode step would use at index 0, up to the
        host's receipt of it."""
        t0 = time.perf_counter()
        with obs.span("serve.sample_first", req=r.id):
            keys0 = sampling.step_keys(
                sampling.request_keys(self.cfg.seed,
                                      jnp.asarray([r.id], jnp.int32)), 0)
            first_logits = logits[:, -1, :].astype(jnp.float32)
            if self.executor is not None:
                first_logits = self.executor.replicate_logits(first_logits)
            first = sampling.sample(first_logits, keys0, r.temperature)
            t_wait = time.perf_counter()
            first = np.asarray(first)
        t1 = time.perf_counter()
        self.stats["sample_first_s"] += t1 - t0
        self.stats["sample_first_wait_s"] += t1 - t_wait
        return first

    # ------------------------------------------------------------------
    # chunked prefill
    # ------------------------------------------------------------------
    def _begin_chunked_prefill(self, slot: int, r: Request, need: int,
                               now: float) -> None:
        """Claim a slot and the prompt's blocks; prefix-cache hits adopt
        the matched blocks (read-only) and skip their chunks.  The slot
        stays decode-inactive until the last chunk lands."""
        cfg, P = self.cfg, len(r.prompt)
        hit_blocks, matched = [], 0
        if self._cache is not None:
            hit_blocks, matched = self._cache.acquire(r.id, r.prompt)
        n_own = max(1, -(-P // cfg.block_size)) - len(hit_blocks)
        own = self.pool.alloc(r.id, n_own)
        self._reserved += need - len(hit_blocks) - n_own
        blocks = hit_blocks + own
        self._req_ids[slot] = r.id
        self._temps[slot] = r.temperature
        self._slot_req[slot] = r
        self._emitted[slot] = []
        self._emit_times[slot] = []
        self._recv_times[slot] = []
        # the slot's live table row stays TRASH until activation — the
        # decode step writes unconditionally per slot, and only the trash
        # block may absorb writes for not-yet-active slots
        self._prefill[slot] = {
            "table": kv_cache.table_row(blocks, cfg.max_blocks_per_request),
            "blocks": blocks, "done": matched}
        self._meta[slot] = {"admitted": now, "first_token": now,
                            "admitted_step": self.stats["steps"],
                            "need": need, "hit_tokens": matched,
                            "preemptions": 0}
        if self._obs:
            self._m_wait.observe(max(now - r.arrival, 0.0))
            self._pend_waits.append((r.priority, max(now - r.arrival, 0.0)))

    def _prefill_tick(self, now: float) -> bool:
        """Run ONE prefill chunk for the most urgent prefilling slot.
        One chunk per scheduler tick is the ITL bound: decode ticks are
        never delayed by more than one chunk's latency."""
        best = None
        for s in range(self.cfg.slots):
            if self._prefill[s] is None:
                continue
            if best is None or self._prio_key(self._slot_req[s]) < \
                    self._prio_key(self._slot_req[best]):
                best = s
        if best is None:
            return False
        self._prefill_chunk_step(best, now)
        return True

    def _prefill_chunk_step(self, slot: int, now: float) -> None:
        cfg, st, r = self.cfg, self._prefill[slot], self._slot_req[slot]
        P, C = len(r.prompt), cfg.prefill_chunk
        o = st["done"]
        n_valid = min(C, P - o)
        toks = np.zeros((1, C), np.int32)
        toks[0, :n_valid] = np.asarray(r.prompt, np.int32)[o:o + n_valid]
        t0 = time.perf_counter()
        with obs.span("serve.prefill_chunk", req=r.id, offset=o,
                      tokens=n_valid):
            logits, self.pool_state = self._chunk_fn(
                self._exec_params, self.pool_state,
                jnp.asarray(st["table"]), jnp.asarray(toks),
                jnp.int32(o), jnp.int32(n_valid))
        self.stats["prefill_dispatch_s"] += time.perf_counter() - t0
        st["done"] = o + n_valid
        self.stats["prefill_chunks"] += 1
        self.stats["prefill_tokens"] += n_valid
        if st["done"] >= P:
            self._activate_prefilled(slot, logits, now)

    def _activate_prefilled(self, slot: int, logits: jnp.ndarray,
                            now: float) -> None:
        """Last chunk landed: sample the first token, cache the prompt's
        full blocks, flip the slot decode-active."""
        cfg, st, r = self.cfg, self._prefill[slot], self._slot_req[slot]
        P = len(r.prompt)
        first = self._sample_first(logits, r)
        recv = self._clock()
        self.stats["prefills"] += 1
        if self._obs:
            self._m_ttft.observe(max(recv - r.arrival, 0.0))
            self._c_prefills.inc()
            self._c_prefill_tokens.inc(P)
        if self._cache is not None:
            self._cache.insert(r.prompt, st["blocks"][:P // cfg.block_size])
        self._tables[slot] = st["table"]
        self._pos[slot] = P
        self._token[slot, 0] = int(first[0])
        self._tok_idx[slot] = 1
        self._active[slot] = True
        self._emitted[slot] = [int(first[0])]
        self._emit_times[slot] = [now]
        self._recv_times[slot] = [recv]
        self._meta[slot]["first_token"] = now
        self._prefill[slot] = None
        self._maybe_finish(slot, now)

    # ------------------------------------------------------------------
    # preemption / resume
    # ------------------------------------------------------------------
    def _preempt(self, slot: int, now: float) -> None:
        """Evict an active request: copy its written K/V rows to the
        host, free its blocks, re-queue it.  Resume restores the rows
        via ``scatter_prefill`` (an identity cast for pool-dtype data),
        so the decode continues bitwise-exactly where it stopped."""
        r = self._slot_req[slot]
        pos = int(self._pos[slot])
        blocks = self.pool.blocks_of(r.id)
        flat = kv_cache.flat_slots(blocks, pos, self.cfg.block_size)
        with obs.span("serve.preempt", req=r.id, tokens=pos):
            kv = {name: np.asarray(self.pool_state[name][:, flat])
                  for name in self.pool_state}
        meta = dict(self._meta[slot])
        meta["preemptions"] = meta.get("preemptions", 0) + 1
        self._preempted[r.id] = {
            "pos": pos, "token": int(self._token[slot, 0]),
            "tok_idx": int(self._tok_idx[slot]),
            "emitted": list(self._emitted[slot]),
            "emit_times": list(self._emit_times[slot]),
            "recv_times": list(self._recv_times[slot]),
            "kv": kv, "meta": meta}
        self._reserved -= meta["need"] - len(blocks)
        self.pool.free_request(r.id)
        self._active[slot] = False
        self._tables[slot] = kv_cache.TRASH_BLOCK
        self._pos[slot] = 0
        self._slot_req[slot] = None
        self._emitted[slot] = []
        self._emit_times[slot] = []
        self._recv_times[slot] = []
        self.queue.append(r)
        self.stats["preemptions"] += 1
        log.debug("preempted request %d at pos %d", r.id, pos)

    def _resume_into(self, slot: int, r: Request, saved: Dict[str, Any],
                     need: int, now: float) -> None:
        cfg = self.cfg
        pos = saved["pos"]
        n0 = max(1, -(-pos // cfg.block_size))
        blocks = self.pool.alloc(r.id, n0)
        self._reserved += need - n0
        flat = kv_cache.flat_slots(blocks, pos, cfg.block_size)
        self.pool_state = kv_cache.scatter_prefill(self.pool_state,
                                                   saved["kv"], flat)
        self._tables[slot] = kv_cache.table_row(blocks,
                                                cfg.max_blocks_per_request)
        self._pos[slot] = pos
        self._token[slot, 0] = saved["token"]
        self._req_ids[slot] = r.id
        self._tok_idx[slot] = saved["tok_idx"]
        self._temps[slot] = r.temperature
        self._active[slot] = True
        self._slot_req[slot] = r
        self._emitted[slot] = list(saved["emitted"])
        self._emit_times[slot] = list(saved["emit_times"])
        self._recv_times[slot] = list(saved["recv_times"])
        meta = dict(saved["meta"])
        meta["need"] = need
        self._meta[slot] = meta
        self.stats["resumes"] += 1
        log.debug("resumed request %d at pos %d", r.id, pos)

    # ------------------------------------------------------------------
    # decode loop
    # ------------------------------------------------------------------
    def _grow_blocks(self) -> None:
        """Lazy allocation: a slot about to write position ``pos`` needs
        block ``pos // block_size``; admission reserved it, so this alloc
        cannot fail."""
        for slot in range(self.cfg.slots):
            if not self._active[slot]:
                continue
            r = self._slot_req[slot]
            need_idx = int(self._pos[slot]) // self.cfg.block_size
            have = len(self.pool.blocks_of(r.id))
            if need_idx >= have:
                new = self.pool.alloc(r.id, need_idx - have + 1)
                self._reserved -= len(new)
                self._tables[slot, have:have + len(new)] = new

    def _tick(self, now: float) -> None:
        """One jitted decode step over all slots + host-side bookkeeping,
        in three phases, each a span and a ``stats`` counter: prepare
        (block growth, uploads, dispatch), wait (the host blocked until
        the sampled token arrives), emit (append, stamp and retire)."""
        st = self.stats
        with obs.step_span("serve.tick", st["steps"]):
            t0 = time.perf_counter()
            with obs.span("serve.tick.prepare"):
                self._grow_blocks()
                t_step = time.perf_counter()
                token, self.pool_state = self._step_fn(
                    self._exec_params, self.pool_state,
                    jnp.asarray(self._tables), jnp.asarray(self._pos),
                    jnp.asarray(self._token), jnp.asarray(self._req_ids),
                    jnp.asarray(self._tok_idx), jnp.asarray(self._active),
                    jnp.asarray(self._temps))
            t1 = time.perf_counter()
            with obs.span("serve.tick.wait"):
                token = np.asarray(token)   # device sync: the step finished
            t2 = time.perf_counter()
            recv = self._clock()
            with obs.span("serve.tick.emit"):
                st["step_walls"].append(t2 - t_step)
                st["steps"] += 1
                n_active = int(self._active.sum())
                st["active_slot_steps"] += n_active
                st["context_tokens"] += int(
                    (self._pos[self._active] + 1).sum())
                for slot in range(self.cfg.slots):
                    if not self._active[slot]:
                        continue
                    self._emitted[slot].append(int(token[slot, 0]))
                    self._emit_times[slot].append(now)
                    if self._obs:
                        self._pend_itl.append(
                            recv - self._recv_times[slot][-1])
                    self._recv_times[slot].append(recv)
                    self._token[slot] = token[slot]
                    self._pos[slot] += 1
                    self._tok_idx[slot] += 1
                    self._maybe_finish(slot, now)
                if self._obs:
                    self._record_tick_obs(n_active)
            t3 = time.perf_counter()
        st["tick_prepare_s"] += t1 - t0
        st["tick_wait_s"] += t2 - t1
        st["tick_emit_s"] += t3 - t2

    def _record_tick_obs(self, n_active: int) -> None:
        """Per-tick SLO recordings: everything here is host state the
        decode loop already computed (the token sync in ``_tick`` is the
        baseline sync, not one obs added).  Kept as ONE method so
        ``benchmarks/serve_bench.bench_obs_overhead`` can time the exact
        recording sequence the loop runs to derive its overhead gate.
        Scheduler-event counters (chunks, preemptions, cache traffic)
        flush as per-tick deltas against ``stats`` — one ``inc`` per
        instrument per tick regardless of event volume."""
        self._m_step.observe(self.stats["step_walls"][-1])
        self._m_queue.observe(len(self.queue))
        self._m_occ.observe(self.pool.num_live
                            / max(self.cfg.num_blocks - 1, 1))
        self._c_decode_steps.inc()
        self._c_decode_tokens.inc(n_active)
        self._flush_delta(self._c_prefill_chunks, "prefill_chunks",
                          self.stats["prefill_chunks"])
        self._flush_delta(self._c_preemptions, "preemptions",
                          self.stats["preemptions"])
        if self._cache is not None:
            self._flush_delta(self._c_prefix_hits, "hits", self._cache.hits)
            self._flush_delta(self._c_prefix_misses, "misses",
                              self._cache.misses)
            self._flush_delta(self._c_prefix_hit_tokens, "hit_tokens",
                              self._cache.hit_tokens)
            self._flush_delta(self._c_prefix_evicted, "evicted",
                              self._cache.evicted_blocks)
        self._flush_waits()
        self._m_itl.observe_many(self._pend_itl)   # one gap per slot
        self._pend_itl.clear()

    def _flush_delta(self, counter: Any, key: str, total: int) -> None:
        d = total - self._obs_flushed[key]
        if d:
            counter.inc(d)
            self._obs_flushed[key] = total

    def _wait_hist(self, priority: int) -> Any:
        """Per-priority admission-wait histogram, bound once per class."""
        h = self._m_wait_prio.get(priority)
        if h is None:
            h = obs.registry().histogram(
                f"serve.admission_wait_s.p{priority}", obs.LATENCY_BUCKETS_S)
            self._m_wait_prio[priority] = h
        return h
    def _flush_waits(self) -> None:
        # bounded by max_prefills_per_tick admissions per tick — this is
        # a per-tick flush of already-buffered host floats, not a
        # per-token recording
        for prio, wait in self._pend_waits:
            self._wait_hist(prio).observe(wait)
        self._pend_waits.clear()

    def _maybe_finish(self, slot: int, now: float) -> None:
        r = self._slot_req[slot]
        toks = self._emitted[slot]
        reason = None
        if r.eos_id is not None and toks and toks[-1] == r.eos_id:
            reason = "eos"
        elif len(toks) >= r.max_new_tokens:
            reason = "length"
        if reason is None:
            return
        meta = self._meta[slot]
        self._reserved -= meta["need"] - len(self.pool.blocks_of(r.id))
        self.pool.free_request(r.id)
        self._active[slot] = False
        self._tables[slot] = kv_cache.TRASH_BLOCK
        self._pos[slot] = 0
        self._slot_req[slot] = None
        self.results[r.id] = RequestResult(
            id=r.id, tokens=np.asarray(toks, np.int32), reason=reason,
            prompt_len=len(r.prompt), arrival=r.arrival,
            admitted=meta["admitted"], first_token=meta["first_token"],
            finished=now, admitted_step=meta["admitted_step"],
            finished_step=self.stats["steps"], priority=r.priority,
            prefix_hit_tokens=meta.get("hit_tokens", 0),
            preemptions=meta.get("preemptions", 0),
            token_times=np.asarray(self._emit_times[slot], np.float64),
            recv_times=np.asarray(self._recv_times[slot], np.float64))

    # ------------------------------------------------------------------
    # driver
    # ------------------------------------------------------------------
    def _clock(self) -> float:
        """Seconds since run() started: the clock of arrivals and stamps."""
        return time.monotonic() - self._t0

    def _busy(self) -> bool:
        return bool(self._active.any()) or \
            any(p is not None for p in self._prefill)

    def run(self, requests: Optional[List[Request]] = None
            ) -> List[RequestResult]:
        """Serve every submitted request to completion (trace-driven: a
        request with ``arrival > now`` waits).  Returns results by id."""
        for r in requests or ():
            self.submit(r)
        self._t0 = time.monotonic()
        while self.queue or self._busy():
            now = self._clock()
            if not self._busy() and self.queue and \
                    all(r.arrival > now for r in self.queue):
                self._await_arrival(now)
                continue
            admitted = self._admit(now)
            prefilled = self._prefill_tick(self._clock())
            if self._active.any():
                self._tick(self._clock())
            elif not admitted and not prefilled:
                # nothing running and the head could not be admitted:
                # with no sharers left every cache block is evictable and
                # submit() bounds need to the pool size, so this is a
                # scheduler accounting bug — fail loudly, don't spin
                raise RuntimeError(
                    f"scheduler stall: {len(self.queue)} queued, "
                    f"{self.pool.num_free} free blocks, "
                    f"{self._reserved} reserved")
        return [self.results[i] for i in sorted(self.results)]

    def _await_arrival(self, now: float) -> None:
        """Open loop, nothing in flight: sleep toward the next arrival."""
        with obs.span("serve.await_arrival"):
            soonest = min(r.arrival for r in self.queue)
            time.sleep(min(soonest - now, 0.05))

    def defrag(self) -> int:
        """Compact live blocks to the low end of the pool; returns the
        number of blocks moved.  Safe between ticks: tables of active
        and prefilling slots — and the prefix cache's node ids — are
        rewritten from the allocator's remapped state."""
        remap = self.pool.defrag()
        if self._obs:
            self._c_defrags.inc()
            self._c_defrag_blocks.inc(len(remap))
        if not remap:
            return 0
        self.pool_state = kv_cache.apply_defrag(
            self.pool_state, remap, self.cfg.num_blocks, self.cfg.block_size)
        if self._cache is not None:
            self._cache.apply_defrag(remap)
        for slot, r in enumerate(self._slot_req):
            if r is None:
                continue
            row = kv_cache.table_row(self.pool.blocks_of(r.id),
                                     self.cfg.max_blocks_per_request)
            if self._prefill[slot] is not None:
                self._prefill[slot]["table"] = row
                self._prefill[slot]["blocks"] = self.pool.blocks_of(r.id)
            else:
                self._tables[slot] = row
        return len(remap)


def synthetic_trace(num_requests: int, rate: float, vocab: int,
                    prompt_len: tuple = (8, 16), max_new_tokens: int = 16,
                    temperature: float = 0.0, eos_id: Optional[int] = None,
                    seed: int = 0, priorities: int = 1,
                    deadline_s: Optional[float] = None,
                    shared_prefix_len: int = 0) -> List[Request]:
    """Poisson(rate) arrival trace with uniform prompt lengths — the
    synthetic load for ``launch/serve.py`` and ``benchmarks/serve_bench``.
    ``rate <= 0`` means every request arrives at t=0 (closed-loop
    pressure).  ``priorities > 1`` assigns each request a uniform random
    priority class in ``[0, priorities)``; ``deadline_s`` gives every
    request ``arrival + deadline_s`` as its deadline.
    ``shared_prefix_len > 0`` prepends one common system-prompt prefix to
    every prompt (the prefix-cache traffic shape); ``prompt_len`` then
    sizes the per-request tail."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, vocab, size=shared_prefix_len).astype(np.int32)
    t, reqs = 0.0, []
    for i in range(num_requests):
        if rate > 0:
            t += float(rng.exponential(1.0 / rate))
        P = int(rng.integers(prompt_len[0], prompt_len[1] + 1))
        tail = rng.integers(0, vocab, size=P).astype(np.int32)
        prompt = np.concatenate([prefix, tail]) if shared_prefix_len else tail
        reqs.append(Request(
            id=i, prompt=prompt, max_new_tokens=max_new_tokens,
            temperature=temperature, eos_id=eos_id, arrival=t,
            priority=int(rng.integers(0, priorities)) if priorities > 1 else 0,
            deadline=None if deadline_s is None else t + deadline_s))
    return reqs
