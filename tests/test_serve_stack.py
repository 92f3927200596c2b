"""Continuous-batching serving stack (serve/batcher.py + paged KV pool).

The load-bearing property is the token-identity anchor: for every
request in a mixed-length trace, continuous-batched output must equal a
solo static ``Engine.generate`` of the same prompt — dense and
2:4-packed, greedy and temperature-sampled — with the decode step jitted
exactly once (joins and retirements never re-specialize).  Plus the
engine regressions this PR fixes: position overrun validation and
per-request (not per-call) sampling PRNG.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import VLMConfig
from repro.configs.opt125m_proxy import tiny_config
from repro.core.sparsity import round_tree_nm, satisfies, SparsitySpec
from repro.models.registry import load_arch, model_def
from repro.serve import (BatchConfig, ContinuousBatcher, Engine, PoolExhausted,
                         Request, ServeConfig, synthetic_trace)

#: the anchor compares against a solo engine whose cache width equals the
#: batcher's per-request context (same masked-softmax reduction widths)
BC = BatchConfig(slots=3, block_size=8, max_blocks_per_request=4,
                 num_blocks=16)

#: chunked-prefill + prefix-cache variant of the same serving shape
import dataclasses as _dc
CBC = _dc.replace(BC, prefill_chunk=8, prefix_cache=True)


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config().replace(num_layers=2, d_model=64, d_ff=128,
                                num_heads=4, num_kv_heads=4, vocab=128)
    model = model_def(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _mixed_requests(vocab, temperature=0.0):
    rng = np.random.default_rng(3)
    spec = [(5, 6), (9, 4), (3, 8), (12, 5), (7, 7)]   # 5 requests > 3 slots
    return [Request(id=i, prompt=rng.integers(0, vocab, size=p).astype(np.int32),
                    max_new_tokens=n, temperature=temperature)
            for i, (p, n) in enumerate(spec)]


def _solo_generate(model, params, r, temperature=0.0, sparse="auto"):
    eng = Engine(model, params, ServeConfig(cache_len=BC.context_len,
                                            temperature=temperature,
                                            sparse=sparse))
    return eng.generate(jnp.asarray(r.prompt[None, :]),
                        max_new_tokens=r.max_new_tokens,
                        request_ids=[r.id])[0]


class TestTokenIdentity:
    def test_dense_mixed_lengths(self, tiny):
        model, params = tiny
        reqs = _mixed_requests(model.cfg.vocab)
        batcher = ContinuousBatcher(model, params, BC)
        results = batcher.run(list(reqs))
        assert [r.id for r in results] == [r.id for r in reqs]
        for req, res in zip(reqs, results):
            np.testing.assert_array_equal(
                res.tokens, _solo_generate(model, params, req),
                err_msg=f"request {req.id} diverged from solo generate")
            assert res.reason == "length"
        # joins and retirements never re-specialized the decode step
        assert batcher._step_fn._cache_size() == 1

    def test_packed_24_checkpoint(self, tiny):
        model, params = tiny
        sparse = round_tree_nm(params)
        assert satisfies(np.asarray(sparse["layers"]["attn"]["wq"][0]).T,
                         SparsitySpec(kind="nm", n=2, m=4))
        reqs = _mixed_requests(model.cfg.vocab)
        batcher = ContinuousBatcher(model, sparse, BC)
        assert batcher.sparse_stats["mode"] == "packed"
        results = batcher.run(list(reqs))
        for req, res in zip(reqs, results):
            np.testing.assert_array_equal(
                res.tokens, _solo_generate(model, sparse, req))

    def test_temperature_sampling(self, tiny):
        model, params = tiny
        reqs = _mixed_requests(model.cfg.vocab, temperature=0.7)
        results = ContinuousBatcher(model, params, BC).run(list(reqs))
        for req, res in zip(reqs, results):
            np.testing.assert_array_equal(
                res.tokens, _solo_generate(model, params, req, temperature=0.7))

    def test_windowed_moe_arch(self):
        """Sliding-window + MoE (mixtral smoke, window=16): the paged
        window mask must agree with the solo engine past the window."""
        d = load_arch("mixtral-8x7b", smoke=True)
        params = d.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(11)
        reqs = [Request(id=i, prompt=rng.integers(0, d.cfg.vocab, size=p)
                        .astype(np.int32), max_new_tokens=n)
                for i, (p, n) in enumerate([(14, 8), (10, 6), (18, 8)])]
        results = ContinuousBatcher(d, params, BC).run(list(reqs))
        for req, res in zip(reqs, results):
            np.testing.assert_array_equal(
                res.tokens, _solo_generate(d, params, req),
                err_msg=f"windowed request {req.id} diverged")

    def test_eos_retires_early(self, tiny):
        model, params = tiny
        base = _mixed_requests(model.cfg.vocab)[0]
        solo = _solo_generate(model, params, base)
        eos = int(solo[2])                   # force an early EOS hit
        cut = int(np.argmax(solo == eos))    # first occurrence
        req = Request(id=base.id, prompt=base.prompt,
                      max_new_tokens=base.max_new_tokens, eos_id=eos)
        res = ContinuousBatcher(model, params, BC).run([req])[0]
        assert res.reason == "eos"
        np.testing.assert_array_equal(res.tokens, solo[:cut + 1])


class TestScheduler:
    def test_pool_pressure_serializes(self, tiny):
        """A pool too small for two concurrent requests still serves all
        of them correctly — pressure queues, it never corrupts."""
        model, params = tiny
        cfg = BatchConfig(slots=2, block_size=8, max_blocks_per_request=4,
                          num_blocks=4)      # 3 allocatable blocks
        reqs = _mixed_requests(model.cfg.vocab)[:3]   # each needs 2-3 blocks
        results = ContinuousBatcher(model, params, cfg).run(list(reqs))
        assert len(results) == 3
        for req, res in zip(reqs, results):
            np.testing.assert_array_equal(
                res.tokens, _solo_generate(model, params, req))

    def test_defrag_between_ticks(self, tiny):
        """Defragmenting the pool mid-flight (blocks move, tables rewrite)
        must not change a single token."""
        model, params = tiny
        reqs = _mixed_requests(model.cfg.vocab)
        batcher = ContinuousBatcher(model, params, BC)
        for r in reqs:
            batcher.submit(r)
        while batcher.queue or batcher._active.any():
            batcher._admit(0.0)
            if batcher._active.any():
                batcher._tick(0.0)
            batcher.defrag()
        for req in reqs:
            np.testing.assert_array_equal(
                batcher.results[req.id].tokens,
                _solo_generate(model, params, req))

    def test_submit_validation(self, tiny):
        model, params = tiny
        batcher = ContinuousBatcher(model, params, BC)
        long = Request(id=0, prompt=np.zeros(30, np.int32), max_new_tokens=8)
        with pytest.raises(ValueError, match="serving context|max_seq"):
            batcher.submit(long)           # 38 > context_len 32
        batcher.submit(Request(id=1, prompt=np.zeros(4, np.int32)))
        with pytest.raises(ValueError, match="duplicate"):
            batcher.submit(Request(id=1, prompt=np.zeros(4, np.int32)))
        small = ContinuousBatcher(model, params,
                                  BatchConfig(slots=1, block_size=4,
                                              max_blocks_per_request=8,
                                              num_blocks=3))
        with pytest.raises(PoolExhausted):
            small.submit(Request(id=2, prompt=np.zeros(8, np.int32),
                                 max_new_tokens=8))

    def test_unsupported_family_raises(self):
        d = load_arch("mamba2-780m", smoke=True)
        with pytest.raises(ValueError, match="paged serving"):
            ContinuousBatcher(d, params=None)
        # vlm inherits the transformer paged step but Request carries no
        # patch extras — silently serving text-only would be wrong output
        with pytest.raises(ValueError, match="patch"):
            ContinuousBatcher(load_arch("internvl2-2b", smoke=True),
                              params=None)

    def test_synthetic_trace_shape(self):
        trace = synthetic_trace(8, rate=4.0, vocab=64, prompt_len=(4, 6),
                                max_new_tokens=5, seed=1)
        assert [r.id for r in trace] == list(range(8))
        assert all(4 <= len(r.prompt) <= 6 for r in trace)
        arr = [r.arrival for r in trace]
        assert arr == sorted(arr) and arr[0] > 0


class TestPagedBitwise:
    def test_paged_attention_matches_contiguous(self):
        """Deterministic pin of the paged-read contract (the hypothesis
        sweep lives in tests/test_kv_pool.py, an optional dep): paged
        decode attention == contiguous-cache decode attention, bitwise,
        at ragged per-slot positions."""
        from repro.models import common
        from repro.serve.kv_cache import BlockPool, flat_slots, scatter_prefill
        cfg = tiny_config().replace(num_layers=1, d_model=16, num_heads=2,
                                    num_kv_heads=2, vocab=32)
        p = common.attn_init(cfg, jax.random.PRNGKey(2))
        rng = np.random.default_rng(2)
        S, W, BS, nkv, hd = 3, 16, 4, 2, cfg.resolved_head_dim()
        x = jnp.asarray(rng.standard_normal((S, 1, cfg.d_model)), jnp.float32)
        ck = jnp.asarray(rng.standard_normal((S, W, nkv, hd)), jnp.float32)
        cv = jnp.asarray(rng.standard_normal((S, W, nkv, hd)), jnp.float32)
        pos = np.asarray([0, 7, 15], np.int32)
        pool = BlockPool(num_blocks=S * (W // BS) + 1, block_size=BS)
        T = (S * (W // BS) + 1) * BS
        state = {"k": jnp.zeros((1, T, nkv, hd)), "v": jnp.zeros((1, T, nkv, hd))}
        gather = np.zeros((S, W), np.int32)
        for b in range(S):
            flat = flat_slots(pool.alloc(b, W // BS), W, BS)
            state = scatter_prefill(state, {"k": ck[b][None], "v": cv[b][None]},
                                    flat)
            gather[b] = flat
        out_paged, _ = common.mha_decode_paged(
            cfg, p, x, jnp.asarray(pos),
            {"k": state["k"][0], "v": state["v"][0]},
            jnp.asarray(gather[np.arange(S), pos]), jnp.asarray(gather),
            jnp.ones((S,), bool))
        # the contiguous reference decodes all S rows at slot b's position
        # and row b is compared: XLA's CPU dot rounds a 1-row matmul
        # differently from an S-row one, so a batch-1 reference would pin
        # the matmul's row count, not the paged read
        for b in range(S):
            out_contig, _ = common.mha_decode(
                cfg, p, x, jnp.int32(pos[b]), {"k": ck, "v": cv})
            np.testing.assert_array_equal(np.asarray(out_paged[b:b + 1]),
                                          np.asarray(out_contig[b:b + 1]))


class TestDecodeImpl:
    """decode_impl="fused" (the default) vs "reference": token-identical
    end to end — the fused path either runs the block-table kernel (TPU)
    or an oracle that is bitwise the reference gather math (here)."""

    @pytest.mark.parametrize("temperature", [0.0, 0.7])
    def test_batcher_fused_equals_reference(self, tiny, temperature):
        import dataclasses
        model, params = tiny
        for weights in (params, round_tree_nm(params)):
            reqs = _mixed_requests(model.cfg.vocab, temperature=temperature)
            results = {}
            for impl in ("fused", "reference"):
                cfg = dataclasses.replace(BC, decode_impl=impl)
                results[impl] = ContinuousBatcher(model, weights, cfg) \
                    .run(list(reqs))
            for a, b in zip(results["fused"], results["reference"]):
                np.testing.assert_array_equal(
                    a.tokens, b.tokens,
                    err_msg=f"request {a.id} diverged across decode impls")

    def test_batcher_fused_under_defrag(self, tiny):
        """Mid-run defrag (blocks move, tables rewrite) under the fused
        impl: tokens still match the solo engine."""
        import dataclasses
        model, params = tiny
        reqs = _mixed_requests(model.cfg.vocab)
        batcher = ContinuousBatcher(
            model, params, dataclasses.replace(BC, decode_impl="fused"))
        for r in reqs:
            batcher.submit(r)
        while batcher.queue or batcher._active.any():
            batcher._admit(0.0)
            if batcher._active.any():
                batcher._tick(0.0)
            batcher.defrag()
        for req in reqs:
            np.testing.assert_array_equal(
                batcher.results[req.id].tokens,
                _solo_generate(model, params, req))

    def test_unknown_impl_rejected(self, tiny):
        import dataclasses
        model, params = tiny
        with pytest.raises(ValueError, match="decode_impl"):
            ContinuousBatcher(model, params,
                              dataclasses.replace(BC, decode_impl="turbo"))
        with pytest.raises(ValueError, match="decode_impl"):
            Engine(model, params, ServeConfig(decode_impl="turbo"))

    def test_engine_flag_forwarding(self, tiny):
        """The contiguous-cache engine serves via the reference path
        either way — the flag must validate and not change tokens."""
        model, params = tiny
        prompt = jnp.asarray(np.full((1, 5), 3, np.int32))
        outs = [Engine(model, params, ServeConfig(decode_impl=impl))
                .generate(prompt, max_new_tokens=6)
                for impl in ("fused", "reference")]
        np.testing.assert_array_equal(outs[0], outs[1])


class TestChunkedPrefixServing:
    """Chunked prefill + radix prefix cache + SLA scheduling: every path
    stays on the token-identity anchor, and the chunk executable — like
    the decode step — traces exactly once."""

    def _shared_prefix_requests(self, vocab, temperature=0.0):
        rng = np.random.default_rng(9)
        prefix = rng.integers(0, vocab, size=8).astype(np.int32)
        spec = [(5, 6), (9, 4), (3, 8), (12, 5), (7, 7)]
        return [Request(id=i, prompt=np.concatenate(
                            [prefix, rng.integers(0, vocab, size=p)]
                        ).astype(np.int32),
                        max_new_tokens=n, temperature=temperature)
                for i, (p, n) in enumerate(spec)]

    def _solo_chunked(self, model, params, r, temperature=0.0):
        eng = Engine(model, params,
                     ServeConfig(cache_len=CBC.context_len,
                                 temperature=temperature,
                                 block_size=CBC.block_size,
                                 prefill_chunk=CBC.prefill_chunk))
        return eng.generate(jnp.asarray(r.prompt[None, :]),
                            max_new_tokens=r.max_new_tokens,
                            request_ids=[r.id])[0]

    @pytest.mark.parametrize("temperature", [0.0, 0.7])
    def test_token_identity_dense_and_packed(self, tiny, temperature):
        model, params = tiny
        for weights in (params, round_tree_nm(params)):
            reqs = self._shared_prefix_requests(model.cfg.vocab, temperature)
            batcher = ContinuousBatcher(model, weights, CBC)
            results = batcher.run(list(reqs))
            for req, res in zip(reqs, results):
                np.testing.assert_array_equal(
                    res.tokens,
                    self._solo_chunked(model, weights, req, temperature),
                    err_msg=f"chunked request {req.id} diverged from solo")
            # shared prefixes actually hit once the first insert lands
            assert sum(r.prefix_hit_tokens for r in results) > 0
            # one chunk executable, one decode executable — joins, hits,
            # and ragged tails never re-specialize
            assert batcher._chunk_fn._cache_size() == 1
            assert batcher._step_fn._cache_size() == 1

    def test_cache_hit_bitwise_equals_cold(self, tiny):
        """The same (prompt, id) served cold and served from a warm
        cache must produce bitwise-identical tokens (temperature on, so
        a single logit ULP would flip the comparison)."""
        model, params = tiny
        rng = np.random.default_rng(17)
        prompt = rng.integers(0, model.cfg.vocab, size=13).astype(np.int32)
        warm = ContinuousBatcher(model, params, CBC)
        warm.run([Request(id=0, prompt=prompt, max_new_tokens=6,
                          temperature=0.7)])
        warm.run([Request(id=1, prompt=prompt, max_new_tokens=6,
                          temperature=0.7)])
        hit = warm.results[1]
        assert hit.prefix_hit_tokens == 8        # (13-1)//8 = 1 block
        cold = ContinuousBatcher(model, params, CBC).run(
            [Request(id=1, prompt=prompt, max_new_tokens=6,
                     temperature=0.7)])[0]
        assert cold.prefix_hit_tokens == 0
        np.testing.assert_array_equal(hit.tokens, cold.tokens)

    def test_preempt_then_resume_identity(self, tiny):
        """An urgent arrival preempts a lower-priority active request
        (K/V swapped to host, blocks freed); the victim resumes and
        still matches its solo run bitwise — temperature on, so the
        restored sampling index is load-bearing.  The batcher is driven
        manually until both low-priority requests have grown to fill the
        pool, so the urgent request always lands under pressure."""
        import dataclasses
        model, params = tiny
        cfg = dataclasses.replace(CBC, slots=2, num_blocks=7)
        rng = np.random.default_rng(23)
        mk = lambda i, prio: Request(
            id=i, prompt=rng.integers(0, model.cfg.vocab, size=12)
            .astype(np.int32), max_new_tokens=12, temperature=0.7,
            priority=prio)
        reqs = [mk(0, 5), mk(1, 5), mk(2, 0)]
        batcher = ContinuousBatcher(model, params, cfg)
        batcher.submit(reqs[0])
        batcher.submit(reqs[1])
        while batcher.queue or not batcher._active.all():
            batcher._admit(0.0)
            if not batcher._prefill_tick(0.0) and batcher._active.any():
                batcher._tick(0.0)
        while batcher.pool.num_free:   # decode until both grow to 3 blocks
            batcher._tick(0.0)
        batcher.submit(reqs[2])
        results = batcher.run()
        assert batcher.stats["preemptions"] >= 1
        assert batcher.stats["resumes"] == batcher.stats["preemptions"]
        assert any(r.preemptions > 0 for r in results)
        for req, res in zip(reqs, results):
            np.testing.assert_array_equal(
                res.tokens, self._solo_chunked(model, params, req, 0.7),
                err_msg=f"request {req.id} diverged through preemption")

    def test_defrag_with_cache_and_prefilling_slots(self, tiny):
        """Defrag on every tick while chunked prefills are in flight and
        the radix cache holds shared blocks: tables, prefill state, and
        trie node ids all remap — tokens unchanged."""
        model, params = tiny
        reqs = self._shared_prefix_requests(model.cfg.vocab)
        batcher = ContinuousBatcher(model, params, CBC)
        for r in reqs:
            batcher.submit(r)
        moved = 0
        while batcher.queue or batcher._busy():
            batcher._admit(0.0)
            batcher._prefill_tick(0.0)
            if batcher._active.any():
                batcher._tick(0.0)
            moved += batcher.defrag()
        for req in reqs:
            np.testing.assert_array_equal(
                batcher.results[req.id].tokens,
                self._solo_chunked(model, params, req),
                err_msg=f"request {req.id} diverged under defrag")
        assert moved > 0

    def test_config_validation(self, tiny):
        import dataclasses
        model, params = tiny
        with pytest.raises(ValueError, match="prefix_cache requires"):
            ContinuousBatcher(model, params,
                              dataclasses.replace(BC, prefix_cache=True))
        with pytest.raises(ValueError, match="prefill_chunk"):
            ContinuousBatcher(model, params,
                              dataclasses.replace(BC, prefill_chunk=0))
        with pytest.raises(ValueError, match="token prompts only"):
            Engine(model, params,
                   ServeConfig(prefill_chunk=8)).generate(
                jnp.zeros((1, 4), jnp.int32), max_new_tokens=2,
                extras={"patches": jnp.zeros((1, 2, 4))})

    def test_sla_queue_orders_by_priority_then_deadline(self, tiny):
        """One slot: completion order must follow (priority, deadline)
        for requests that all arrived before the first admission."""
        import dataclasses
        model, params = tiny
        cfg = dataclasses.replace(CBC, slots=1)
        rng = np.random.default_rng(29)
        mk = lambda i, prio, dl: Request(
            id=i, prompt=rng.integers(0, model.cfg.vocab, size=6)
            .astype(np.int32), max_new_tokens=3, priority=prio, deadline=dl)
        reqs = [mk(0, 2, None), mk(1, 0, 9.0), mk(2, 0, 1.0), mk(3, 1, None)]
        batcher = ContinuousBatcher(model, params, cfg)
        results = batcher.run(list(reqs))
        order = sorted(results, key=lambda r: r.first_token)
        assert [r.id for r in order] == [2, 1, 3, 0]


class TestEngineRegressions:
    def test_position_overrun_raises(self, tiny):
        """prompt_len + max_new_tokens > max_seq used to silently wrap or
        overrun positions; now it's a hard error before any compute."""
        model, params = tiny                # tiny max_seq = 128
        eng = Engine(model, params, ServeConfig())
        prompt = jnp.zeros((1, 100), jnp.int32)
        with pytest.raises(ValueError, match="max_seq"):
            eng.generate(prompt, max_new_tokens=64)
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.generate(prompt, max_new_tokens=0)
        with pytest.raises(ValueError, match="at least one token"):
            eng.generate(jnp.zeros((1, 0), jnp.int32))

    def test_whisper_overrun_raises(self):
        """whisper's learned pos_embed lookup silently clamped past
        max_seq — the validation must fire for prefill-less families too."""
        d = load_arch("whisper-base", smoke=True)
        eng = Engine(d, params=None)        # raises before touching params
        prompt = jnp.zeros((1, d.cfg.max_seq), jnp.int32)
        with pytest.raises(ValueError, match="max_seq"):
            eng.generate(prompt, max_new_tokens=8)

    def test_temperature_independent_of_batch(self, tiny):
        """Per-request folded PRNG: a sampled request's tokens depend on
        its request id, never on what else shares the batch."""
        model, params = tiny
        eng = Engine(model, params, ServeConfig(temperature=0.8))
        rng = np.random.default_rng(5)
        p = rng.integers(0, model.cfg.vocab, size=(2, 6)).astype(np.int32)
        both = eng.generate(jnp.asarray(p), max_new_tokens=6,
                            request_ids=[7, 9])
        for row, rid in ((0, 7), (1, 9)):
            solo = eng.generate(jnp.asarray(p[row:row + 1]), max_new_tokens=6,
                                request_ids=[rid])
            np.testing.assert_array_equal(both[row], solo[0])

    def test_identical_requests_identical_output(self, tiny):
        """Two submissions of the same (prompt, request id) sample the
        same tokens — regardless of engine call boundaries."""
        model, params = tiny
        eng = Engine(model, params, ServeConfig(temperature=1.0))
        prompt = jnp.asarray(np.full((1, 5), 3, np.int32))
        a = eng.generate(prompt, max_new_tokens=5, request_ids=[42])
        b = eng.generate(prompt, max_new_tokens=5, request_ids=[42])
        np.testing.assert_array_equal(a, b)

    def test_vlm_decode_positions(self):
        """Patch embeddings occupy positions: greedy decode must continue
        at position n_patches + P, matching the teacher-forced forward
        (the engine used to restart at P, wrapping the cache)."""
        cfg = load_arch("internvl2-2b", smoke=True).cfg.replace(
            num_layers=2, d_model=64, num_heads=4, num_kv_heads=4,
            d_ff=128, vocab=128, max_seq=128, vlm=VLMConfig(num_patches=6))
        d = model_def(cfg)
        params = d.init(jax.random.PRNGKey(0))
        batch = d.make_batch(jax.random.PRNGKey(1), 1, 14)
        prompt, patches = batch["tokens"], batch["patches"]
        n = 4
        gen = Engine(d, params, ServeConfig(max_new_tokens=n)).generate(
            prompt, extras={"patches": patches})
        seq = jnp.concatenate([prompt, jnp.asarray(gen)], axis=1)
        logits = d.forward_logits(params, {"tokens": seq, "patches": patches})
        start = patches.shape[1] + prompt.shape[1] - 1
        want = np.asarray(jnp.argmax(
            logits[:, start:start + n].astype(jnp.float32), axis=-1))
        np.testing.assert_array_equal(np.asarray(gen), want)
