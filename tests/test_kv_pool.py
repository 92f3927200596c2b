"""Hypothesis invariants for the paged KV block pool (serve/kv_cache.py).

Three layers of guarantee, each load-bearing for the serving stack:
the allocator never hands a block to two requests (aliasing would
cross-contaminate contexts), alloc/free round-trips conserve the pool,
and the paged read — scatter into blocks, gather back in position order
— is **bitwise** equal to a contiguous cache, including through the full
paged decode attention (``mha_decode_paged`` vs ``mha_decode``) on
ragged per-slot lengths.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

pytest.importorskip(
    "hypothesis",
    reason="optional test dep (pip install '.[test]') — see pyproject.toml")
from hypothesis import given, settings, strategies as st

from repro.configs.opt125m_proxy import tiny_config
from repro.models import common
from repro.serve.kv_cache import (TRASH_BLOCK, BlockPool, PoolExhausted,
                                  apply_defrag, flat_slots, scatter_prefill,
                                  table_row)

NB, BS = 9, 4          # 8 allocatable blocks of 4 slots

# an op is (request_id, n_blocks) for alloc, or (request_id, 0) for free
OPS = st.lists(st.tuples(st.integers(0, 4), st.integers(0, 3)),
               min_size=1, max_size=40)


def _replay(ops):
    pool, model = BlockPool(NB, BS), {}
    for rid, n in ops:
        if n == 0:
            pool.free_request(rid)
            model.pop(rid, None)
        else:
            try:
                got = pool.alloc(rid, n)
            except PoolExhausted:
                assert n > pool.num_free
                continue
            assert len(got) == n
            model.setdefault(rid, []).extend(got)
    return pool, model


class TestAllocatorProps:
    @given(OPS)
    @settings(max_examples=60, deadline=None)
    def test_conservation_and_no_aliasing(self, ops):
        pool, model = _replay(ops)
        owned = [b for bl in model.values() for b in bl]
        # the allocator agrees with the independently tracked model
        for rid, bl in model.items():
            assert pool.blocks_of(rid) == bl
        # no aliasing: a block belongs to at most one request; trash never
        assert len(owned) == len(set(owned))
        assert TRASH_BLOCK not in owned
        # conservation: free + owned is exactly the allocatable set
        free = set(range(1, NB)) - set(owned)
        assert pool.num_free == len(free)
        assert pool.num_live == len(owned)

    @given(OPS)
    @settings(max_examples=30, deadline=None)
    def test_defrag_compacts_and_remaps(self, ops):
        pool, model = _replay(ops)
        before = {rid: list(bl) for rid, bl in model.items()}
        remap = pool.defrag()
        live = sorted(b for bl in pool._owned.values() for b in bl)
        # compacted: live blocks occupy the lowest ids, order preserved
        assert live == list(range(1, len(live) + 1))
        for rid, bl in before.items():
            assert pool.blocks_of(rid) == [remap.get(b, b) for b in bl]
        # a full pool round-trips: everything frees back
        for rid in list(model):
            pool.free_request(rid)
        assert pool.num_free == NB - 1 and pool.num_live == 0


LENGTHS = st.lists(st.integers(1, 2 * BS), min_size=1, max_size=3)


class TestPagedReadBitwise:
    @given(LENGTHS, st.integers(0, 2 ** 31 - 1))
    @settings(max_examples=15, deadline=None)
    def test_scatter_gather_roundtrip(self, lengths, seed):
        """Paged read == contiguous read, bitwise, on ragged lengths."""
        rng = np.random.default_rng(seed)
        L, nkv, hd = 2, 2, 4
        pool = BlockPool(NB, BS)
        state = {"k": jnp.zeros((L, (NB) * BS, nkv, hd), jnp.float32)}
        contig, tables = {}, {}
        for rid, P in enumerate(lengths):
            blocks = pool.alloc(rid, -(-P // BS))
            kv = rng.standard_normal((L, P, nkv, hd)).astype(np.float32)
            contig[rid], tables[rid] = kv, blocks
            state = scatter_prefill(state, {"k": jnp.asarray(kv)},
                                    flat_slots(blocks, P, BS))
        for rid, P in enumerate(lengths):
            row = table_row(tables[rid], max_blocks=2)
            j = np.arange(2 * BS)
            gather = row[j // BS] * BS + j % BS
            got = np.asarray(state["k"][:, gather])[:, :P]
            np.testing.assert_array_equal(got, contig[rid])

    @given(st.integers(0, 2 ** 31 - 1),
           st.lists(st.integers(0, 15), min_size=3, max_size=3))
    @settings(max_examples=10, deadline=None)
    def test_paged_attention_matches_contiguous(self, seed, positions):
        """mha_decode_paged == mha_decode bitwise, per slot, at ragged
        per-slot positions — the strongest form of the paged-read claim."""
        cfg = tiny_config().replace(num_layers=1, d_model=16, num_heads=2,
                                    num_kv_heads=2, vocab=32)
        p = common.attn_init(cfg, jax.random.PRNGKey(seed % 1000))
        rng = np.random.default_rng(seed)
        S, W, nkv, hd = 3, 16, 2, cfg.resolved_head_dim()
        x = jnp.asarray(rng.standard_normal((S, 1, cfg.d_model)), jnp.float32)
        ck = jnp.asarray(rng.standard_normal((S, W, nkv, hd)), jnp.float32)
        cv = jnp.asarray(rng.standard_normal((S, W, nkv, hd)), jnp.float32)
        pos = np.asarray(positions, np.int32)

        # paged side: one pool, every slot's W context rows scattered in
        pool = BlockPool(num_blocks=S * (W // BS) + 1, block_size=BS)
        state = {"k": jnp.zeros((1, (S * (W // BS) + 1) * BS, nkv, hd)),
                 "v": jnp.zeros((1, (S * (W // BS) + 1) * BS, nkv, hd))}
        gather = np.zeros((S, W), np.int32)
        for b in range(S):
            blocks = pool.alloc(b, W // BS)
            flat = flat_slots(blocks, W, BS)
            state = scatter_prefill(state, {"k": ck[b][None], "v": cv[b][None]},
                                    flat)
            gather[b] = flat
        write_idx = gather[np.arange(S), pos]
        out_paged, new_paged = common.mha_decode_paged(
            cfg, p, x, jnp.asarray(pos),
            {"k": state["k"][0], "v": state["v"][0]},
            jnp.asarray(write_idx), jnp.asarray(gather),
            jnp.ones((S,), bool))

        # all S rows decode at slot b's position and row b is compared:
        # XLA's CPU dot rounds a 1-row matmul differently from an S-row one
        for b in range(S):
            out_solo, new_solo = common.mha_decode(
                cfg, p, x, jnp.int32(pos[b]), {"k": ck, "v": cv})
            np.testing.assert_array_equal(np.asarray(out_paged[b:b + 1]),
                                          np.asarray(out_solo[b:b + 1]))
            # the written K/V row matches too (cache side of the contract)
            np.testing.assert_array_equal(
                np.asarray(new_paged["k"][gather[b]])[pos[b]],
                np.asarray(new_solo["k"])[b, pos[b]])


class TestDefragDeviceMove:
    def test_apply_defrag_preserves_contents(self):
        rng = np.random.default_rng(0)
        L, nkv, hd = 2, 2, 4
        pool = BlockPool(NB, BS)
        state = {"k": jnp.zeros((L, NB * BS, nkv, hd), jnp.float32)}
        data = {}
        for rid, P in ((0, 6), (1, 4), (2, 7)):
            blocks = pool.alloc(rid, -(-P // BS))
            kv = rng.standard_normal((L, P, nkv, hd)).astype(np.float32)
            data[rid] = (kv, P)
            state = scatter_prefill(state, {"k": jnp.asarray(kv)},
                                    flat_slots(blocks, P, BS))
        pool.free_request(1)
        remap = pool.defrag()
        assert remap                      # request 2's blocks moved down
        state = apply_defrag(state, remap, NB, BS)
        for rid in (0, 2):
            kv, P = data[rid]
            flat = flat_slots(pool.blocks_of(rid), P, BS)
            np.testing.assert_array_equal(np.asarray(state["k"][:, flat]), kv)
