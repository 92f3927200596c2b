"""Block-table flash-decode fast path (kernels/paged_attention.py).

Deterministic pins for the fused decode path, layered the same way the
code is:

* oracle vs. a handwritten numpy softmax over the gathered context —
  ragged per-slot lengths, a window narrower than the context, softcap,
  block tables with holes and trash-block-0 tails;
* Pallas kernels vs. the oracle under ``interpret=True`` (the
  ``kernels_interpret`` marker; compiled-mode parity needs a TPU),
  including GQA with several kv heads per pool block and the fused MLP;
* the serving contract: ``impl="fused"`` is BITWISE the reference
  gather path on this backend (DESIGN.md §11), at the attention level
  and through a full multi-step ``paged_serve_step`` drive — dense and
  packed-2:4, windowed and not, with an inactive slot in the batch.

The hypothesis sweeps over random scenarios live in
tests/test_paged_attention_props.py (optional dep, skips without it).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.opt125m_proxy import tiny_config
from repro.core.sparsity import round_tree_nm
from repro.kernels import ops as kops
from repro.kernels import paged_attention as pk
from repro.kernels import ref
from repro.models import common, transformer
from repro.serve.packed import pack_tree

TRASH = 0       # serve/kv_cache.py reserves block 0 as the trash block
NB, BS = 10, 4  # pool blocks / block size for the scenarios here


def build_scenario(seed, lengths, nkv=2, g=2, hd=8, trash_fill=37.0):
    """Random pool + block tables for ragged per-slot contexts.

    Each slot's blocks come from one permutation of 1..NB-1, so
    consecutive table columns are non-contiguous pool blocks (holes);
    table tails pad with the trash block, and the trash block is filled
    with large garbage so an unmasked read shows up loudly.  Returns
    numpy (q, k_pool, v_pool, tables, pos); pos = lengths - 1.
    """
    rng = np.random.default_rng(seed)
    S = len(lengths)
    MB = max(-(-int(l) // BS) for l in lengths) + 1   # >= 1 trash tail col
    perm = rng.permutation(np.arange(1, NB))
    tables = np.full((S, MB), TRASH, np.int32)
    used = 0
    for s, L in enumerate(lengths):
        nb = -(-int(L) // BS)
        tables[s, :nb] = perm[used:used + nb]
        used += nb
    assert used <= NB - 1, "scenario too large for the pool"
    T = NB * BS
    k_pool = rng.standard_normal((T, nkv, hd)).astype(np.float32)
    v_pool = rng.standard_normal((T, nkv, hd)).astype(np.float32)
    k_pool[:BS] = trash_fill
    v_pool[:BS] = trash_fill
    q = rng.standard_normal((S, nkv * g, hd)).astype(np.float32)
    pos = np.asarray(lengths, np.int32) - 1
    return q, k_pool, v_pool, tables, pos


def naive_paged_attention(q, k_pool, v_pool, tables, pos, active,
                          window=0, softcap=0.0):
    """Per-slot, per-head loop-and-softmax in float64 — the independent
    check the oracle (and through it the kernel) is pinned against.
    Inactive slots return zeros (their serving output is discarded)."""
    S, nq, hd = q.shape
    nkv = k_pool.shape[1]
    g = nq // nkv
    out = np.zeros_like(q)
    for s in range(S):
        if not active[s]:
            continue
        lo = max(0, pos[s] - window + 1) if window else 0
        flat = [tables[s, t // BS] * BS + t % BS
                for t in range(lo, pos[s] + 1)]
        k, v = k_pool[flat].astype(np.float64), v_pool[flat].astype(np.float64)
        for h in range(nkv):
            for gg in range(g):
                sc = k[:, h] @ q[s, h * g + gg].astype(np.float64)
                sc /= np.sqrt(hd)
                if softcap > 0:
                    sc = np.tanh(sc / softcap) * softcap
                p = np.exp(sc - sc.max())
                p /= p.sum()
                out[s, h * g + gg] = p @ v[:, h]
    return out


def pack_random_24(rng, m, n, scale=1.0):
    """A random exactly-2:4 (m, n) matrix (groups along n) and its packed
    form — two random survivors per 4-group."""
    w = rng.standard_normal((m, n)).astype(np.float32) * scale
    keep = rng.random((m, n // 4, 4)).argsort(axis=-1) < 2
    w = w * keep.reshape(m, n)
    vals, meta = kops.pack24(jnp.asarray(w))
    return w, vals, meta


class TestOracle:
    """ref.paged_attention vs. the handwritten numpy reduction."""

    @pytest.mark.parametrize("window,softcap", [(0, 0.0), (3, 0.0),
                                                (0, 5.0), (5, 2.0)])
    def test_matches_naive(self, window, softcap):
        q, k, v, tables, pos = build_scenario(0, lengths=[1, 7, 8])
        active = np.ones(3, bool)
        got = ref.paged_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(tables), jnp.asarray(pos), jnp.asarray(active),
            block_size=BS, window=window, softcap=softcap)
        want = naive_paged_attention(q, k, v, tables, pos, active,
                                     window=window, softcap=softcap)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                                   atol=1e-6)

    def test_trash_block_never_leaks(self):
        """Changing the trash block's contents must not move a single bit
        of any slot's output — the tail columns of every table row alias
        positions past ``pos`` and mask out."""
        outs = []
        for fill in (37.0, -1e4):
            q, k, v, tables, pos = build_scenario(1, lengths=[5, 2],
                                                  trash_fill=fill)
            outs.append(np.asarray(ref.paged_attention(
                jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                jnp.asarray(tables), jnp.asarray(pos),
                jnp.ones((2,), bool), block_size=BS)))
        np.testing.assert_array_equal(outs[0], outs[1])

    def test_inactive_slot_isolated(self):
        """Flipping one slot inactive leaves the other slots' outputs
        bitwise unchanged (retirement can't perturb neighbours)."""
        q, k, v, tables, pos = build_scenario(2, lengths=[6, 3, 8])
        args = (jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                jnp.asarray(tables), jnp.asarray(pos))
        all_on = np.asarray(ref.paged_attention(
            *args, jnp.ones((3,), bool), block_size=BS))
        one_off = np.asarray(ref.paged_attention(
            *args, jnp.asarray([True, False, True]), block_size=BS))
        np.testing.assert_array_equal(one_off[[0, 2]], all_on[[0, 2]])


@pytest.mark.kernels_interpret
class TestKernelInterpret:
    """Pallas kernels vs. the jnp oracles under ``interpret=True``."""

    @pytest.mark.parametrize("window,softcap", [(0, 0.0), (3, 0.0),
                                                (5, 2.0)])
    def test_attention_matches_oracle(self, window, softcap):
        q, k, v, tables, pos = build_scenario(3, lengths=[1, 6, 8])
        args = (jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                jnp.asarray(tables), jnp.asarray(pos),
                jnp.ones((3,), bool))
        got = pk.paged_decode_attn(*args, block_size=BS, window=window,
                                   softcap=softcap, interpret=True)
        want = ref.paged_attention(*args, block_size=BS, window=window,
                                   softcap=softcap)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)

    def test_attention_inactive_and_holes(self):
        q, k, v, tables, pos = build_scenario(4, lengths=[7, 2])
        active = jnp.asarray([True, False])
        args = (jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                jnp.asarray(tables), jnp.asarray(pos), active)
        got = pk.paged_decode_attn(*args, block_size=BS, interpret=True)
        want = ref.paged_attention(*args, block_size=BS)
        np.testing.assert_allclose(np.asarray(got)[:1], np.asarray(want)[:1],
                                   rtol=1e-5, atol=1e-6)

    def test_attention_gqa_heads_matches_oracle(self):
        """More kv heads and a wider group than the other scenarios: the
        kernel loops over every kv head of a pool block, each with its
        own online-softmax state."""
        q, k, v, tables, pos = build_scenario(5, lengths=[5, 8, 3], nkv=3,
                                              g=4)
        args = (jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                jnp.asarray(tables), jnp.asarray(pos),
                jnp.asarray([True, True, False]))
        got = pk.paged_decode_attn(*args, block_size=BS, window=3,
                                   interpret=True)
        want = ref.paged_attention(*args, block_size=BS, window=3)
        assert got.shape == q.shape
        np.testing.assert_allclose(np.asarray(got)[:2], np.asarray(want)[:2],
                                   rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("gated,f,bf", [(True, 16, 16), (True, 12, 8),
                                            (False, 12, 8)])
    def test_fused_mlp_matches_oracle(self, gated, f, bf):
        """One-dispatch MLP vs. the unpack-and-matmul oracle; f % bf != 0
        exercises the d_ff tile padding."""
        rng = np.random.default_rng(6)
        B, d = 3, 8
        x = jnp.asarray(rng.standard_normal((B, d)), jnp.float32)
        _, w1v, w1m = pack_random_24(rng, f, d)
        _, w2v, w2m = pack_random_24(rng, d, f)
        if gated:
            _, upv, upm = pack_random_24(rng, f, d)
            b1 = b2 = None
            act = "silu"
        else:
            upv = upm = None
            b1 = jnp.asarray(rng.standard_normal((f,)), jnp.float32)
            b2 = jnp.asarray(rng.standard_normal((d,)), jnp.float32)
            act = "gelu"
        got = pk.fused_mlp24(x, w1v, w1m, b1, upv, upm, w2v, w2m, b2,
                             act=act, bf=bf, interpret=True)
        want = ref.fused_mlp24(x, w1v, w1m, b1, upv, upm, w2v, w2m, b2,
                               act=act)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)


def _gather_from_tables(tables, block_size):
    S, MB = tables.shape
    j = np.arange(MB * block_size)
    return tables[:, j // block_size] * block_size + j % block_size


class TestFusedEqualsReference:
    """The serving contract: on this backend the fused impl routes to an
    oracle that repeats the reference gather math element-for-element,
    so impl="fused" == impl="reference" BITWISE (DESIGN.md §11)."""

    @pytest.mark.parametrize("window,packed_wo", [(None, False), (3, False),
                                                  (None, True)])
    def test_mha_decode_paged(self, window, packed_wo):
        cfg = tiny_config().replace(num_layers=1, d_model=16, num_heads=2,
                                    num_kv_heads=2, vocab=32)
        p = common.attn_init(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(7)
        hd, nq = cfg.resolved_head_dim(), cfg.num_heads
        if packed_wo:
            wo, vals, meta = pack_random_24(rng, cfg.d_model, nq * hd, 0.2)
            p = dict(p, wo={"vals": vals, "meta": meta})
        _, k, v, tables, pos = build_scenario(7, lengths=[6, 2, 8], nkv=2,
                                              g=1, hd=hd)
        x = jnp.asarray(rng.standard_normal((3, 1, cfg.d_model)), jnp.float32)
        cache = {"k": jnp.asarray(k), "v": jnp.asarray(v)}
        write_idx = jnp.asarray(
            tables[np.arange(3), pos // BS] * BS + pos % BS)
        gather = jnp.asarray(_gather_from_tables(tables, BS))
        active = jnp.asarray([True, True, False])
        out_ref_, cache_ref = common.mha_decode_paged(
            cfg, p, x, jnp.asarray(pos), cache, write_idx, gather, active,
            window, impl="reference")
        out_fused, cache_fused = common.mha_decode_paged(
            cfg, p, x, jnp.asarray(pos), cache, write_idx, None, active,
            window, tables=jnp.asarray(tables), block_size=BS, impl="fused")
        np.testing.assert_array_equal(np.asarray(out_fused),
                                      np.asarray(out_ref_))
        for key in ("k", "v"):
            np.testing.assert_array_equal(np.asarray(cache_fused[key]),
                                          np.asarray(cache_ref[key]))

    @pytest.mark.parametrize("window", [None, 6])
    @pytest.mark.parametrize("packed", [False, True])
    def test_paged_serve_step_multi_step(self, window, packed):
        """Full decode steps (attention + MLP + head) driven for several
        ticks at ragged positions: logits and pools bitwise-identical
        between the impls, dense and packed-2:4."""
        cfg = tiny_config().replace(num_layers=2, d_model=32, d_ff=64,
                                    num_heads=4, num_kv_heads=2, vocab=64,
                                    window=window)
        params = transformer.init(cfg, jax.random.PRNGKey(1))
        if packed:
            params = pack_tree(round_tree_nm(params), dtype=None)[0]
        rng = np.random.default_rng(8)
        S, MB = 3, 4                        # 3 ctx blocks + trash tail
        perm = rng.permutation(np.arange(1, S * 3 + 1))
        tables = np.full((S, MB), TRASH, np.int32)
        tables[:, :3] = perm.reshape(S, 3)
        tables = jnp.asarray(tables)
        pool_r = pool_f = transformer.init_paged_caches(cfg, S * 3 + 1, BS)
        pos0 = np.asarray([0, 3, 5], np.int32)
        active = jnp.asarray([True, True, False])
        for t in range(4):
            token = jnp.asarray(rng.integers(0, cfg.vocab, (S, 1)), jnp.int32)
            pos = jnp.asarray(pos0 + t)
            lr, pool_r = transformer.paged_serve_step(
                cfg, params, pool_r, tables, token, pos, active, BS,
                impl="reference")
            lf, pool_f = transformer.paged_serve_step(
                cfg, params, pool_f, tables, token, pos, active, BS,
                impl="fused")
            np.testing.assert_array_equal(np.asarray(lf), np.asarray(lr),
                                          err_msg=f"step {t} logits diverged")
            for key in ("k", "v"):
                np.testing.assert_array_equal(np.asarray(pool_f[key]),
                                              np.asarray(pool_r[key]))


class TestDispatchRouting:
    """ops.py routing contracts the serving paths rely on."""

    def test_cpu_routes_to_oracle(self):
        if jax.default_backend() == "tpu":
            pytest.skip("TPU backend compiles the kernel instead")
        assert not kops.use_decode_kernel(128, 16)
        assert not kops.use_fused_mlp(4096, 11008)

    def test_kernel_shape_gates(self):
        # independent of backend: misaligned shapes always fall back
        assert not kops.use_decode_kernel(64, 16)   # head_dim < lane width
        assert not kops.use_decode_kernel(128, 6)   # block_size % 8 != 0
        assert not kops.use_fused_mlp(64, 11008)
        assert not kops.use_fused_mlp(4096, 128)

    def test_ops_paged_decode_attn_is_oracle_off_tpu(self):
        q, k, v, tables, pos = build_scenario(9, lengths=[4, 7])
        args = (jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                jnp.asarray(tables), jnp.asarray(pos),
                jnp.ones((2,), bool))
        got = kops.paged_decode_attn(*args, block_size=BS, window=3)
        want = ref.paged_attention(*args, block_size=BS, window=3)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
