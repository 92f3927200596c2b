"""Block-table flash-decode fast path (kernels/paged_attention.py).

Deterministic pins for the fused decode path, layered the same way the
code is:

* oracle vs. a handwritten numpy softmax over the gathered context —
  ragged per-slot lengths, a window narrower than the context, softcap,
  block tables with holes and trash-block-0 tails;
* Pallas kernels vs. the oracle under ``interpret=True`` (the
  ``kernels_interpret`` marker; compiled-mode parity needs a TPU),
  including GQA with several kv heads per pool block and the fused MLP;
* the lane-dense kernel for head_dim < 128 at OPT-125M's head shapes
  (head_dim 64, 12 kv heads, blocks of 16) vs. the float64 loop and the
  oracle, with every block a slot must not read poisoned with NaN;
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
                          window=0, softcap=0.0, block_size=BS):
    """Per-slot, per-head loop-and-softmax in float64 — the independent
    check the oracle (and through it the kernel) is pinned against.
    Inactive slots return zeros (their serving output is discarded)."""
    S, nq, hd = q.shape
    nkv = int(np.prod(k_pool.shape[1:])) // hd
    g = nq // nkv
    k_pool = k_pool.reshape(-1, nkv, hd)
    v_pool = v_pool.reshape(-1, nkv, hd)
    out = np.zeros_like(q)
    for s in range(S):
        if not active[s]:
            continue
        lo = max(0, pos[s] - window + 1) if window else 0
        flat = [tables[s, t // block_size] * block_size + t % block_size
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


LANE_BS = 16    # OPT-125M's serving block size


def build_lane_scenario(seed, lengths, cols, nkv=12, g=1, hd=64, window=0,
                        poison=True):
    """Pool rows of ``nkv*hd`` (the pool layout below head_dim 128) for
    ragged contexts of ``lengths`` in tables ``cols`` wide.

    Every block a slot must not read holds NaN when ``poison`` (else
    large finite garbage): the trash block, the blocks its table's tail
    columns point at (each its own block, the last column the trash
    block), and under a window its blocks wholly before the window.
    Returns numpy (q, k_pool, v_pool, tables, pos), pos = lengths - 1.
    """
    rng = np.random.default_rng(seed)
    S, D = len(lengths), nkv * hd
    nb = 1 + S * cols
    perm = rng.permutation(np.arange(1, nb))
    tables = perm.reshape(S, cols).astype(np.int32)
    k = rng.standard_normal((nb * LANE_BS, D)).astype(np.float32)
    v = rng.standard_normal((nb * LANE_BS, D)).astype(np.float32)
    dead = [TRASH]
    for s, L in enumerate(lengths):
        live = -(-L // LANE_BS)
        if live < cols:
            tables[s, -1] = TRASH
        first = max(0, L - window) // LANE_BS if window else 0
        dead += list(tables[s, live:]) + list(tables[s, :first])
    for b in dead:
        k[b * LANE_BS:(b + 1) * LANE_BS] = np.nan if poison else 37.0
        v[b * LANE_BS:(b + 1) * LANE_BS] = np.nan if poison else -37.0
    q = rng.standard_normal((S, nkv * g, hd)).astype(np.float32)
    return q, k, v, tables, np.asarray(lengths, np.int32) - 1


@pytest.mark.kernels_interpret
class TestLaneKernelInterpret:
    """``paged_decode_attn_lanes`` (head_dim < 128) under
    ``interpret=True``: OPT-125M's heads (hd 64, 12 kv heads, blocks of
    16).  Lengths end at a block's first token, mid-block, on a block's
    last token and on the table's last column; two slots have fewer
    live columns than one inner step takes; one slot is inactive."""

    LENGTHS = [1, 16, 17, 100, 160, 33]
    ACTIVE = [True, True, True, True, True, False]
    COLS = 10

    def _run(self, q, k, v, tables, pos, blocks, **kw):
        return np.asarray(pk.paged_decode_attn_lanes(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(tables), jnp.asarray(pos),
            jnp.asarray(self.ACTIVE), block_size=LANE_BS, blocks=blocks,
            interpret=True, **kw))

    @pytest.mark.parametrize("window,softcap", [(0, 0.0), (40, 0.0),
                                                (0, 5.0), (37, 2.0)])
    def test_matches_naive_and_oracle(self, window, softcap):
        on = np.asarray(self.ACTIVE)
        scen = build_lane_scenario(10, self.LENGTHS, self.COLS,
                                   window=window)
        got = self._run(*scen, blocks=4, window=window, softcap=softcap)
        assert np.isfinite(got).all()
        want = naive_paged_attention(*scen, on, window=window,
                                     softcap=softcap, block_size=LANE_BS)
        np.testing.assert_allclose(got[on], want[on], rtol=1e-5, atol=1e-5)
        # the oracle reads every column (masked), so it gets the pools
        # with finite garbage where the kernel's had NaN
        clean = build_lane_scenario(10, self.LENGTHS, self.COLS,
                                    window=window, poison=False)
        oracle = np.asarray(ref.paged_attention(
            *map(jnp.asarray, clean), jnp.asarray(on), block_size=LANE_BS,
            window=window, softcap=softcap))
        np.testing.assert_allclose(got[on], oracle[on], rtol=1e-5, atol=1e-6)

    def test_reads_no_block_past_the_live_ones(self):
        """NaN or finite garbage in every block a slot must not read
        moves no bit of any output: those blocks are never copied in."""
        outs = [self._run(*build_lane_scenario(11, self.LENGTHS, self.COLS,
                                               window=40, poison=p),
                          blocks=4, window=40) for p in (True, False)]
        np.testing.assert_array_equal(outs[0], outs[1])

    @pytest.mark.parametrize("blocks", [1, 3, 16])
    def test_any_block_count_per_step(self, blocks):
        """One column per step, steps that straddle a slot's end, and one
        step wider than every slot's context give the same answer."""
        on = np.asarray(self.ACTIVE)
        scen = build_lane_scenario(12, self.LENGTHS, self.COLS)
        got = self._run(*scen, blocks=blocks)
        assert np.isfinite(got).all()
        want = naive_paged_attention(*scen, on,
                                     block_size=LANE_BS)
        np.testing.assert_allclose(got[on], want[on], rtol=1e-5, atol=1e-5)

    def test_gqa(self):
        """24 query heads on 12 kv heads: two query rows per kv head's
        lanes, each with its own softmax."""
        on = np.asarray(self.ACTIVE)
        scen = build_lane_scenario(13, self.LENGTHS, self.COLS, g=2)
        got = self._run(*scen, blocks=8, window=50)
        assert got.shape == scen[0].shape and np.isfinite(got).all()
        want = naive_paged_attention(*scen, on, window=50,
                                     block_size=LANE_BS)
        np.testing.assert_allclose(got[on], want[on], rtol=1e-5, atol=1e-5)


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


    @pytest.mark.parametrize("impl", ["reference", "fused"])
    def test_pool_row_layout_moves_no_bit(self, impl):
        """The same decode steps over a pool of (nkv*hd) rows and over
        one of (nkv, hd) rows: logits and pool contents bitwise equal."""
        cfg = tiny_config().replace(num_layers=2, d_model=32, d_ff=64,
                                    num_heads=4, num_kv_heads=2, vocab=64)
        params = transformer.init(cfg, jax.random.PRNGKey(2))
        rng = np.random.default_rng(9)
        S = 2
        tables = jnp.asarray([[1, 2, TRASH], [3, 4, 5]], jnp.int32)
        rows = transformer.init_paged_caches(cfg, 6, BS)
        assert rows["k"].ndim == 3
        L, T, D = rows["k"].shape
        heads = {k: v.reshape(L, T, 2, D // 2) for k, v in rows.items()}
        active = jnp.asarray([True, True])
        for t in range(3):
            token = jnp.asarray(rng.integers(0, cfg.vocab, (S, 1)), jnp.int32)
            pos = jnp.asarray([2 + t, 5 + t], jnp.int32)
            lr, rows = transformer.paged_serve_step(
                cfg, params, rows, tables, token, pos, active, BS, impl=impl)
            lh, heads = transformer.paged_serve_step(
                cfg, params, heads, tables, token, pos, active, BS, impl=impl)
            np.testing.assert_array_equal(np.asarray(lr), np.asarray(lh))
        for key in ("k", "v"):
            np.testing.assert_array_equal(
                np.asarray(rows[key]), np.asarray(heads[key]).reshape(L, T, D))


class TestDispatchRouting:
    """ops.py routing contracts the serving paths rely on."""

    def test_cpu_routes_to_oracle(self):
        if jax.default_backend() == "tpu":
            pytest.skip("TPU backend compiles the kernel instead")
        assert not kops.use_decode_kernel(128, 16)
        assert not kops.use_fused_mlp(4096, 11008)

    def test_kernel_shape_gates(self, monkeypatch):
        """On a TPU backend the gates read shapes only: head_dim, the kv
        heads' lanes side by side, block_size."""
        monkeypatch.setattr(kops, "_interpret", lambda: False)
        # head_dim 64: the lane-dense kernel when 12 heads fill 768 lanes
        assert kops.use_decode_lanes(64, 12, 16)
        assert not kops.use_decode_kernel(64, 16)
        # 64 and 192 lanes are not whole 128-lane rows; block_size 6 is
        # not sublane-aligned: the oracle
        assert not kops.use_decode_lanes(64, 1, 16)
        assert not kops.use_decode_lanes(64, 3, 16)
        assert not kops.use_decode_lanes(64, 12, 6)
        # head_dim 128 keeps the per-head kernel
        assert kops.use_decode_kernel(128, 16)
        assert not kops.use_decode_lanes(128, 8, 16)
        assert not kops.use_decode_kernel(128, 6)   # block_size % 8 != 0
        assert not kops.use_fused_mlp(64, 11008)
        assert not kops.use_fused_mlp(4096, 128)

    @pytest.mark.parametrize("hd,nkv,want", [(64, 12, "lanes"),
                                             (64, 3, "oracle"),
                                             (128, 8, "per_head")])
    def test_ops_routes_by_shape_on_tpu(self, monkeypatch, hd, nkv, want):
        """``ops.paged_decode_attn`` on a TPU backend: which path a shape
        takes, with the pool in its own row layout, and the lane-dense
        kernel given 128 context tokens a step."""
        monkeypatch.setattr(kops, "_interpret", lambda: False)
        took = {}

        def spy(name):
            def fn(q, *a, **kw):
                took[name] = kw
                return q
            return fn

        monkeypatch.setattr(pk, "paged_decode_attn_lanes", spy("lanes"))
        monkeypatch.setattr(pk, "paged_decode_attn", spy("per_head"))
        monkeypatch.setattr(ref, "paged_attention", spy("oracle"))
        S, bs = 2, 16
        pool = jnp.zeros((4 * bs,) + kops.pool_row_shape(nkv, hd))
        kops.paged_decode_attn(jnp.zeros((S, nkv, hd)), pool, pool,
                               jnp.zeros((S, 4), jnp.int32),
                               jnp.zeros((S,), jnp.int32),
                               jnp.ones((S,), bool), block_size=bs)
        assert list(took) == [want]
        if want == "lanes":
            assert took[want]["blocks"] == 128 // bs

    def test_pool_row_layout(self):
        """Heads under 128 lanes sit side by side in one pool row; wider
        heads keep a row per head (``ops.pool_row_shape``)."""
        assert kops.pool_row_shape(12, 64) == (768,)
        assert kops.pool_row_shape(3, 64) == (192,)
        assert kops.pool_row_shape(8, 128) == (8, 128)
        cfg = tiny_config().replace(num_layers=2, d_model=32, num_heads=4,
                                    num_kv_heads=2)
        pool = transformer.init_paged_caches(cfg, 5, BS)
        assert pool["k"].shape == (2, 5 * BS, 2 * 8)

    def test_ops_paged_decode_attn_is_oracle_off_tpu(self):
        q, k, v, tables, pos = build_scenario(9, lengths=[4, 7])
        args = (jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                jnp.asarray(tables), jnp.asarray(pos),
                jnp.ones((2,), bool))
        got = kops.paged_decode_attn(*args, block_size=BS, window=3)
        want = ref.paged_attention(*args, block_size=BS, window=3)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
