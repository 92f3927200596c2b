"""Multi-device test cases, run in a subprocess with forced host devices.

Invoked by tests/test_distributed.py as
    python distributed_cases.py <case> [devices]
which forces ``devices`` (default 8) fake host devices via XLA_FLAGS
before jax initializes.  Prints "CASE_OK <case>" on success; exits 42
("CASE_SKIP") when the requested device count is not available — the
pytest wrapper turns that into a clean skip.

The ``*_parity`` cases are the sharded-vs-single-device acceptance
anchors of the mesh-native substrate (DESIGN.md §10): one pruning unit's
Gram+solve, held-out perplexity/KL, and a multi-request continuous-batcher
run must be bitwise / token-identical between a 1-device run and the
8-fake-device mesh.
"""
import os
import sys

_DEVICES = int(sys.argv[2]) if len(sys.argv) > 2 else 8
# replace (not prepend to) any inherited device-count flag — the CI
# distributed job exports =8 globally, and a duplicated flag would let
# the job's value override a case asking for a different count (6)
_flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
          if not f.startswith("--xla_force_host_platform_device_count")]
os.environ["XLA_FLAGS"] = " ".join(
    [f"--xla_force_host_platform_device_count={_DEVICES}"] + _flags)

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from repro.launch.mesh import make_mesh  # noqa: E402

if jax.device_count() < _DEVICES:
    # the backend ignored the fake-device flag (e.g. a GPU platform):
    # only 1 device is visible — skip cleanly instead of failing
    print(f"CASE_SKIP need {_DEVICES} devices, have {jax.device_count()}")
    sys.exit(42)


def _tiny_model(seed: int = 0):
    from repro.configs.opt125m_proxy import tiny_config
    from repro.models.registry import model_def

    cfg = tiny_config().replace(num_layers=2, d_model=64, d_ff=128,
                                num_heads=4, num_kv_heads=4, vocab=128)
    model = model_def(cfg)
    return model, model.init(jax.random.PRNGKey(seed))


def case_rowfista():
    from repro.core import fista as fista_lib
    from repro.core import gram as gram_lib
    from repro.distributed.rowfista import sharded_solve

    mesh = make_mesh((2, 4), ("data", "model"))
    rng = np.random.default_rng(0)
    m, n = 32, 48
    a = rng.normal(size=(n, n)).astype(np.float32) * 0.3
    G = jnp.asarray(a @ a.T)
    B = jnp.asarray(rng.normal(size=(m, n)).astype(np.float32))
    y0 = jnp.asarray(rng.normal(size=(m, n)).astype(np.float32))
    L = gram_lib.max_eigval(G) * 1.01
    want, _ = fista_lib.solve(G, B, y0, 0.5, L=L, max_iters=50)
    got = sharded_solve(mesh, G, B, y0, 0.5, L, max_iters=50)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=1e-5)


def case_gram_psum():
    from repro.core import gram as gram_lib
    from repro.distributed.rowfista import sharded_accumulate

    mesh = make_mesh((8,), ("data",))
    rng = np.random.default_rng(1)
    p, n, m = 64, 16, 8
    xd = rng.normal(size=(p, n)).astype(np.float32)
    xp = xd + 0.1 * rng.normal(size=(p, n)).astype(np.float32)
    w = rng.normal(size=(m, n)).astype(np.float32)
    wx = xd @ w.T
    serial = gram_lib.accumulate(gram_lib.init_stats(n), xd, xp, wx)
    sharded = sharded_accumulate(mesh, gram_lib.init_stats(n),
                                 jnp.asarray(xd), jnp.asarray(xp), jnp.asarray(wx))
    np.testing.assert_allclose(np.asarray(sharded.G), np.asarray(serial.G),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(float(sharded.h), float(serial.h), rtol=1e-5)


def case_sharded_train():
    from repro.configs.opt125m_proxy import tiny_config
    from repro.distributed.train import make_train_step
    from repro.models.registry import model_def
    from repro.train import optim

    cfg = tiny_config().replace(num_layers=2, d_model=64, d_ff=128,
                                num_heads=4, num_kv_heads=4, vocab=128)
    model = model_def(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = optim.init(params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 128, jnp.int32)
    batch = {"tokens": tokens, "labels": tokens}

    ocfg = optim.AdamWConfig(lr=1e-3, warmup_steps=0, total_steps=4)
    # unsharded reference
    def step(params, opt_state, batch):
        def loss_fn(p):
            return model.loss(p, batch)
        (l, m), g = jax.value_and_grad(lambda p: loss_fn(p)[0], has_aux=False) \
            (params), None
        return l
    def ref_step(params, opt_state, batch):
        (l, m), grads = jax.value_and_grad(
            lambda p: model.loss(p, batch), has_aux=True)(params)
        p2, o2, om = optim.update(ocfg, grads, opt_state, params)
        return p2, o2, l

    p_ref, o_ref, l_ref = jax.jit(ref_step)(params, opt, batch)

    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    build = make_train_step(model, mesh, ocfg, donate=False)
    fn, _ = build(params, opt, batch)
    p_sh, o_sh, metrics = fn(params, opt, batch)
    assert np.isclose(float(metrics["loss"]), float(l_ref), rtol=1e-4), \
        (float(metrics["loss"]), float(l_ref))
    for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_leaves_with_path(p_ref),
            jax.tree_util.tree_leaves_with_path(p_sh)):
        np.testing.assert_allclose(np.asarray(b, np.float32),
                                   np.asarray(a, np.float32),
                                   rtol=5e-3, atol=5e-4)


def case_pipeline():
    from repro.distributed.pipeline import (pipeline_apply, split_microbatches,
                                            merge_microbatches, stack_to_stages)

    mesh = make_mesh((4, 2), ("pod", "data"))
    rng = np.random.default_rng(2)
    L, D = 8, 16
    ws = jnp.asarray(rng.normal(size=(L, D, D)).astype(np.float32) * 0.2)

    def layer(w, x):
        return jnp.tanh(x @ w)

    def plain(x):
        for i in range(L):
            x = layer(ws[i], x)
        return x

    def stage_fn(stage_params, x):
        def body(h, w):
            return layer(w, h), None
        out, _ = jax.lax.scan(body, x, stage_params)
        return out

    x = jnp.asarray(rng.normal(size=(12, D)).astype(np.float32))
    xs = split_microbatches(x, 6)
    stages = stack_to_stages(ws, 4)
    got = merge_microbatches(pipeline_apply(mesh, stage_fn, stages, xs))
    np.testing.assert_allclose(np.asarray(got), np.asarray(plain(x)),
                               rtol=1e-4, atol=1e-5)


def case_compression():
    from repro.distributed.compression import (compressed_allreduce,
                                               ef_compress, init_residuals)

    mesh = make_mesh((8,), ("data",))
    rng = np.random.default_rng(3)
    D = 8
    grads = {"w": jnp.asarray(rng.normal(size=(D, 16, 8)).astype(np.float32))}
    residuals = init_residuals(grads)
    mean, new_r = compressed_allreduce(mesh, grads, residuals)
    want = np.asarray(grads["w"]).mean(axis=0)
    got = np.asarray(mean["w"][0])
    # int8 quantization error bounded by sum of per-shard scales / 127
    scale_bound = np.abs(np.asarray(grads["w"])).max(axis=(1, 2)).sum() / 127 / D
    assert np.abs(got - want).max() <= scale_bound * 1.5 + 1e-6
    # error feedback: residual equals what quantization dropped
    q, s, r = ef_compress(grads["w"][0], residuals["w"][0])
    np.testing.assert_allclose(
        np.asarray(r), np.asarray(grads["w"][0]) - np.asarray(q, np.float32) * s,
        rtol=1e-5, atol=1e-6)


def case_ef_convergence():
    """Error feedback makes quantized SGD track exact SGD on a quadratic."""
    from repro.distributed.compression import ef_compress

    rng = np.random.default_rng(4)
    A = jnp.asarray(rng.normal(size=(16, 16)).astype(np.float32))
    Q = A @ A.T / 16 + jnp.eye(16)
    x_exact = jnp.ones((16,))
    x_q = jnp.ones((16,))
    r = jnp.zeros((16,))
    lr = 0.05
    for _ in range(200):
        g_exact = Q @ x_exact
        x_exact = x_exact - lr * g_exact
        g = Q @ x_q
        q, s, r = ef_compress(g, r)
        x_q = x_q - lr * (q.astype(jnp.float32) * s)
    assert float(jnp.linalg.norm(x_q)) < 1e-2, float(jnp.linalg.norm(x_q))


def case_moe_sharded():
    from repro.distributed.train import make_train_step
    from repro.models.registry import load_arch
    from repro.train import optim

    model = load_arch("mixtral-8x7b", smoke=True)
    params = model.init(jax.random.PRNGKey(0))
    opt = optim.init(params)
    batch = model.make_batch(jax.random.PRNGKey(1), 4, 16)
    mesh = make_mesh((2, 4), ("data", "model"))
    build = make_train_step(model, mesh, optim.AdamWConfig(), donate=False)
    fn, _ = build(params, opt, batch)
    _, _, metrics = fn(params, opt, batch)
    assert np.isfinite(float(metrics["loss"]))


def case_debug_mesh():
    """Device-backed construction of the debug mesh at the forced count
    (run at 6 and 8 devices by the wrapper) — every factorization must
    build and keep data >= model."""
    from repro.launch.mesh import make_debug_mesh

    n = jax.device_count()
    mesh = make_debug_mesh(n)
    assert int(np.prod(list(mesh.shape.values()))) == n, mesh.shape
    assert mesh.shape["data"] >= mesh.shape["model"] >= 1, mesh.shape
    if n % 2 == 0:
        m2 = make_debug_mesh(n, multi_pod=True)
        assert int(np.prod(list(m2.shape.values()))) == n, m2.shape
        assert m2.shape["pod"] == 2


def case_prune_unit_parity():
    """Acceptance anchor 1 (prune): Gram accumulation data-parallel over
    8 calibration micro-batches (one per shard + one psum) + the fused
    group solves yield BITWISE-identical pruned weights to the serial
    single-device path, for every unit of the model."""
    from repro import api
    from repro.data import CalibConfig, CorpusConfig, MarkovCorpus, \
        calibration_batches

    model, params = _tiny_model()
    corpus = MarkovCorpus(CorpusConfig(vocab=model.cfg.vocab, seed=7))
    calib = calibration_batches(corpus, CalibConfig(num_sequences=32,
                                                    seq_len=32, batch_size=4))
    assert len(calib) == 8      # one micro-batch per data shard (bitwise
    # contract of the psum merge — see distributed/executor.py)
    solver = {"fista_iters": 5, "max_outer": 4}
    serial = api.PruneRecipe(sparsity="2:4", solver=solver)
    mesh = api.PruneRecipe(sparsity="2:4", solver=solver,
                           mesh={"devices": 8, "data_parallel": 8,
                                 "model_parallel": 1})
    p1, _, _ = api.prune(model, params, calib, serial)
    p8, _, s8 = api.prune(model, params, calib, mesh)
    assert s8["mesh"] == {"data": 8, "model": 1, "devices": 8}
    for (path, a), (_, b) in zip(jax.tree_util.tree_leaves_with_path(p1),
                                 jax.tree_util.tree_leaves_with_path(p8)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), \
            f"{jax.tree_util.keystr(path)} diverged under the 8-device mesh"


def case_gram_init_seeding():
    """sharded_group_stats seeds SHARD 0's scan with the carried-in init
    (a group spanning several shape buckets), preserving the serial
    left-fold association ((init+g0)+g1)+... — bitwise, not just close."""
    from repro.core import gram as gram_lib
    from repro.distributed.executor import MeshConfig, MeshExecutor

    ex = MeshExecutor(MeshConfig(devices=8, data_parallel=8,
                                 model_parallel=1))
    rng = np.random.default_rng(0)
    n, B = 16, 8
    xd = jnp.asarray(rng.normal(size=(B, 32, n)).astype(np.float32))
    xp = xd + 0.1 * jnp.asarray(rng.normal(size=(B, 32, n)).astype(np.float32))
    wx = jnp.asarray(rng.normal(size=(B, 32, n)).astype(np.float32))
    # nonzero carried stats, as left by an earlier shape bucket
    init = {"op": gram_lib.accumulate(
        gram_lib.init_stats(n), xd[0] * 0.3, xp[0] * 0.3, wx[0] * 0.3)}

    def scan_fn(start, current, ws, caps, ps, **kw):
        def body(acc, xs):
            return {"op": gram_lib.accumulate(acc["op"], xs["xd"], xs["xp"],
                                              xs["wx"])}, None
        out, _ = jax.lax.scan(body, start, caps)
        return out

    serial = init
    for b in range(B):
        serial = {"op": gram_lib.accumulate(serial["op"], xd[b], xp[b], wx[b])}
    sharded = ex.sharded_group_stats(
        scan_fn, init, {}, {}, {"xd": xd, "xp": xp, "wx": wx},
        jnp.zeros((B,), jnp.float32))
    for a, b in zip(jax.tree_util.tree_leaves(serial),
                    jax.tree_util.tree_leaves(sharded)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), \
            "carried-init sharded accumulation diverged from serial fold"


def case_rowfista_solver_parity():
    """FISTA with row-sharded inner solves (PruneRecipe mesh.model_parallel
    + solver.row_shard, the distributed/rowfista path) matches the host
    Algorithm-1 oracle: identical sparsity supports, weights to fp32
    round-off."""
    from repro import api
    from repro.data import CalibConfig, CorpusConfig, MarkovCorpus, \
        calibration_batches

    model, params = _tiny_model()
    corpus = MarkovCorpus(CorpusConfig(vocab=model.cfg.vocab, seed=7))
    calib = calibration_batches(corpus, CalibConfig(num_sequences=16,
                                                    seq_len=32, batch_size=4))
    solver = {"fista_iters": 5, "max_outer": 4, "outer_impl": "host"}
    host = api.PruneRecipe(sparsity="2:4", solver=solver)
    row = api.PruneRecipe(sparsity="2:4", solver=dict(solver, row_shard=True),
                          mesh={"devices": 8, "data_parallel": 2,
                                "model_parallel": 4})
    p1, _, _ = api.prune(model, params, calib, host)
    p2, _, _ = api.prune(model, params, calib, row)
    for (path, a), (_, b) in zip(jax.tree_util.tree_leaves_with_path(p1),
                                 jax.tree_util.tree_leaves_with_path(p2)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.array_equal(a == 0, b == 0), \
            f"{jax.tree_util.keystr(path)}: sparsity support diverged"
        np.testing.assert_allclose(b, a, rtol=2e-5, atol=2e-5,
                                   err_msg=jax.tree_util.keystr(path))


def case_eval_parity():
    """Acceptance anchor 2 (eval): held-out perplexity and KL with the
    batches sharded over "data" are BITWISE-equal to the serial loop
    (whole batches stay device-local; per-batch scalars reduce on the
    host in batch order)."""
    from repro.data import CorpusConfig, MarkovCorpus
    from repro.distributed.executor import MeshConfig, MeshExecutor
    from repro.eval import EvalConfig, evaluate_perplexity, kl_divergence

    model, params = _tiny_model()
    pruned = _tiny_model(seed=1)[1]     # any second params for KL
    corpus = MarkovCorpus(CorpusConfig(vocab=model.cfg.vocab, seed=7))
    cfg = EvalConfig(num_batches=8, batch_size=4, seq_len=32, kl_batches=8)
    for dxm in ((8, 1), (4, 2)):
        ex = MeshExecutor(MeshConfig(devices=8, data_parallel=dxm[0],
                                     model_parallel=dxm[1]))
        serial = evaluate_perplexity(model, params, corpus, cfg)
        sharded = evaluate_perplexity(model, params, corpus, cfg, executor=ex)
        assert serial.ce_nats == sharded.ce_nats and serial.ppl == sharded.ppl, \
            (dxm, serial.ce_nats, sharded.ce_nats)
        ks = kl_divergence(model, params, pruned, corpus, cfg)
        kx = kl_divergence(model, params, pruned, corpus, cfg, executor=ex)
        assert ks.kl == kx.kl and ks.top1_agreement == kx.top1_agreement, dxm


def case_batcher_tp_parity():
    """Acceptance anchor 3 (serve): a multi-request continuous-batcher run
    with params TP-sharded over "model" (Megatron col/row rules) and the
    paged KV pool heads-sharded is TOKEN-IDENTICAL to the single-device
    batcher — dense and packed-2:4, greedy and temperature."""
    from repro.core.sparsity import round_tree_nm
    from repro.distributed.executor import MeshConfig, MeshExecutor
    from repro.serve import BatchConfig, ContinuousBatcher, synthetic_trace

    model, params = _tiny_model()
    pruned = round_tree_nm(params)
    bc = BatchConfig(slots=3, block_size=8, max_blocks_per_request=3,
                     num_blocks=24)
    ex = MeshExecutor(MeshConfig(devices=8, data_parallel=2, model_parallel=4))

    def run(weights, sparse, temp, executor):
        trace = synthetic_trace(5, rate=0.0, vocab=model.cfg.vocab,
                                prompt_len=(4, 10), max_new_tokens=6,
                                temperature=temp, seed=3)
        import dataclasses
        b = ContinuousBatcher(model, weights,
                              dataclasses.replace(bc, sparse=sparse),
                              executor=executor)
        return b, b.run(trace)

    for weights, sparse in ((params, "dense"), (pruned, "packed")):
        for temp in (0.0, 0.8):
            _, r1 = run(weights, sparse, temp, None)
            b2, r2 = run(weights, sparse, temp, ex)
            if sparse == "packed":
                assert b2.sparse_stats["mode"] == "packed"
            for a, b in zip(r1, r2):
                assert np.array_equal(a.tokens, b.tokens), \
                    (sparse, temp, a.id, a.tokens, b.tokens)


def case_batcher_chunked_prefix_tp_parity():
    """Chunked prefill + radix prefix cache under tensor parallelism:
    the chunk executable's scatter/gather runs over the heads-sharded
    paged pool, and cache-shared blocks are shared ACROSS the shards —
    tokens must stay identical to the single-device chunked batcher."""
    import dataclasses
    from repro.core.sparsity import round_tree_nm
    from repro.distributed.executor import MeshConfig, MeshExecutor
    from repro.serve import BatchConfig, ContinuousBatcher, Request

    model, params = _tiny_model()
    pruned = round_tree_nm(params)
    bc = BatchConfig(slots=3, block_size=8, max_blocks_per_request=3,
                     num_blocks=24, prefill_chunk=8, prefix_cache=True)
    ex = MeshExecutor(MeshConfig(devices=8, data_parallel=2, model_parallel=4))

    rng = np.random.default_rng(31)
    prefix = rng.integers(0, model.cfg.vocab, size=8).astype(np.int32)
    spec = [(4, 6), (9, 4), (2, 5), (7, 6)]

    def trace(temp):
        return [Request(id=i, prompt=np.concatenate(
                            [prefix, rng.integers(0, model.cfg.vocab, size=p)]
                        ).astype(np.int32),
                        max_new_tokens=n, temperature=temp)
                for i, (p, n) in enumerate(spec)]

    for weights, sparse in ((params, "dense"), (pruned, "packed")):
        for temp in (0.0, 0.8):
            reqs = trace(temp)
            runs = []
            for executor in (None, ex):
                b = ContinuousBatcher(model, weights,
                                      dataclasses.replace(bc, sparse=sparse),
                                      executor=executor)
                res = b.run([dataclasses.replace(r) for r in reqs])
                assert sum(r.prefix_hit_tokens for r in res) > 0, \
                    (sparse, temp, "no cache hits")
                runs.append(res)
            for a, b2 in zip(*runs):
                assert np.array_equal(a.tokens, b2.tokens), \
                    (sparse, temp, a.id, a.tokens, b2.tokens)


def case_paged_attn_shardmap():
    """The fused decode attention's shard_map boundary (models/common.
    _paged_attn_sharded): with the KV pools heads-sharded over "model"
    and the block table / positions replicated, the output equals the
    meshless local dispatch."""
    from repro.models import common

    rng = np.random.default_rng(0)
    S, nkv, g, hd, NB, BS = 3, 4, 2, 8, 10, 4   # nkv % model_parallel == 0
    T = NB * BS
    q = jnp.asarray(rng.standard_normal((S, nkv * g, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((T, nkv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((T, nkv, hd)), jnp.float32)
    lengths = [9, 4, 1]
    perm = rng.permutation(np.arange(1, NB))
    tables = np.zeros((S, 3), np.int32)         # trash-padded tails
    used = 0
    for s, L in enumerate(lengths):
        nb = -(-L // BS)
        tables[s, :nb] = perm[used:used + nb]
        used += nb
    tables = jnp.asarray(tables)
    pos = jnp.asarray(np.asarray(lengths, np.int32) - 1)
    active = jnp.asarray([True, True, False])
    args = (q, k, v, tables, pos, active, BS, 3, 0.0)

    want = common._paged_attn_sharded(*args)
    mesh = make_mesh((2, 4), ("data", "model"))
    with mesh, jax.sharding.set_mesh(mesh):
        got = common._paged_attn_sharded(*args)
    act = np.asarray(active)
    np.testing.assert_array_equal(np.asarray(got)[act], np.asarray(want)[act])


def case_engine_tp_parity():
    """Engine.generate with TP-sharded params + caches decodes the same
    tokens as the single-device engine (greedy and temperature)."""
    from repro.distributed.executor import MeshConfig, MeshExecutor
    from repro.serve import Engine, ServeConfig

    model, params = _tiny_model()
    ex = MeshExecutor(MeshConfig(devices=8, data_parallel=2, model_parallel=4))
    prompt = jnp.asarray(
        np.random.default_rng(1).integers(0, model.cfg.vocab, size=(2, 6)),
        jnp.int32)
    for temp in (0.0, 0.7):
        cfg = ServeConfig(max_new_tokens=5, temperature=temp, cache_len=32)
        t1 = Engine(model, params, cfg).generate(prompt)
        t2 = Engine(model, params, cfg, executor=ex).generate(prompt)
        assert np.array_equal(t1, t2), (temp, t1, t2)


CASES = {k[5:]: v for k, v in list(globals().items()) if k.startswith("case_")}

if __name__ == "__main__":
    name = sys.argv[1]
    CASES[name]()
    print(f"CASE_OK {name}")
