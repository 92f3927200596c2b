"""CPU tests of the benchmark's yardstick and of its checks.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They cover the trace reduction and the readers on traces recorded on
the chip, the work counts against hand-worked shapes, the pins that
hold the dense default of the layout rules to what the harness did
before, a configuration that brings its own layout (an expert fixture
in ``testdata``), the traffic generator, the TTFT reading under a slow
prefill, the controls (the reference one precision lower must read
worse than the program), and the faults that a run must report as not
correct.  The cells run here at small sizes, with the harness's look
for a chip skipped.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

from lib import harness, traffic, weights, work  # noqa: E402


@pytest.fixture(autouse=True)
def _no_compile_cache():
    """Cached CPU programs from other hosts only add noise here."""
    import jax
    jax.config.update("jax_enable_compilation_cache", False)

TINY_OPT = {
    "name": "tiny-opt", "reference": "decoder_ref", "arch": "opt125m-proxy",
    "model_overrides": {"num_layers": 2, "d_model": 128, "num_heads": 4,
                        "num_kv_heads": 4, "d_ff": 512, "vocab": 512,
                        "tie_embeddings": True},
    "hidden_size": 128, "ffn_dim": 512, "num_attention_heads": 4,
    "num_hidden_layers": 2, "vocab_size": 512, "do_layer_norm_before": True,
    "layer_norm_eps": 1e-5, "enable_bias": True, "activation_function": "relu",
    "tie_word_embeddings": True, "torch_dtype": "float16",
    "assumed": {"activation_function": "gelu_pytorch_tanh",
                "rope_theta": 1e4, "torch_dtype": "bfloat16"}}
TINY_ILM = {
    "name": "tiny-ilm", "reference": "decoder_ref", "arch": "internlm2-20b",
    "model_overrides": {"num_layers": 2, "d_model": 256, "num_heads": 4,
                        "num_kv_heads": 2, "d_ff": 512, "vocab": 512},
    "hidden_size": 256, "intermediate_size": 512, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 512,
    "rope_theta": 1e6, "rms_norm_eps": 1e-5, "hidden_act": "silu",
    "torch_dtype": "bfloat16"}
TINY_CALIB = {"num_sequences": 16, "seq_len": 128, "batch_size": 8}
TINY_SERVING = {"slots": 4, "block_size": 16, "max_blocks_per_request": 12,
                "num_blocks": 64, "prefill_chunk": 64, "decode_impl": "fused"}
TINY_CHAT = {"arrivals": "poisson",
             "prompt": {"dist": "lognormal", "median": 40, "sigma": 1.0,
                        "min": 4, "max": 150},
             "output": {"dist": "lognormal", "median": 8, "sigma": 0.8,
                        "min": 2, "max": 30},
             "temperature": 0.0}


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------
def _mix(name):
    return harness.read_json(BENCH, "traffic", f"{name}.json")


@pytest.mark.parametrize("name", ["chat", "batch"])
def test_traffic_same_work_for_every_seed(name):
    """Two seeds offer the same sizes and arrivals in the same order;
    only the token ids differ."""
    mix = _mix(name)
    n = traffic.count(mix, 8.0, 30)
    a = traffic.generate(mix, 2 ** 31 + 5, 92544, n, 8.0)
    b = traffic.generate(mix, 7, 92544, n, 8.0)
    for key in ("max_new", "arrival"):
        assert [r[key] for r in a] == [r[key] for r in b]
    assert [len(r["prompt"]) for r in a] == [len(r["prompt"]) for r in b]
    assert not all((x["prompt"] == y["prompt"]).all() for x, y in zip(a, b))
    # the fixed order is shuffled, not sorted
    assert [len(r["prompt"]) for r in a] != sorted(len(r["prompt"]) for r in a)


def test_traffic_deterministic_and_in_range():
    mix = _mix("chat")
    a = traffic.generate(mix, 99, 92544, 300, 8.0)
    b = traffic.generate(mix, 99, 92544, 300, 8.0)
    assert all((x["prompt"] == y["prompt"]).all() and x["arrival"] ==
               y["arrival"] and x["max_new"] == y["max_new"]
               for x, y in zip(a, b))
    P = np.array([len(r["prompt"]) for r in a])
    O = np.array([r["max_new"] for r in a])
    assert P.min() >= 32 and P.max() <= 4096 and O.min() >= 8 and O.max() <= 512
    assert abs(np.median(P) - 512) < 20 and abs(np.median(O) - 128) < 8
    assert a[0]["arrival"] == 0.0 and np.all(np.diff([r["arrival"] for r in a]) > 0)
    span = a[-1]["arrival"]
    assert 300 / 8.0 * 0.9 < span < 300 / 8.0 * 1.05
    assert all(0 <= r["prompt"].min() and r["prompt"].max() < 92544 for r in a)
    assert traffic.count(mix, 8.0, 30) == 240


# ---------------------------------------------------------------------------
# work counts
# ---------------------------------------------------------------------------
ILM_DIMS = {"layers": 8, "d": 6144, "heads": 48, "kv_heads": 8,
            "head_dim": 128, "d_ff": 16384, "vocab": 92544, "act": "silu"}


def test_layer_weights_by_hand():
    # InternLM2-20B layer: q 6144x6144, k/v 6144x1024 each, o 6144x6144,
    # gate/up 6144x16384 each, down 16384x6144
    assert work.layer_weights(ILM_DIMS) == (
        2 * 6144 * 6144 + 2 * 6144 * 1024 + 3 * 6144 * 16384)
    opt = dict(ILM_DIMS, d=768, heads=12, kv_heads=12, head_dim=64,
               d_ff=3072, act="gelu")
    assert work.layer_weights(opt) == 4 * 768 * 768 + 2 * 768 * 3072


def test_decode_step_bytes_by_hand():
    lw = 390_070_272                       # the layer weights counted above
    assert work.layer_weights(ILM_DIMS) == lw
    w = work.decode_step(ILM_DIMS, tokens=20, context=20_000, packed=True)
    kv = 20_000 * 2 * 8 * 8 * 128 * 2      # K and V, 8 layers, 8 heads of 128
    assert w["bytes"] == 8 * lw * 1.25 + 6144 * 92544 * 2 + kv
    d = work.decode_step(ILM_DIMS, tokens=20, context=20_000, packed=False)
    assert d["bytes"] - w["bytes"] == pytest.approx(8 * lw * 0.75)
    assert d["flops"] == pytest.approx(
        2 * 20 * (8 * lw + 6144 * 92544) + 4 * 8 * 48 * 128 * 20_000)


def test_packed_kernels_and_attention_by_hand():
    # spmm24: q and o 6144x6144, k and v 6144x1024, half the products kept
    s = work.spmm24(ILM_DIMS, rows=32)
    attn = 2 * 6144 * 6144 + 2 * 6144 * 1024
    acts = 32 * 2 * ((6144 + 6144) * 2 + (6144 + 1024) * 2)
    assert s["flops"] == pytest.approx(8 * 32 * attn)
    assert s["bytes"] == pytest.approx(8 * (attn * 1.25 + acts))
    # fused_mlp24: gate, up 6144x16384 and down 16384x6144; only the
    # input and output rows of width 6144 leave the kernel
    m = work.fused_mlp24(ILM_DIMS, rows=32)
    mlp = 3 * 6144 * 16384
    assert m["flops"] == pytest.approx(8 * 32 * mlp)
    assert m["bytes"] == pytest.approx(8 * (mlp * 1.25 + 2 * 32 * 6144 * 2))
    assert (s["flops"] + m["flops"]) == pytest.approx(
        8 * 32 * work.layer_weights(ILM_DIMS))
    a = work.decode_attention(ILM_DIMS, tokens=10, context=1000)
    assert a["bytes"] == pytest.approx(1000 * 2 * 8 * 8 * 128 * 2
                                       + 2 * 8 * 10 * 48 * 128 * 2)


def test_prune_flops_by_hand():
    opt = {"layers": 12, "d": 768, "heads": 12, "kv_heads": 12,
           "head_dim": 64, "d_ff": 3072, "act": "gelu", "vocab": 50272}
    tokens, T = 128 * 2048, 2048
    fwd = 2 * tokens * (4 * 768 ** 2 + 2 * 768 * 3072) \
        + 2 * 128 * T * T * 768
    grams = 3 * 2 * tokens * (5 * 768 ** 2 + 3072 ** 2) \
        + 2 * tokens * (4 * 768 ** 2 + 2 * 768 * 3072)
    rep = [{"shape": (768, 3072), "fista_iters": 100, "outer_iters": 5}]
    solve = 2 * 768 * 3072 ** 2 * (100 + 10 + 1) + 64 * 2 * 3072 ** 2
    assert work.prune(opt, 128, T, rep) == pytest.approx(
        12 * (6 * fwd + grams) + solve)


# ---------------------------------------------------------------------------
# pins: the dense default of the layout rules gives what the harness gave
# before configurations could bring their own layout
# ---------------------------------------------------------------------------
CONFIG_FILES = ["opt-125m", "internlm2-20b.d8"]


def _pinned_shapes(dm, bias):
    """A copy of ``weights.shapes`` as it was, with its role rule."""
    L, d, f, V = dm["layers"], dm["d"], dm["d_ff"], dm["vocab"]
    q, kv = dm["heads"] * dm["head_dim"], dm["kv_heads"] * dm["head_dim"]
    out = {"embed": (V, d)} if dm["tie"] else {"embed": (V, d), "head": (d, V)}
    norms = ("scale", "bias") if dm["norm"] == "layernorm" else ("scale",)
    for n in norms:
        out[f"layers/ln1/{n}"] = out[f"layers/ln2/{n}"] = (L, d)
        out[f"final_norm/{n}"] = (d,)
    out.update({"layers/attn/wq": (L, d, q), "layers/attn/wk": (L, d, kv),
                "layers/attn/wv": (L, d, kv), "layers/attn/wo": (L, q, d)})
    if bias:
        out.update({"layers/attn/bq": (L, q), "layers/attn/bk": (L, kv),
                    "layers/attn/bv": (L, kv)})
    if dm["act"] == "silu":
        out.update({"layers/mlp/gate": (L, d, f), "layers/mlp/up": (L, d, f),
                    "layers/mlp/down": (L, f, d)})
    else:
        out.update({"layers/mlp/fc1": (L, d, f), "layers/mlp/b1": (L, f),
                    "layers/mlp/fc2": (L, f, d), "layers/mlp/b2": (L, d)})
    return out


def _pinned_role(path, shp):
    if path.endswith("scale"):
        return "scale"
    if path == "embed":
        return "embed"
    if path == "head":                  # unit gain, never masked
        return "dense_matrix"
    return "matrix" if len(shp) == 3 else "vector"


def _pinned_make(key, leaves, sparse24, tie):
    """A copy of ``weights._make`` as it was, on (path, shape) leaves."""
    import jax
    import jax.numpy as jnp
    from lib import weights

    def make(key):
        out = {}
        for i, (path, shp) in enumerate(leaves):
            k = jax.random.fold_in(key, i)
            z = jax.random.normal(k, shp, jnp.bfloat16)
            if path.endswith("scale"):
                w = 1.0 + 0.1 * z
            elif path == "embed":
                w = z * (shp[-1] ** -0.5) if tie else z
            elif len(shp) == 3 or path == "head":
                w = z * (shp[-2] ** -0.5)
                if sparse24 and len(shp) == 3:
                    w = weights.mask24(w)
            else:
                w = 0.1 * z
            out[path] = w.astype(jnp.bfloat16)
        return out
    return jax.jit(make)(key)


def _config_dims(name):
    conf = harness.read_json(BENCH, "configs", f"{name}.json")
    return conf, harness.ref_dims(conf)


@pytest.mark.parametrize("name", CONFIG_FILES)
def test_pinned_leaves_of_config_files(name):
    """The dense reference's layout gives the leaves, roles and dtypes
    of the old dense rule, in the same order, and the harness hands
    them to the work counts."""
    from lib import weights
    conf, dm = _config_dims(name)
    bias = conf.get("enable_bias", False)
    ref = harness.reference(conf)
    want = tuple((p, s, _pinned_role(p, s), "bfloat16")
                 for p, s in sorted(_pinned_shapes(dm, bias).items()))
    assert weights.leaves(dm, bias, ref.layout) == want
    assert dm["leaves"] == want


@pytest.mark.parametrize("sparse24", [False, True], ids=["dense", "2:4"])
@pytest.mark.parametrize("name", CONFIG_FILES)
def test_pinned_weights_bit_identical(name, sparse24):
    """At a reduced size of each file's dims, every seeded array is bit
    for bit what the harness seeded before layouts."""
    import jax
    from lib import weights
    conf, dm = _config_dims(name)
    small = dict(dm, layers=2, d=64, heads=4, kv_heads=2 if dm["kv_heads"]
                 != dm["heads"] else 4, head_dim=16, d_ff=128, vocab=256)
    bias = conf.get("enable_bias", False)
    seed = 2 ** 33 + 17
    layout = harness.reference(conf).layout
    got = jax.tree_util.tree_flatten_with_path(
        weights.make(small, bias, seed, sparse24, layout))[0]
    want = _pinned_make(weights.seed_key(seed),
                        tuple(sorted(_pinned_shapes(small, bias).items())),
                        sparse24, small["tie"])
    assert len(got) == len(want)
    for path, arr in got:
        key = "/".join(p.key for p in path)
        assert arr.dtype == want[key].dtype
        np.testing.assert_array_equal(np.asarray(arr).view(np.uint16),
                                      np.asarray(want[key]).view(np.uint16))


def _pinned_work(dm):
    """Copies of the work counts as they were, before layer kinds."""
    d, f = dm["d"], dm["d_ff"]
    q, kv = dm["heads"] * dm["head_dim"], dm["kv_heads"] * dm["head_dim"]
    mats = {"attn/wq": (d, q), "attn/wk": (d, kv), "attn/wv": (d, kv),
            "attn/wo": (q, d)}
    if dm["act"] == "silu":
        mats.update({"mlp/gate": (d, f), "mlp/up": (d, f), "mlp/down": (f, d)})
    else:
        mats.update({"mlp/fc1": (d, f), "mlp/fc2": (f, d)})
    lw = sum(i * o for i, o in mats.values())
    L, hd = dm["layers"], dm["head_dim"]
    kvb = 2 * L * dm["kv_heads"] * hd * 2

    def decode_step(tokens, context, packed):
        head = d * dm["vocab"]
        flops = 2.0 * tokens * (L * lw * (0.5 if packed else 1.0) + head)
        flops += 4.0 * L * dm["heads"] * hd * context
        wbytes = L * lw * (1.25 if packed else 2) + head * 2
        return {"flops": flops, "bytes": wbytes + context * kvb}

    def kernel(prefix, rows):
        flops, nbytes = 0.0, 0.0
        for key, (i, o) in mats.items():
            if key.startswith(prefix):
                flops += 2.0 * rows * i * o * 0.5
                nbytes += i * o * 1.25 + (rows * (i + o) * 2
                                          if prefix == "attn/" else 0)
        if prefix == "mlp/":
            nbytes += 2 * rows * d * 2
        return {"flops": L * flops, "bytes": L * nbytes}

    def decode_attention(tokens, context):
        return {"flops": 4.0 * L * dm["heads"] * hd * context,
                "bytes": context * kvb + 2.0 * L * tokens * dm["heads"] * hd
                * 2}

    def unit_forward(seqs, seq_len):
        return 2.0 * (seqs * seq_len) * lw \
            + 2.0 * seqs * seq_len * seq_len * dm["heads"] * hd

    def prune(seqs, seq_len, reports):
        tokens = seqs * seq_len
        flops = (2 + 4) * unit_forward(seqs, seq_len)
        for i, o in mats.values():
            flops += 3 * 2.0 * tokens * i * i + 2.0 * tokens * i * o
        flops *= L
        for r in reports:
            m, n = r["shape"]
            flops += 2.0 * m * n * n * (r["fista_iters"]
                                        + 2 * r["outer_iters"] + 1) \
                + 64 * 2.0 * n * n
        return flops
    return {"layer_weights": lw,
            "decode_step": decode_step, "spmm24": lambda r: kernel("attn/", r),
            "fused_mlp24": lambda r: kernel("mlp/", r),
            "decode_attention": decode_attention, "unit_forward": unit_forward,
            "prune": prune}


WORK_FNS = ["decode_step", "spmm24", "fused_mlp24", "decode_attention",
            "unit_forward", "prune"]


def _work_cases():
    reps = [{"shape": (768, 3072), "fista_iters": 97, "outer_iters": 5},
            {"shape": (6144, 1024), "fista_iters": 13, "outer_iters": 2}]
    return {
        "decode_step": [(t, c, p) for t, c, p in
                        [(63.1875, 22615.25, True), (20, 20_000, False),
                         (1, 17, True)]],
        "spmm24": [(r,) for r in (1, 32, 64)],
        "fused_mlp24": [(r,) for r in (1, 32, 64)],
        "decode_attention": [(63.1875, 22615.25), (10, 1000)],
        "unit_forward": [(128, 2048), (16, 128)],
        "prune": [(s, t, reps) for s, t in [(128, 2048), (16, 128)]]}


@pytest.mark.parametrize("fn", WORK_FNS + ["sizes"])
@pytest.mark.parametrize("name", CONFIG_FILES)
def test_pinned_work_counts(name, fn):
    """Every count for both files' dims equals the count before layer
    kinds, to the last bit (``==``, no tolerance)."""
    _, dm = _config_dims(name)
    old = _pinned_work(dm)
    if fn == "sizes":
        assert work.layer_weights(dm) == old["layer_weights"]
        return
    for args in _work_cases()[fn]:
        got = getattr(work, fn)(dm, *args)
        want = old[fn](*args)
        if isinstance(want, dict):
            got = {k: got[k] for k in want}
        assert got == want, (fn, args)


@pytest.mark.parametrize("fn", WORK_FNS)
@pytest.mark.parametrize("name", CONFIG_FILES)
def test_declared_dense_kinds_equal_the_default(name, fn):
    """The dense decoder declared as one layer kind over its layout's
    leaves (in, out, stored, role and dtype read from them) counts what
    the default of its sizes counts, to the last bit."""
    _, dm = _config_dims(name)
    hd = dm["head_dim"]
    declared = dict(dm, layer_kinds=[{
        "count": dm["layers"],
        "operators": {p: 1 for p, _, r, _ in dm["leaves"] if r == "matrix"},
        "attention": {"heads": dm["heads"], "head_dim": hd,
                      "kv_elems_per_token": 2 * dm["kv_heads"] * hd,
                      "window": None}}])
    for args in _work_cases()[fn]:
        assert getattr(work, fn)(declared, *args) == \
            getattr(work, fn)(dm, *args), (fn, args)


# ---------------------------------------------------------------------------
# a configuration that brings its own layout: a test fixture of experts
# ---------------------------------------------------------------------------
def _fixture():
    return harness.read_json(BENCH, "testdata", "qwen2-moe-smoke.json")


def test_layout_fixture_builds_and_seeds_the_programs_tree():
    """The nested ``moe`` override reaches the program's config, and the
    seeded tree has the structure, shapes and dtypes of the program's
    own init: experts stacked (layers, experts, in, out), 2:4 along
    their input axis; the router (float32) and the gate never masked."""
    import jax
    conf = _fixture()
    model = harness.build_model(conf)
    assert model.cfg.moe.num_experts == 8 and model.cfg.moe.top_k == 2
    assert model.cfg.moe.shared_ff == 64 and model.cfg.moe.num_shared == 1
    tree = weights.for_config(conf, 2 ** 31 + 3, sparse24=True)
    want = jax.eval_shape(model.init, jax.random.key(0))
    assert jax.tree.structure(tree) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(want)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    moe = tree["layers"]["moe"]
    w = np.asarray(moe["w_gate"], np.float32)             # (2, 8, 64, 32)
    kept = (w.reshape(2, 8, 16, 4, 32) != 0).sum(axis=3)
    assert kept.max() == 2
    assert moe["router"].dtype == np.float32
    assert np.all(np.asarray(moe["router"]) != 0)
    assert np.all(np.asarray(moe["shared_gate"], np.float32) != 0)


@pytest.mark.parametrize("change", [
    {"moe": {"top_k": 3}}, {"num_layers": 3}, {"window": 16}],
    ids=["nested size", "depth", "window"])
def test_layout_fixture_program_sizes_are_checked(change):
    """A program config that departs from the reference's
    ``program_sizes``, in a nested config too, is refused."""
    conf = _fixture()
    over = dict(conf["model_overrides"])
    for k, v in change.items():
        over[k] = dict(over[k], **v) if isinstance(v, dict) else v
    with pytest.raises(ValueError, match="departs from the file"):
        harness.build_model(dict(conf, model_overrides=over))


def test_program_value_refuses_what_the_config_lacks():
    from repro.configs import qwen2_moe_a2_7b
    cfg = qwen2_moe_a2_7b.config()
    assert harness.program_value(cfg, "moe.top_k") == 4
    assert harness.program_value(cfg, "resolved_head_dim") == 128
    with pytest.raises(ValueError, match="no 'moe.no_such'"):
        harness.program_value(cfg, "moe.no_such")
    with pytest.raises(ValueError, match="names no nested config"):
        harness.build_model(dict(_fixture(), model_overrides={
            "ssm": {"state": 4}}))


def test_layout_fixture_work_by_hand():
    """A token passes top_k of the experts, the chip stores all of them;
    the router, the shared expert and its gate run for every token.  A
    packed cell packs the 2:4 matrices only: the float32 router and the
    gate keep their bytes and FLOPs."""
    dm = harness.ref_dims(_fixture())
    d, E, k, fe, fs, L, V = 64, 8, 2, 32, 64, 2, 256
    attn = 4 * d * d
    routed = k * 3 * d * fe
    per_token = attn + d * E + routed + 3 * d * fs + d
    stored = attn + d * E + E * 3 * d * fe + 3 * d * fs + d
    masked = attn + E * 3 * d * fe + 3 * d * fs        # role "matrix"
    kv = 300 * L * 2 * 4 * 16 * 2                  # K and V, 4 heads of 16
    w = work.decode_step(dm, tokens=5, context=300, packed=False)
    assert w["flops"] == pytest.approx(2 * 5 * (L * per_token + d * V)
                                       + 4 * L * 4 * 16 * 300)
    assert w["bytes"] == pytest.approx(
        L * ((stored - d * E) * 2 + d * E * 4) + d * V * 2 + kv)
    p = work.decode_step(dm, tokens=5, context=300, packed=True)
    assert w["bytes"] - p["bytes"] == pytest.approx(L * masked * 0.75)
    assert w["flops"] - p["flops"] == pytest.approx(
        2 * 5 * L * (attn + routed + 3 * d * fs) * 0.5)
    assert work.layer_weights(dm) == stored
    # no operator under attn/ or mlp/ besides attention: no fused MLP
    assert work.fused_mlp24(dm, rows=8) == {"flops": 0.0, "bytes": 0.0}
    assert work.spmm24(dm, rows=8)["flops"] == pytest.approx(
        L * 8 * attn)
    seqs, T = 4, 32
    assert work.unit_forward(dm, seqs, T) == pytest.approx(
        2 * seqs * T * per_token + 2 * seqs * T * T * 4 * 16)


def test_layout_fixture_held_share_of_experts():
    """On a chip that holds 2 of the 8 experts, the layout's expert axis
    gives what one layer stores, and per_token what a token passes; an
    operator that names no stacked matrix of the layout is refused."""
    conf = _fixture()
    ref = harness.reference(conf)
    dm = dict(harness.ref_dims(conf), held=2)
    dm["layer_kinds"] = [dict(dm["layer_kinds"][0], operators=dict(
        dm["layer_kinds"][0]["operators"], **{
            f"layers/moe/{p}": 2 * 2 / 8 for p in ("w_gate", "w_up",
                                                   "w_down")}))]
    from lib import weights
    dm["leaves"] = weights.leaves(dm, False, ref.layout)
    d, fe, fs, E = 64, 32, 64, 8
    base = 4 * d * d + d * E + 3 * d * fs + d
    assert work.layer_weights(dm) == base + 2 * 3 * d * fe
    full = harness.ref_dims(conf)
    a = work.decode_step(dm, 1, 10, False)
    b = work.decode_step(full, 1, 10, False)
    assert b["flops"] - a["flops"] == pytest.approx(
        2 * 2 * (2 - 0.5) * 3 * d * fe)
    for path, why in [("layers/moe/nope", "no leaf"),
                      ("layers/attn/bq", "not a matrix"),
                      ("head", "not a matrix")]:
        bad = dict(full, layer_kinds=[dict(full["layer_kinds"][0],
                                           operators={path: 1})])
        with pytest.raises(ValueError, match=why):
            work.layer_kinds(bad)


def _windowed(dm, window):
    kind = dm["layer_kinds"][0]
    full = dict(kind, count=1)
    band = dict(kind, count=1, attention=dict(kind["attention"],
                                              window=window))
    return dict(dm, layer_kinds=[full, band])


def test_windowed_kind_reads_only_its_window():
    """A windowed layer counts each slot's context capped at the
    window, from the run record's counter; without it, nothing."""
    dm = _windowed(harness.ref_dims(_fixture()), 16)
    assert work.decode_step(dm, 5, 300, False) is None
    assert work.decode_attention(dm, 5, 300) is None
    full = harness.ref_dims(_fixture())
    one = 2 * 4 * 16 * 2                        # K and V bytes, one layer
    a = work.decode_attention(dm, 5, 300, windowed=80)
    assert a["kv_bytes"] == 300 * one + 80 * one
    assert a["flops"] == pytest.approx(4 * 4 * 16 * (300 + 80))
    w = work.decode_step(dm, 5, 300, False, windowed=80)
    f = work.decode_step(full, 5, 300, False)
    assert f["bytes"] - w["bytes"] == pytest.approx((300 - 80) * one)
    st = {"steps": 4, "active_slot_steps": 20, "context_tokens": 1200}
    assert work.per_step(st) == {"tokens": 5, "context": 300,
                                 "windowed": None}
    st[work.WINDOWED_CONTEXT] = 320
    assert work.per_step(st)["windowed"] == 80
    # prefill: each of T queries sees at most W keys
    T, W = 64, 16
    assert work.unit_forward(dm, 1, T) * 2 == pytest.approx(
        2 * (2 * T * work._per_token(work.layer_kinds(dm)[0]))
        + 2 * T * T * 64 + 2 * (2 * W * T - W * W) * 64)
    with pytest.raises(ValueError, match="one window"):
        work.layer_kinds(dict(dm, layer_kinds=[
            dm["layer_kinds"][1], dict(dm["layer_kinds"][1], attention=dict(
                dm["layer_kinds"][1]["attention"], window=32))]))


@pytest.mark.parametrize("reader", ["serve.decode_mfu",
                                    "paged_decode_attn_roofline"])
def test_step_readers_need_the_windowed_count(reader):
    dm = _windowed(harness.ref_dims(_fixture()), 16)
    trace = {"program_count": lambda name: 4,
             "program_time": lambda name: 0.4,
             "kernel_time": lambda prog, *k: 0.1}
    stats = {"steps": 4, "active_slot_steps": 20, "context_tokens": 1200}
    ctx = {"trace": trace, "dims": dm, "peaks": {"bf16_flops": 197e12,
                                                 "hbm_bytes_per_s": 819e9},
           "run": {"stats": stats, "sparse": {"mode": "dense"}}}
    read = harness.metric_reader(reader).read
    assert read(ctx) is None
    stats[work.WINDOWED_CONTEXT] = 320
    assert read(ctx) > 0


def test_layout_fixture_serves_on_cpu():
    """Through the unchanged set-up and window of a serve cell: the
    batcher serves every request of the window to its length."""
    from lib import serve_cell
    conf = _fixture()
    cell = _serve_cell(weights="2:4-magnitude", sparse="dense", rate=20.0)
    st = serve_cell.setup(cell, conf, 2 ** 31 + 9)
    rec = serve_cell.window(st, cell, TINY_CHAT, 2 ** 31 + 9, 0.3)
    assert rec["offered"] == 6 and len(rec["results"]) == 6
    for r, q in rec["results"]:
        assert len(r.tokens) == q["max_new"]
        assert all(0 <= t < 256 for t in r.tokens)
    m = serve_cell.metrics(rec)
    assert m["failed"] == 0 and m["tokens"] == sum(
        q["max_new"] for _, q in rec["results"])


# ---------------------------------------------------------------------------
# trace reduction, on a trace recorded on the chip
# ---------------------------------------------------------------------------
TESTDATA = os.path.join(BENCH, "testdata")


#: recorded traces: one from before the program wrote spans or had the
#: lane-dense decode kernel, and a ``--trace 1`` run of the batch cell
RECORDED = ["serve_packed", "serve_batch"]


def _testdata_trace(name="serve_packed"):
    import gzip
    from jax.profiler import ProfileData
    from lib import trace
    with gzip.open(os.path.join(TESTDATA, f"{name}.xplane.pb.gz")) as f:
        planes = ProfileData.from_serialized_xspace(f.read()).planes
    return trace.reduce_planes(planes)


@pytest.mark.parametrize("name", RECORDED)
def test_trace_reduction_recorded_chip_trace(name):
    want = harness.read_json(TESTDATA, f"{name}.expected.json")
    t = _testdata_trace(name)
    assert t is not None
    assert t["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert t["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0 < t["busy_s"] < t["window_s"]
    for name_, sec in want["programs"].items():
        assert t["programs"][name_] == pytest.approx(sec, rel=1e-9)
    assert t["program_count"]("step") == want["program_counts"]["step"]
    for kern, sec in want["step_kernels"].items():
        assert t["kernel_time"]("step", kern) == pytest.approx(sec, rel=1e-9)
    # a kernel's time lies inside its program's time
    assert t["kernel_time"]("step", *want["step_kernels"]) <= \
        t["program_time"]("step")
    assert len(t["breakdown"]["device_ops"]) <= 10
    assert sum(s for _, s in t["breakdown"]["idle_gaps"]) == pytest.approx(
        t["window_s"] - t["busy_s"], rel=1e-6)
    assert not any(" while(" in n for n, _ in t["breakdown"]["device_ops"])
    spans = want.get("program_spans", {})
    assert set(t["program_spans"]) == set(spans)
    for n, sp in spans.items():
        assert t["program_spans"][n]["count"] == sp["count"]
        assert t["program_spans"][n]["seconds"] == pytest.approx(
            sp["seconds"], rel=1e-9)
    assert sum(t["idle_by_program_span"].values()) == pytest.approx(
        t["window_s"] - t["busy_s"], rel=1e-6)


def test_readers_on_recorded_chip_trace():
    """Every per-layer reader of the batch cell finds its number in the
    recorded ``--trace 1`` run's trace and run record, and reads what
    that run printed on the chip; no share of a roofline or a peak
    passes 100 %."""
    want = harness.read_json(TESTDATA, "serve_batch.expected.json")
    t = _testdata_trace("serve_batch")
    conf = harness.read_json(BENCH, "configs", "opt-125m.json")
    dm = harness.ref_dims(conf)
    run = dict(want["run"], dims=dm, cell=harness.read_json(
        BENCH, "workloads", f"{BATCH}.json"))
    ctx = {"trace": t, "run": run, "dims": dm,
           "peaks": harness.peaks("TPU v5 lite")}
    metrics = [m for m in harness.benchmark()["per_layer"]
               if BATCH in m["workloads"]]
    out = harness.per_layer(BATCH, metrics, ctx)
    assert set(out) == {m["name"] for m in metrics} == set(want["metrics"])
    for name, m in out.items():
        assert m["value"] == pytest.approx(want["metrics"][name], rel=1e-9)
        assert m["value"] > 0, name
        if m["unit"] == "%":
            assert m["value"] <= 100, name


class _Ev:
    def __init__(self, name, start, dur):
        self.name, self.start_ns, self.duration_ns = name, start, dur


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, [_Ev(*e) for e in events]


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, [_Line(*ln) for ln in lines]


def test_trace_reduction_by_hand():
    """Busy time is the union of op intervals inside the window; a
    kernel counts toward the program whose module holds it; each idle
    gap goes to the innermost harness span open at its middle."""
    from lib import trace
    host = _Plane("/host:CPU", [("python", [
        ("bench.window", 100, 1000), ("bench.decode_tick", 100, 500),
        ("bench.admit", 600, 100)])])
    dev = _Plane("/device:TPU:0", [
        ("XLA Modules", [("jit_step(1)", 150, 300), ("jit_chunk_step(2)",
                                                       800, 200)]),
        ("XLA Ops", [("%fusion.1 = f32[8] fusion(x)", 150, 100),
                     ("%paged_decode_attn.3 = bf16[8] custom-call(q)", 250,
                      150),
                     ("%copy.2 = bf16[8] copy(y)", 300, 50),
                     ("%spmm24.7 = bf16[8] custom-call(x)", 800, 100),
                     ("%dot.4 = f32[8] dot(a, b)", 1050, 200)])])
    t = trace.reduce_planes([host, dev])
    assert t["window_s"] == pytest.approx(1000e-9)
    # busy: [150, 400) + [800, 900) + [1050, 1100) clipped at 1100
    assert t["busy_s"] == pytest.approx(400e-9)
    assert t["program_time"]("step") == pytest.approx(300e-9)
    assert t["program_count"]("chunk_step") == 1
    assert t["kernel_time"]("step", "paged_decode_attn") == \
        pytest.approx(150e-9)
    assert t["kernel_time"]("chunk_step", "spmm24") == pytest.approx(100e-9)
    gaps = dict(t["breakdown"]["idle_gaps"])
    # gap [100, 150): the tick; [400, 800), middle 600: the admit span
    # (shorter than the tick, also open there); [900, 1050): no span
    ops = dict(t["breakdown"]["device_ops"])
    assert ops["paged_decode_attn.3 (paged_decode_attn)"] == pytest.approx(
        150e-9)
    assert gaps == pytest.approx({"bench.decode_tick": 50e-9,
                                  "bench.admit": 400e-9,
                                  "outside the harness's spans": 150e-9})


def test_reader_declares_a_kernel(tmp_path, monkeypatch):
    """A reader file with a module constant ``KERNELS`` adds its kernel
    to the reduction, with no edit of ``trace.py``; the readers in the
    tree declare none beyond the built-in four."""
    from lib import trace
    assert set(trace.kernel_patterns()) == set(trace.KERNELS)
    (tmp_path / "grouped_mm_roofline.py").write_text(
        '"""A reader."""\nKERNELS = ("grouped_mm",)\n\n\n'
        'def read(ctx):\n    return None\n')
    (tmp_path / "other.py").write_text("KERNEL = ('not_one',)\n")
    host = _Plane("/host:CPU", [("python", [("bench.window", 0, 1000)])])
    dev = _Plane("/device:TPU:0", [
        ("XLA Modules", [("jit_step(1)", 0, 1000)]),
        ("XLA Ops", [("%grouped_mm.12 = bf16[8] custom-call(x)", 100, 300),
                     ("%spmm24.3 = bf16[8] custom-call(x)", 500, 100)])])
    assert trace.reduce_planes([host, dev])["kernel_time"](
        "step", "grouped_mm") is None
    monkeypatch.setattr(trace, "METRICS_DIR", str(tmp_path))
    assert trace.reader_kernels() == ("grouped_mm",)
    t = trace.reduce_planes([host, dev])
    assert t["kernel_time"]("step", "grouped_mm") == pytest.approx(300e-9)
    assert t["kernel_time"]("step", "spmm24") == pytest.approx(100e-9)
    assert dict(t["breakdown"]["device_ops"])[
        "grouped_mm.12 (grouped_mm)"] == pytest.approx(300e-9)


def test_trace_union_and_labels():
    from lib import trace
    assert trace._union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    spans = [("bench.decode_tick", 0, 10), ("bench.admit", 2, 4)]
    starts = [s[1] for s in spans]
    assert trace._label(spans, starts, 3) == "bench.admit"
    assert trace._label(spans, starts, 7) == "bench.decode_tick"
    assert trace._label(spans, starts, 11).startswith("outside")


# ---------------------------------------------------------------------------
# cells at a small size, the look for a chip skipped
# ---------------------------------------------------------------------------
@pytest.fixture()
def no_chip(monkeypatch):
    monkeypatch.setattr(harness, "device_info", lambda chips: {
        "platform": "cpu", "kind": "cpu", "count": chips})
    monkeypatch.setattr(harness, "peaks", lambda kind: {
        "bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})


def _run(monkeypatch, capsys, workload, conf, cell=None, mix=None,
         seconds=1.0, seed=2 ** 31 + 11):
    orig = harness.cell_files

    def files(name):
        if name in HELD_OUT:
            e = {"name": name, "chips": 1}
            c = harness.read_json(BENCH, "workloads", f"{name}.json")
            m = harness.read_json(BENCH, "traffic", f"{HELD_OUT[name]}.json")
        else:
            e, c, _, m = orig(name)
        return e, dict(c, **(cell or {})), conf, dict(m, **(mix or {}))
    monkeypatch.setattr(harness, "cell_files", files)
    import runpy
    sys.argv = ["run.py", "--workload", workload, "--seed", str(seed),
                "--seconds", str(seconds), "--trace", "0"]
    with pytest.raises(SystemExit) as ex:
        runpy.run_path(os.path.join(BENCH, "run.py"), run_name="__main__")
    assert ex.value.code == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


PRUNE = "opt-125m.prune.fista-2to4"
CHAT = "internlm2-20b.d8.dense.chat"
#: cells whose files are kept but that BENCHMARK.json leaves out (PERF.md),
#: with their traffic mixes
HELD_OUT = {PRUNE: "prune.fista-2to4", CHAT: "chat"}


def _serve_cell(**kw):
    cell = {"serving": TINY_SERVING, "rate": 20.0,
            "check": {"served_tokens": 40, "limits": {"logit_gap": 0.1}}}
    cell.update(kw)
    return cell


def test_prune_run_correct(no_chip, monkeypatch, capsys):
    out = _run(monkeypatch, capsys, PRUNE, TINY_OPT,
               mix={"calibration": TINY_CALIB})
    assert out["correct"] is True
    assert "setup_s" in out["metrics"]
    assert list(out)[-1] == "checks"
    assert out["device"]["platform"] == "cpu"


BATCH = "opt-125m.packed.batch"
TINY_BATCH = {"batch": 600,
              "prompt": {"dist": "lognormal", "median": 40, "sigma": 1.0,
                         "min": 4, "max": 150},
              "output": {"dist": "lognormal", "median": 8, "sigma": 0.8,
                         "min": 2, "max": 30}}


def _serve_run(monkeypatch, capsys, workload, **chat):
    """A run of a serve cell at a small size: the chat cell's open loop
    (``chat`` overrides its cell file) or the batch cell's closed loop."""
    if workload == CHAT:
        return _run(monkeypatch, capsys, CHAT, TINY_ILM, _serve_cell(**chat),
                    TINY_CHAT, seconds=0.1 if chat else 1.0)
    return _run(monkeypatch, capsys, BATCH, TINY_OPT,
                {"serving": dict(TINY_SERVING, slots=8)}, TINY_BATCH)


@pytest.mark.parametrize("workload", [CHAT, BATCH])
def test_serve_run_correct(no_chip, monkeypatch, capsys, workload):
    out = _serve_run(monkeypatch, capsys, workload)
    assert out["correct"] is True and out["failed"] == 0
    want = {m["name"] for m in harness.benchmark()["end_to_end"]
            if workload in m.get("workloads", [workload])}
    assert set(out["metrics"]) == want and "setup_s" in want
    assert all(v["value"] > 0 for v in out["metrics"].values())


def test_ttft_counts_a_slow_prefill(no_chip, monkeypatch):
    """TTFT is read no earlier than the host's receipt of the first
    token: a prefill chunk made 0.3 s slower shows up whole in TTFT."""
    from lib import serve_cell
    cell = _serve_cell(weights="2:4-magnitude", sparse="packed")
    mix = dict(TINY_CHAT, prompt=dict(TINY_CHAT["prompt"], min=100,
                                      max=100),
               output=dict(TINY_CHAT["output"], min=6, max=6))
    st = serve_cell.setup(cell, TINY_ILM, 5)
    fast = serve_cell.metrics(serve_cell.window(
        st, dict(cell, rate=0.5), mix, 5, 2.0))
    chunk = st["batcher"]._chunk_fn

    def slow(*a):
        out = chunk(*a)
        import jax
        jax.block_until_ready(out)
        time.sleep(0.3)
        return out
    st["batcher"]._chunk_fn = slow
    rec = serve_cell.window(st, dict(cell, rate=0.5), mix, 5, 2.0)
    slow_m = serve_cell.metrics(rec)
    # 100-token prompts take two 64-token chunks: at least 0.6 s more
    assert slow_m["ttft_p50_ms"] - fast["ttft_p50_ms"] >= 600.0
    r = rec["results"][0][0]
    assert r.token_times[1] - r.token_times[0] >= 0.3   # stamp 0 is early


# ---------------------------------------------------------------------------
# controls: the reference one precision lower reads worse than the program
# ---------------------------------------------------------------------------
def test_prune_control_reads_worse():
    from lib import prune_cell
    job = dict(_mix("prune.fista-2to4"), calibration=TINY_CALIB)
    cell = {"check": {"units": 2, "limits": {}}}
    st = prune_cell.setup(cell, TINY_OPT, job, 3)
    rec = prune_cell.window(st, 0.0)
    reports = [{"unit": r.unit, "key": r.key, "error": float(r.error),
                "rel_error": float(r.rel_error)} for r in rec["reports"]]
    prog = prune_cell.check(TINY_OPT, cell, job, 3,
                            {"pruned": rec["pruned"], "reports": reports})
    ctrl = prune_cell.check(TINY_OPT, cell, job, 3,
                            prune_cell.control_answer(TINY_OPT, job, 3))
    assert ctrl["quality_gap"] > 10 * prog["quality_gap"]
    assert ctrl["err_gap"] > 10 * prog["err_gap"]


def test_serve_control_reads_worse():
    """fp8 operands move the first choice far more than bf16 serving
    does (a wide vocabulary has near-ties for the control to flip)."""
    from lib import serve_cell
    conf = dict(TINY_ILM, vocab_size=8192, model_overrides=dict(
        TINY_ILM["model_overrides"], vocab=8192))
    cell = _serve_cell(weights="2:4-magnitude", sparse="packed", rate=30.0)
    mix = dict(TINY_CHAT, prompt=dict(TINY_CHAT["prompt"], max=120))
    st = serve_cell.setup(cell, conf, 4)
    rec = serve_cell.window(st, cell, mix, 4, 1.0)
    picked = serve_cell.sample(rec, 4, 200)
    serve_cell.free(st)
    prog = serve_cell.logit_gaps(conf, cell, 4, picked).max()
    ctrl = serve_cell.logit_gaps(conf, cell, 4, picked, control=True).max()
    assert ctrl > 3 * prog


# ---------------------------------------------------------------------------
# faults: a run with the timed path broken underneath is not correct
# ---------------------------------------------------------------------------
def test_fault_prune_returns_state_unchanged(no_chip, monkeypatch, capsys):
    from repro import api
    monkeypatch.setattr(api, "prune", lambda model, params, calib, recipe, *a,
                        **k: (params, [], {}))
    out = _run(monkeypatch, capsys, PRUNE, TINY_OPT,
               mix={"calibration": TINY_CALIB})
    assert out["correct"] is False
    assert out["checks"]["nm_violations"]["value"] > 0


def test_fault_prune_half_the_batch(no_chip, monkeypatch, capsys):
    from repro import api
    real = api.prune
    monkeypatch.setattr(api, "prune", lambda model, params, calib, recipe,
                        *a, **k: real(model, params, calib[:len(calib) // 2],
                                      recipe, *a, **k))
    out = _run(monkeypatch, capsys, PRUNE, TINY_OPT,
               mix={"calibration": TINY_CALIB})
    assert out["correct"] is False
    assert out["checks"]["energy_gap"]["value"] > 0.3


def test_fault_prune_answer_altered(no_chip, monkeypatch, capsys):
    from repro import api
    real = api.prune

    def altered(*a, **k):
        params, reports, stats = real(*a, **k)
        w = params["layers"]["mlp"]["fc1"]
        params = dict(params, layers=dict(params["layers"], mlp=dict(
            params["layers"]["mlp"], fc1=w.at[:, :, :8].multiply(-1))))
        return params, reports, stats
    monkeypatch.setattr(api, "prune", altered)
    out = _run(monkeypatch, capsys, PRUNE, TINY_OPT,
               mix={"calibration": TINY_CALIB})
    assert out["correct"] is False


@pytest.mark.parametrize("workload", [CHAT, BATCH])
def test_fault_serve_token_altered(no_chip, monkeypatch, capsys, workload):
    from repro.serve import batcher as batcher_lib
    real = batcher_lib.ContinuousBatcher._tick

    def tick(self, now):
        real(self, now)
        for s in range(self.cfg.slots):        # every 5th token off by one
            if self._slot_req[s] is not None and self._emitted[s] and \
                    len(self._emitted[s]) % 5 == 0:
                self._emitted[s][-1] = (self._emitted[s][-1] + 1) % 512
    monkeypatch.setattr(batcher_lib.ContinuousBatcher, "_tick", tick)
    out = _serve_run(monkeypatch, capsys, workload)
    assert out["correct"] is False


@pytest.mark.parametrize("workload", [CHAT, BATCH])
def test_fault_serve_step_returns_state_unchanged(no_chip, monkeypatch,
                                                  capsys, workload):
    from repro.models import registry
    real_def = registry.model_def

    def broken(cfg):
        md = real_def(cfg)
        step = md.paged_step

        def paged_step(params, pool, *a, **k):
            logits, _ = step(params, pool, *a, **k)
            return logits, pool              # the K/V pool never updated
        import dataclasses
        return dataclasses.replace(md, paged_step=paged_step)
    monkeypatch.setattr(registry, "model_def", broken)
    out = _serve_run(monkeypatch, capsys, workload)
    assert out["correct"] is False


@pytest.mark.parametrize("workload", [CHAT, BATCH])
def test_fault_serve_half_the_slots(no_chip, monkeypatch, capsys, workload):
    """The decode step computes only the first half of its slots and
    hands their logits to the other half."""
    from repro.models import registry
    real_def = registry.model_def

    def broken(cfg):
        md = real_def(cfg)
        step = md.paged_step

        def paged_step(params, pool, *a, **k):
            logits, pool = step(params, pool, *a, **k)
            half = logits.shape[0] // 2
            return logits.at[half:].set(logits[:logits.shape[0] - half]), pool
        import dataclasses
        return dataclasses.replace(md, paged_step=paged_step)
    monkeypatch.setattr(registry, "model_def", broken)
    # chat: arrivals close enough together that every slot is busy
    out = _serve_run(monkeypatch, capsys, workload, rate=400.0)
    assert out["correct"] is False


def test_every_name_finds_its_file():
    """Cells, configurations, mixes and per-layer metrics are found by
    name: adding one adds files, and edits no code."""
    b = harness.benchmark()
    for w in b["workloads"]:
        entry, cell, conf, mix = harness.cell_files(w["name"])
        assert conf["name"] == w["config"]
        assert harness.reference(conf).dims(conf)["layers"] >= 1
    for c in b["configs"]:
        assert os.path.exists(os.path.join(os.path.dirname(BENCH), c["file"]))
    for m in b["per_layer"]:
        assert callable(harness.metric_reader(m["name"]).read)
        assert m["moves"] in {e["name"] for e in b["end_to_end"]}
