"""CPU tests of the benchmark's yardstick and of its checks.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They cover the trace reduction on a trace recorded on the chip, the
work counts against hand-worked shapes, the traffic generator, the TTFT
reading under a slow prefill, the controls (the reference one precision
lower must read worse than the program), and the faults that a run must
report as not correct.  The cells run here at small sizes, with the
harness's look for a chip skipped.
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

from lib import harness, traffic, work  # noqa: E402


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
# trace reduction, on a trace recorded on the chip
# ---------------------------------------------------------------------------
TESTDATA = os.path.join(BENCH, "testdata")


def _testdata_trace():
    import gzip
    from jax.profiler import ProfileData
    from lib import trace
    with gzip.open(os.path.join(TESTDATA, "serve_packed.xplane.pb.gz")) as f:
        planes = ProfileData.from_serialized_xspace(f.read()).planes
    return trace.reduce_planes(planes)


def test_trace_reduction_recorded_chip_trace():
    want = harness.read_json(TESTDATA, "serve_packed.expected.json")
    t = _testdata_trace()
    assert t is not None
    assert t["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert t["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0 < t["busy_s"] < t["window_s"]
    for name, sec in want["programs"].items():
        assert t["programs"][name] == pytest.approx(sec, rel=1e-9)
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


def test_readers_on_recorded_chip_trace():
    """Every per-layer reader of the batch cell finds its number in the
    recorded trace, and no share of a roofline or peak passes 100 %."""
    t = _testdata_trace()
    conf = harness.read_json(BENCH, "configs", "opt-125m.json")
    dm = harness.ref_dims(conf)
    steps = t["program_count"]("step")
    run = {"stats": {"steps": steps, "active_slot_steps": 8 * steps,
                     "context_tokens": 8 * 300 * steps},
           "cell": harness.read_json(BENCH, "workloads", f"{BATCH}.json"),
           "sparse": {"mode": "packed"}, "dims": dm}
    ctx = {"trace": t, "run": run, "dims": dm,
           "peaks": harness.peaks("TPU v5 lite")}
    metrics = [m for m in harness.benchmark()["per_layer"]
               if BATCH in m["workloads"]]
    out = harness.per_layer(BATCH, metrics, ctx)
    assert set(out) == {m["name"] for m in metrics}
    for name, m in out.items():
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
