"""Chip smoke: the main path prune -> evaluate -> serve on one TPU, at the
published widths of ``opt125m-proxy`` (12 layers, d_model 768, 12 heads,
d_ff 3072, vocab 50272, bf16), through the launchers a user calls.

    python chip_smoke.py              # one chip: prune, evaluate, serve
    python chip_smoke.py --chips 4    # four chips: the mesh paths only

Weights start from a seeded random init and train for a few steps on the
seeded synthetic corpus (``data/corpus.py``); nothing is downloaded.
Everything is written under ``--out`` (cleared first) and JAX's compile
cache.  The script fails (non-zero exit, no ``ok`` line) when JAX sees
no TPU or any check below fails.  Its timings are smoke timings of one
cold run, compile included, not benchmark numbers.  The last line of
standard output is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import glob
import json
import math
import os
import re
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ARCH = "opt125m-proxy"
#: jitted programs whose optimized HLO is dumped to count kernel calls:
#: the continuous batcher's decode step and the fused FISTA solves
_DUMPED = {"decode step": "jit_step",
           "prune solve": "jit__fused_group|jit__fused_single"}


def _fail(msg: str) -> int:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    return 1


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def _peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def _custom_calls(dump_dir: str, module_re: str) -> str:
    """``tpu_custom_call`` instructions in the optimized HLO of each
    dumped module whose name matches ``module_re``.  A count of 0 where
    a kernel was expected means a shape gate in ``kernels/ops.py`` sent
    the work to a ``ref.py`` oracle.  A program served from the
    persistent compile cache is not compiled, hence not dumped."""
    pat = re.compile(rf"\.({module_re})\.")
    counts = []
    for path in sorted(glob.glob(os.path.join(dump_dir,
                                              "*after_optimizations.txt"))):
        if pat.search(os.path.basename(path)):
            with open(path, encoding="utf-8") as f:
                counts.append(len(re.findall(
                    r'custom_call_target="tpu_custom_call"', f.read())))
    return f"{counts} (one count per compiled module)"


def _load_params(run_dir: str, name: str):
    """(model, params) of one model checkpoint of a prune run dir."""
    import jax
    from repro.launch import evaluate as eval_cli

    run = eval_cli.resolve_run(run_dir)
    model = run["recipe"].load_model(smoke=run["smoke"])
    like = model.init(jax.random.PRNGKey(0))
    params, _ = eval_cli._load_params(run_dir, name, like)
    return model, params


def _operators(model, params):
    """(unit/key, (out, in) weight) for every prunable operator."""
    from repro.core import sequential as seq_lib

    for spec in model.units():
        unit = seq_lib._unit_params_of(params, spec)
        for group in spec.groups:
            for key in group:
                yield f"{spec.name}/{key}", seq_lib.get_weight(unit, key).T


def _run_prune(out: str, seed: int, extra=()) -> dict:
    from repro.launch import prune as prune_cli

    report = out + ".json"
    rc = prune_cli.main([
        "--arch", ARCH, "--method", "fista", "--sparsity", "2:4",
        # a few train steps, then a small calibration set: 32 sequences
        # of 512 tokens (four relay batches of 8, one per data shard on
        # four chips); 512 also keeps flash attention's bq = min(512, S)
        # whole for configs that take that kernel
        "--train-steps", "30", "--calib-sequences", "32",
        "--calib-seq-len", "512", "--workers", "1", "--seed", str(seed),
        "--ckpt-dir", out, "--out", report, *extra])
    _check(rc == 0, f"prune exited {rc}")
    with open(report, encoding="utf-8") as f:
        rec = json.load(f)
    _check(math.isfinite(rec["dense_ppl"]) and math.isfinite(rec["pruned_ppl"]),
           f"prune ppl not finite: {rec['dense_ppl']} / {rec['pruned_ppl']}")
    # the scheduler retries a failed unit and re-dispatches a slow one; on
    # the chip either would hide a fault, so every unit must have run once
    with open(os.path.join(out, "run_summary.json"), encoding="utf-8") as f:
        attempts = json.load(f)["attempts_histogram"]
    _check(set(attempts) == {"1"}, f"prune units ran more than once: {attempts}")
    return rec


def _run_serve(ckpt: str, out: str, *extra: str) -> dict:
    from repro.launch import serve as serve_cli

    rc = serve_cli.main([
        "--checkpoint", ckpt, "--requests", "4", "--rate", "0",
        "--max-new-tokens", "8", "--slots", "4", "--prompt-len-min", "8",
        "--prompt-len-max", "16", "--out", out, *extra])
    _check(rc == 0, f"serve {' '.join(extra)} exited {rc}")
    with open(out, encoding="utf-8") as f:
        return json.load(f)


def _phase(name: str, times: dict, dev, fn, *args):
    t0 = time.perf_counter()
    res = fn(*args)
    times[name] = time.perf_counter() - t0
    print(f"smoke timing (cold, not a benchmark) {name}: "
          f"{times[name]:.1f} s, peak device memory so far "
          f"{_peak_bytes(dev)} bytes", flush=True)
    return res


def one_chip(out: str, seed: int, dev) -> None:
    import jax
    import numpy as np

    from repro.core.sparsity import SparsitySpec, satisfies
    from repro.serve import packed as packed_lib
    from repro.serve.engine import prepare_serving_params

    times: dict = {}
    ckpt = os.path.join(out, "prune")
    rec = _phase("prune", times, dev, _run_prune, ckpt, seed)
    print(f"prune: dense_ppl={rec['dense_ppl']} pruned_ppl={rec['pruned_ppl']} "
          f"mean_rel_err={rec['mean_rel_err']}")

    model, pruned = _load_params(ckpt, "pruned_model")
    spec = SparsitySpec.parse("2:4")
    ops = dict(_operators(model, pruned))
    bad = [k for k, w in ops.items() if not satisfies(w, spec)]
    _check(not bad, f"operators not 2:4: {bad[:5]}")
    print(f"2:4 check: all {len(ops)} pruned operators are 2:4")

    def evaluate():
        from repro.launch import evaluate as eval_cli
        path = os.path.join(out, "quality.json")
        rc = eval_cli.main(["--checkpoint", ckpt, "--against-dense",
                            "--out", path])
        _check(rc == 0, f"evaluate exited {rc}")
        with open(path, encoding="utf-8") as f:
            return json.load(f)

    q = _phase("evaluate", times, dev, evaluate)
    _check(math.isfinite(q["ppl"]) and math.isfinite(q["dense_ppl"]),
           f"evaluate ppl not finite: {q['ppl']} / {q['dense_ppl']}")
    print(f"evaluate: dense_ppl={q['dense_ppl']} pruned_ppl={q['ppl']} "
          f"kl={q['kl']} top1_agreement={q['top1_agreement']}")

    packed = _phase("serve packed", times, dev, _run_serve, ckpt,
                    os.path.join(out, "serve_packed.json"))
    dense = _phase("serve dense", times, dev, _run_serve, ckpt,
                   os.path.join(out, "serve_dense.json"), "--sparse", "dense")
    _check(packed["sparse_mode"] == "packed" and dense["sparse_mode"] == "dense",
           f"sparse modes {packed['sparse_mode']} / {dense['sparse_mode']}")
    _check(packed["request_tokens"] == dense["request_tokens"],
           "packed and dense greedy tokens differ: "
           f"{packed['request_tokens']} vs {dense['request_tokens']}")
    print(f"serve: packed and dense greedy tokens agree over "
          f"{packed['tokens']} tokens of {packed['requests']} requests")

    # first-step logits of the same prompts through the packed store
    # (spmm24 over prefill rows) and through dense matmuls
    pk, stats = prepare_serving_params(pruned, "auto")
    dn, _ = prepare_serving_params(pruned, "dense")
    tokens = jax.random.randint(jax.random.PRNGKey(seed), (4, 16), 0,
                                model.cfg.vocab, jax.numpy.int32)
    fwd = jax.jit(model.forward_logits)
    lp = np.asarray(fwd(packed_lib.decode_view(pk), {"tokens": tokens})[:, -1],
                    np.float32)
    ld = np.asarray(fwd(dn, {"tokens": tokens})[:, -1], np.float32)
    diff = float(np.abs(lp - ld).max())
    scale = float(np.abs(ld).max())
    # Both paths multiply the same bf16 weights and accumulate in f32; they
    # differ only in accumulation order, which can flip a bf16 rounding
    # (one part in 2^8) of a matmul output.  Twelve layers carry such flips
    # forward through the residual stream, so allow 4 * 2^-8 (1.6 %) of
    # the largest logit.  A wrong rebuild of the packed tile is O(1) off.
    tol = 4 * 2.0 ** -8 * scale
    _check(bool(np.isfinite(lp).all()) and diff <= tol,
           f"first-step logits: max |packed - dense| {diff} > {tol}")
    print(f"serve: first-step logits max |packed - dense| = {diff} "
          f"(tolerance {tol}, {stats['packed_ops']} packed operators)")

    dump = os.path.join(out, "hlo")
    for label, module_re in _DUMPED.items():
        print(f"tpu_custom_call in compiled {label}: "
              f"{_custom_calls(dump, module_re)}")
    print(f"smoke total (cold): {sum(times.values()):.1f} s")


def four_chips(out: str, seed: int, dev) -> None:
    import numpy as np

    times: dict = {}
    single = os.path.join(out, "prune_1dev")
    mesh = os.path.join(out, "prune_mesh4x1")
    r1 = _phase("prune 1 device", times, dev, _run_prune, single, seed)
    r4 = _phase("prune --mesh 4x1", times, dev, _run_prune, mesh, seed,
                ["--mesh", "4x1"])
    _, w1 = _load_params(single, "pruned_model")
    model, w4 = _load_params(mesh, "pruned_model")
    a, b = dict(_operators(model, w1)), dict(_operators(model, w4))
    mask_diff = sum(int(((np.asarray(a[k]) != 0) != (np.asarray(b[k]) != 0)).sum())
                    for k in a)
    total = sum(int(np.asarray(a[k]).size) for k in a)
    num = math.sqrt(sum(float(np.sum((np.asarray(a[k], np.float32)
                                      - np.asarray(b[k], np.float32)) ** 2))
                        for k in a))
    den = math.sqrt(sum(float(np.sum(np.asarray(a[k], np.float32) ** 2))
                        for k in a))
    rel = num / max(den, 1e-30)
    ppl_rel = abs(r4["pruned_ppl"] - r1["pruned_ppl"]) / r1["pruned_ppl"]
    print(f"mesh prune vs 1 device: weight rel diff {rel}, "
          f"mask entries differing {mask_diff}/{total}, pruned ppl "
          f"{r4['pruned_ppl']} vs {r1['pruned_ppl']} (rel {ppl_rel})")
    # The data-parallel Gram is one psum of per-shard sums: the same f32
    # terms in another order, so the statistics agree to f32 round-off.
    # Near-ties of the 2:4 rounding may then flip a few masks; anything
    # beyond 1e-3 of the masks, 1 % of the weights or 0.1 % of the
    # perplexity is a sharding fault, not round-off.
    _check(mask_diff <= 1e-3 * total and rel <= 1e-2 and ppl_rel <= 1e-3,
           "mesh prune does not match the single-device prune")

    s1 = _phase("serve 1 device", times, dev, _run_serve, single,
                os.path.join(out, "serve_1dev.json"))
    s4 = _phase("serve --mesh 1x4", times, dev, _run_serve, single,
                os.path.join(out, "serve_tp4.json"), "--mesh", "1x4")
    _check(s1["request_tokens"] == s4["request_tokens"],
           "tensor-parallel tokens differ from single-device tokens: "
           f"{s4['request_tokens']} vs {s1['request_tokens']}")
    print(f"serve: --mesh 1x4 greedy tokens agree with 1 device over "
          f"{s1['tokens']} tokens of {s1['requests']} requests")
    print(f"smoke total (cold): {sum(times.values()):.1f} s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: prune -> evaluate -> serve; 4: only the mesh "
                         "paths (prune --mesh 4x1, serve --mesh 1x4) and "
                         "the single-device runs they are compared with")
    ap.add_argument("--out", default=os.path.join(HERE, "experiments",
                                                  "chip_smoke"),
                    help="output directory (cleared first; holds full-width "
                         "checkpoints, hundreds of MB)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        return _fail(f"no repro package under {src}")
    sys.path.insert(0, src)
    shutil.rmtree(args.out, ignore_errors=True)
    os.makedirs(args.out)
    # count kernel calls from the compiler's own optimized HLO; set before
    # JAX creates its backend
    os.environ["XLA_FLAGS"] = " ".join(filter(None, [
        os.environ.get("XLA_FLAGS", ""),
        f"--xla_dump_to={os.path.join(args.out, 'hlo')}",
        "--xla_dump_hlo_as_text",
        f"--xla_dump_hlo_module_re={'|'.join(_DUMPED.values())}"]))

    import jax

    from repro.launch import enable_compile_cache

    devs = jax.devices()
    if devs[0].platform != "tpu":
        return _fail(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < args.chips:
        return _fail(f"--chips {args.chips} but JAX sees {len(devs)} devices")
    print(f"device: {devs[0].device_kind} x{len(devs)}", flush=True)
    print(f"compile cache: {enable_compile_cache()}")
    run = one_chip if args.chips == 1 else four_chips
    try:
        run(args.out, args.seed, devs[0])
    except AssertionError as exc:
        return _fail(str(exc))
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
