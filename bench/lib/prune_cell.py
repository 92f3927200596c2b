"""Prune cells: seeded weights and calibration -> whole ``repro.api.prune``
calls, as the prune launcher makes them.

Set-up makes the weights and the calibration tokens on the device and
runs one whole prune call, which compiles every program the window
runs.  The window repeats whole calls, each ending in
``block_until_ready``, until ``--seconds`` have passed; a unit is one
decoder layer (Gram scans, solves, write-back).  The check judges the
last call's answer against ``prune_ref`` on units drawn from the seed.
"""
from __future__ import annotations

import gc
import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from lib import harness, prune_ref, weights


def calibration(job: Dict[str, Any], vocab: int, seed: int) -> List[Dict]:
    """Seeded token batches (no corpus): ``num_sequences`` x ``seq_len``."""
    c = job["calibration"]
    key = jax.random.fold_in(weights.seed_key(seed), 1)
    toks = jax.random.randint(key, (c["num_sequences"], c["seq_len"]), 0,
                              vocab, jnp.int32)
    b = c["batch_size"]
    return [{"tokens": toks[i:i + b]} for i in range(0, c["num_sequences"], b)]


def recipe(conf: Dict[str, Any], job: Dict[str, Any]):
    from repro import api
    return api.PruneRecipe(arch=conf["arch"], method=job["method"],
                           solver=dict(job["solver"]),
                           sparsity=job["sparsity"],
                           correction=job["correction"],
                           calibration=dict(job["calibration"]),
                           scheduler=dict(job.get("scheduler", {})))


def setup(cell, conf, job, seed) -> Dict[str, Any]:
    from repro import api, obs
    model = harness.build_model(conf)
    dm = harness.ref_dims(conf)
    params = weights.for_config(conf, seed)
    calib = calibration(job, dm["vocab"], seed)
    rec = recipe(conf, job)
    obs.enable()                     # as the prune launcher runs a prune
    out = api.prune(model, params, calib, rec)
    jax.block_until_ready(out[0])
    return {"model": model, "params": params, "calib": calib, "recipe": rec,
            "dm": dm, "units": len(model.units())}


def window(st: Dict[str, Any], seconds: float) -> Dict[str, Any]:
    from repro import api
    calls, out = 0, None
    t0 = time.perf_counter()
    while True:
        out = None                   # free the previous answer first
        out = api.prune(st["model"], st["params"], st["calib"], st["recipe"])
        jax.block_until_ready(out[0])
        calls += 1
        if time.perf_counter() - t0 >= seconds:
            break
    wall = time.perf_counter() - t0
    return {"calls": calls, "units": calls * st["units"], "wall": wall,
            "pruned": out[0], "reports": out[1]}


def free(st: Dict[str, Any]) -> None:
    for k in ("params", "calib"):
        st.pop(k, None)
    gc.collect()


def check(conf, cell, job, seed, answer: Dict[str, Any]) -> Dict[str, float]:
    """Numbers that judge an answer {"pruned": params, "reports": [
    {"unit", "key", "error", "rel_error"}]} against the reference, on
    the units ``cell["check"]["units"]`` drawn from the seed."""
    ref = harness.reference(conf)
    dm = ref.dims(conf)
    dense = weights.for_config(conf, seed)
    calib = calibration(job, dm["vocab"], seed)
    pruned = answer["pruned"]
    out = {"nm_violations": float(prune_ref.nm_violations(pruned, dm["act"]))}
    rng = np.random.default_rng(seed)
    units = sorted(int(u) for u in rng.choice(
        dm["layers"], size=cell["check"]["units"], replace=False))
    reports = {(r["unit"], r["key"]): r for r in answer["reports"]}
    T = calib[0]["tokens"].shape[1]
    pos = jnp.arange(T, dtype=jnp.int32)
    layer = jax.jit(jax.vmap(lambda p, x: ref.layer(dm, p, x, pos),
                             in_axes=(None, 0)))
    xs = [ref.embed(dense, b["tokens"]) for b in calib]
    err_gap, energy_gap, quality = 0.0, 0.0, 0.0
    done = 0
    for u in units:
        for i in range(done, u):
            lp = ref.layer_params(dense, i)
            xs = [layer(lp, x) for x in xs]
        done = u
        st = prune_ref.unit_stats(ref, dm, ref.layer_params(dense, u),
                                  ref.layer_params(pruned, u), xs)
        for key, s in st.items():
            rel = float(np.sqrt(float(s["num"]) / float(s["den"])))
            rep = reports.get((f"layer{u:03d}", key))
            if rep is None:          # an operator the answer does not report
                err_gap = energy_gap = 1.0
                continue
            h_prog = (rep["error"] / max(rep["rel_error"], 1e-30)) ** 2
            err_gap = max(err_gap, abs(rep["rel_error"] - rel) / rel)
            energy_gap = max(energy_gap,
                             abs(h_prog - float(s["den"])) / float(s["den"]))
            w = prune_ref.get(ref.layer_params(dense, u), key).astype(
                jnp.float32).T
            _, rel_ref = prune_ref.solve(w, s)
            quality = max(quality, rel / rel_ref - 1.0)
    out.update(err_gap=err_gap, energy_gap=energy_gap, quality_gap=quality)
    return out


def run(args, entry, cell, conf, job, dev, peaks, bench, tracer, t_start):
    """Set-up, window (traced or not), check; returns the result line."""
    st = setup(cell, conf, job, args.seed)
    setup_s = time.perf_counter() - t_start
    if tracer is not None:
        tracer.wrap_prune()
        tracer.start()
    with harness.CompileCounter() as compiles:
        rec = window(st, args.seconds)
    if tracer is not None:
        tracer.stop()
    harness.stderr(f"bench: {rec['calls']} prune calls, {rec['units']} units "
                   f"in {rec['wall']!r} s, {compiles.count} compiles in the "
                   f"window")
    device = dict(dev, memory_peak_bytes=harness.memory_peak(dev["count"]))
    reports = [{"unit": r.unit, "key": r.key, "error": float(r.error),
                "rel_error": float(r.rel_error),
                "fista_iters": int(r.fista_iters),
                "outer_iters": int(r.outer_iters), "shape": tuple(r.shape)}
               for r in rec["reports"]]
    run_rec = {"units": rec["units"], "calls": rec["calls"],
               "wall": rec["wall"], "reports": reports, "dims": st["dm"],
               "job": job}
    layer_ctx = None
    if tracer is not None:
        layer_ctx = {"trace": tracer.reduce(), "run": run_rec,
                     "peaks": peaks, "dims": st["dm"]}
        device.update(busy_s=layer_ctx["trace"]["busy_s"],
                      window_s=layer_ctx["trace"]["window_s"])
        metrics_out = harness.per_layer(args.workload, bench["per_layer"],
                                        layer_ctx)
    else:
        metrics_out = harness.end_to_end(
            bench, args.workload, {"prune_unit_s": rec["wall"] / rec["units"],
                                   "setup_s": setup_s})
    answer = {"pruned": rec["pruned"], "reports": reports}
    free(st)
    t_check = time.perf_counter()
    nums = check(conf, cell, job, args.seed, answer)
    harness.stderr(f"bench: set-up {setup_s!r} s, window {rec['wall']!r} s, "
                   f"check {time.perf_counter() - t_check!r} s")
    lim = cell["check"]["limits"]
    ok, lines = harness.check_lines(
        [(k, nums[k], lim[k], "==" if k == "nm_violations" else "<=")
         for k in lim])
    result = {"correct": ok, "attempted": rec["units"], "failed": 0,
              "metrics": metrics_out, "device": device}
    if layer_ctx is not None:
        result["breakdown"] = layer_ctx["trace"]["breakdown"]
    result["checks"] = {k: {"value": nums[k], "limit": lim[k]} for k in lim}
    return {"result": result, "checks": lines}


def control_answer(conf, job, seed) -> Dict[str, Any]:
    """The control: the reference prune put in the program's place, one
    precision lower (bf16 relay, statistics and solves), every unit."""
    ref = harness.reference(conf)
    dm = ref.dims(conf)
    dense = weights.for_config(conf, seed)
    calib = calibration(job, dm["vocab"], seed)
    T = calib[0]["tokens"].shape[1]
    pos = jnp.arange(T, dtype=jnp.int32)
    layer = jax.jit(jax.vmap(lambda p, x: ref.layer(dm, p, x, pos),
                             in_axes=(None, 0)))
    xs = [ref.embed(dense, b["tokens"]).astype(jnp.bfloat16) for b in calib]
    units, reports = [], []
    for u in range(dm["layers"]):
        unit = ref.layer_params(dense, u)
        pruned_unit, reps = prune_ref.prune_unit(ref, dm, unit, xs)
        units.append(pruned_unit)
        reports += [{"unit": f"layer{u:03d}", "key": k, "error": e,
                     "rel_error": r} for k, e, r in reps]
        xs = [layer(unit, x).astype(jnp.bfloat16) for x in xs]
    stacked = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *units)
    return {"pruned": dict(dense, layers=stacked), "reports": reports}
