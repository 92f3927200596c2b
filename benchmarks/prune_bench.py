"""Solver-throughput benchmark: FISTA outer-loop variants + the full
per-solver matrix of the registry.

Two sections, both over one transformer pruning unit (all four operator
groups of a decoder layer), both configured through ``PruneRecipe``:

* ``rows`` — Algorithm 1 under its three outer-loop implementations
  (``host`` reference / ``fused`` device-resident / ``fused-group``
  vmap-batched), the PR-1 speedup trajectory;
* ``solver_matrix`` — one row per registered solver (fista, admm,
  frankwolfe, wanda, sparsegpt) per sparsity: wall-clock, mean relative
  error, batched-op share.  This is the extensibility surface made
  measurable — a newly registered solver shows up here by adding its
  name to ``MATRIX``.

Unlike the kernel microbenchmarks, wall-clock is meaningful here on any
backend: the fused paths remove host<->device round trips, which cost on
CPU exactly as they do on TPU.  Each variant is run once to compile and
then timed, so the numbers compare steady-state solves.

Writes ``BENCH_prune.json`` at the repo root (and a copy under
``experiments/bench/``) so the perf trajectory is tracked from PR to PR.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Dict, List

import jax

from repro.api import PruneRecipe
from repro.core.sequential import prune_model
from repro.data import CalibConfig, CorpusConfig, MarkovCorpus, calibration_batches
from repro.models.registry import model_def

OUT_PATH = "BENCH_prune.json"
MESH_OUT_PATH = "BENCH_prune_mesh.json"

SPARSITIES = ("50%", "2:4")
MATRIX = ("fista", "admm", "frankwolfe", "wanda", "sparsegpt")

# paper-default solver depth (K=20), deep enough that the solve dominates
# the unit wall-clock; shared by every fista-family recipe below
_FISTA_KW = {"fista_iters": 20, "max_outer": 12, "patience": 3, "eps": 1e-6}


def _unit_problem(d_model: int = 64, d_ff: int = 128, seed: int = 0):
    from repro.configs.opt125m_proxy import tiny_config
    cfg = tiny_config().replace(num_layers=1, d_model=d_model, d_ff=d_ff,
                                num_heads=4, num_kv_heads=4, vocab=128)
    model = model_def(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    corpus = MarkovCorpus(CorpusConfig(vocab=cfg.vocab, seed=7))
    calib = calibration_batches(corpus, CalibConfig(num_sequences=8, seq_len=32,
                                                    batch_size=4))
    return model, params, calib


def _impl_recipes(sparsity: str) -> Dict[str, PruneRecipe]:
    return {
        "host": PruneRecipe(method="fista", sparsity=sparsity,
                            solver=dict(_FISTA_KW, outer_impl="host")),
        "fused": PruneRecipe(method="fista", sparsity=sparsity,
                             solver=dict(_FISTA_KW, outer_impl="fused",
                                         group_batch=False)),
        "fused-group": PruneRecipe(method="fista", sparsity=sparsity,
                                   solver=dict(_FISTA_KW, outer_impl="fused",
                                               group_batch=True)),
    }


def _timed_prune(model, params, calib, recipe: PruneRecipe,
                 repeats: int) -> Dict:
    cfg = recipe.sequential_config()
    prune_model(model, params, calib, cfg)          # compile
    times, solver_times, reports = [], [], []
    for _ in range(repeats):
        t0 = time.perf_counter()
        _, reports = prune_model(model, params, calib, cfg)
        times.append(time.perf_counter() - t0)
        solver_times.append(sum(r.seconds for r in reports))
    return {
        "unit_seconds": min(times),
        "solver_seconds": min(solver_times),
        "operators": len(reports),
        "batched_operators": sum(1 for r in reports if r.group_size > 1),
        "mean_rel_err": (sum(r.rel_error for r in reports)
                         / max(len(reports), 1)),
    }


def bench_prune_impls(d_model: int = 64, d_ff: int = 128,
                      repeats: int = 5) -> List[Dict]:
    """FISTA outer-loop implementation comparison (host/fused/fused-group)."""
    model, params, calib = _unit_problem(d_model, d_ff)
    rows: List[Dict] = []
    for sparsity in SPARSITIES:
        for name, recipe in _impl_recipes(sparsity).items():
            row = dict(impl=name, sparsity=sparsity, d_model=d_model,
                       d_ff=d_ff,
                       **_timed_prune(model, params, calib, recipe, repeats))
            rows.append(row)
            print(f"{name:>12} {sparsity}: unit {row['unit_seconds']*1e3:8.1f} ms  "
                  f"solver {row['solver_seconds']*1e3:8.1f} ms  "
                  f"({row['batched_operators']}/{row['operators']} batched)")
    return rows


def bench_solver_matrix(d_model: int = 64, d_ff: int = 128,
                        repeats: int = 3) -> List[Dict]:
    """One row per registered solver per sparsity — the pluggable-API
    surface under benchmark.  New solvers: add the name to MATRIX."""
    model, params, calib = _unit_problem(d_model, d_ff)
    rows: List[Dict] = []
    for sparsity in SPARSITIES:
        for method in MATRIX:
            solver_kw = dict(_FISTA_KW) if method == "fista" else {}
            recipe = PruneRecipe(method=method, sparsity=sparsity,
                                 solver=solver_kw)
            # solvers that don't read the pruned-path Gram report the
            # dense-path error ||YX - WX||; tag each row so rel_err
            # columns are not compared across different metrics
            error_stats = ("pruned-path" if recipe.build_solver().wants_pruned_gram
                           else "dense-path")
            row = dict(solver=method, sparsity=sparsity, d_model=d_model,
                       d_ff=d_ff, error_stats=error_stats,
                       **_timed_prune(model, params, calib, recipe, repeats))
            rows.append(row)
            print(f"{method:>12} {sparsity}: unit {row['unit_seconds']*1e3:8.1f} ms  "
                  f"rel_err {row['mean_rel_err']:.4f} ({error_stats})  "
                  f"({row['batched_operators']}/{row['operators']} batched)")
    print("   (rel_err is ||YX*-WX|| for pruned-path rows, ||YX-WX|| for"
          " dense-path rows — compare within a mode, or by table ppl)")
    return rows


def _summarize(rows: List[Dict]) -> Dict[str, float]:
    """Host-loop time / variant time (>1 means the variant wins), averaged
    over sparsities, for both unit wall-clock and the solver phase."""
    out: Dict[str, float] = {}
    for impl in ("fused", "fused-group"):
        for metric in ("unit_seconds", "solver_seconds"):
            ratios = []
            for row in rows:
                if row["impl"] != impl:
                    continue
                host = next(r for r in rows if r["impl"] == "host"
                            and r["sparsity"] == row["sparsity"])
                ratios.append(host[metric] / max(row[metric], 1e-12))
            key = f"{impl}_{metric.removesuffix('_seconds')}"
            out[key] = sum(ratios) / max(len(ratios), 1)
    return out


# ---------------------------------------------------------------------------
# mesh-native Gram accumulation: 1-device vs 8-fake-device dispatch row
# ---------------------------------------------------------------------------
def _mesh_gram_child(devices: int) -> Dict:
    """Runs INSIDE a subprocess whose XLA_FLAGS already forces ``devices``
    fake host devices: prune one unit with the calibration batches
    data-sharded over the mesh and count Gram-accumulation dispatches."""
    from repro.core import sequential as seq_lib

    model, params, _ = _unit_problem()
    # 8 calibration micro-batches so every probed mesh divides them (one
    # batch per shard at 8 devices — the bitwise-parity regime)
    corpus = MarkovCorpus(CorpusConfig(vocab=model.cfg.vocab, seed=7))
    calib = calibration_batches(corpus, CalibConfig(num_sequences=32,
                                                    seq_len=32, batch_size=4))
    counts = {"dispatches": 0, "stacked_batches": 0}
    orig = seq_lib._group_stats_scan

    def counting(init, current, ws, caps, ps, **kw):
        counts["dispatches"] += 1
        counts["stacked_batches"] += int(
            jax.tree_util.tree_leaves(caps)[0].shape[0])
        return orig(init, current, ws, caps, ps, **kw)

    seq_lib._group_stats_scan = counting
    try:
        mesh = ({"devices": devices, "data_parallel": devices,
                 "model_parallel": 1} if devices > 1 else {})
        recipe = PruneRecipe(sparsity="2:4", mesh=mesh,
                             solver=dict(_FISTA_KW, max_outer=4,
                                         fista_iters=5))
        from repro import api
        t0 = time.perf_counter()
        _, reports, _ = api.prune(model, params, calib, recipe)
        wall = time.perf_counter() - t0
    finally:
        seq_lib._group_stats_scan = orig
    # under the mesh the counting wrapper runs inside shard_map, so the
    # stacked length it sees is already the per-device slice
    per_device = counts["stacked_batches"] // max(counts["dispatches"], 1)
    return {
        "devices": devices,
        "data_parallel": devices,
        "gram_dispatches": counts["dispatches"],
        "calib_batches": len(calib),
        # scan trip count each device executes per dispatch — the thing
        # data parallelism divides (the dispatch count itself is mesh-
        # independent: one sharded scan replaces one serial scan)
        "scan_steps_per_device": per_device,
        "operators": len(reports),
        "wall_s": wall,
    }


def bench_mesh_gram(device_counts=(1, 8)) -> Dict:
    """Parent-side: spawn one child per device count (XLA fake-device
    flags must be set before jax initializes, hence subprocesses) and
    assemble the comparison row for BENCH_prune.json.

    The children run on fake CPU host devices, always: their row holds
    CPU counts (dispatches, scan steps), not chip numbers.  The caller
    must not have touched a JAX device yet — a process that holds the
    chip keeps it from every child."""
    from repro.utils.compat import force_host_devices_flags

    rows = []
    for n in device_counts:
        env = dict(os.environ)
        # replace (not prepend to) any inherited device-count flag — the
        # last duplicated XLA flag wins, so an exported =8 would
        # override the child's count
        env["XLA_FLAGS"] = force_host_devices_flags(n)
        env["JAX_PLATFORMS"] = "cpu"
        out = subprocess.run(
            [sys.executable, "-m", "benchmarks.prune_bench",
             "--mesh-gram-child", str(n)],
            env=env, capture_output=True, text=True, timeout=900)
        if out.returncode != 0:
            raise RuntimeError(f"mesh-gram child ({n} devices) failed:\n"
                               f"{out.stdout}\n{out.stderr}")
        row = json.loads(out.stdout.splitlines()[-1])
        rows.append(row)
        print(f"{n:>2} fake CPU device(s): {row['gram_dispatches']} Gram "
              f"dispatches, "
              f"{row['scan_steps_per_device']} scan step(s)/device "
              f"({row['calib_batches']} calib batches)")
    base = rows[0]
    return {"backend": "cpu", "rows": rows,
            "scan_step_ratio": base["scan_steps_per_device"]
            / max(rows[-1]["scan_steps_per_device"], 1)}


def run_all(out_path: str = OUT_PATH) -> List[Dict]:
    # the CPU children first, while this process holds no device
    print("\n== Mesh-native Gram accumulation (1 vs 8 fake CPU devices) ==")
    mesh_gram = bench_mesh_gram()
    print("\n== Prune solver bench (host vs fused vs group-batched) ==")
    rows = bench_prune_impls()
    print("\n== Per-solver matrix (fista / admm / frankwolfe / wanda /"
          " sparsegpt) ==")
    matrix = bench_solver_matrix()
    summary = _summarize(rows)
    payload = {"rows": rows, "solver_matrix": matrix, "summary": summary,
               "mesh_gram": mesh_gram, "backend": jax.default_backend()}
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=1)
    from benchmarks import common
    common.write_result("prune_bench", payload)
    print(f"\nwrote {out_path}; speedup vs host-loop: "
          + "  ".join(f"{k}={v:.2f}x" for k, v in sorted(summary.items())))
    return rows


def main(argv: List[str]) -> int:
    if "--mesh-gram-child" in argv:
        n = int(argv[argv.index("--mesh-gram-child") + 1])
        print(json.dumps(_mesh_gram_child(n)))
        return 0
    if "--mesh-only" in argv:
        # the CI distributed job's cheap entry: just the mesh comparison
        payload = bench_mesh_gram()
        with open(MESH_OUT_PATH, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"wrote {MESH_OUT_PATH}")
        return 0
    run_all()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
