import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                           + os.environ.get("REPRO_DRYRUN_DEVICES", "512")
                           + " " + os.environ.get("XLA_FLAGS", ""))

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST run before any other import (jax locks the
device count on first init).  For every cell this driver

    1. builds the full-size config and ShapeDtypeStruct inputs
       (zero device allocation — weak-type-correct stand-ins),
    2. jits the right step (train_step for train shapes, prefill for
       prefill shapes, serve_step for decode shapes) with the sharding
       rules of distributed/sharding.py on the production mesh,
    3. ``.lower().compile()`` — any sharding mismatch, OOM-at-compile or
       unsupported collective is a bug in the framework, surfaced here,
    4. records memory_analysis / cost_analysis / the collective-bytes
       parse of the optimized HLO into experiments/dryrun/*.json for the
       roofline analysis (launch/roofline.py).

Usage:
    python -m repro.launch.dryrun --arch stablelm-1.6b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import dataclasses
import json
import re
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import api
from repro.configs.base import ALL_ARCHS, SHAPES, ShapeSpec, shape_applicable
from repro.distributed import sharding as rules
from repro.launch import enable_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.models.registry import ModelDef
from repro.train import optim

_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
                "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8, "u16": 2,
                "s16": 2, "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


# ---------------------------------------------------------------------------
# HLO collective parsing
# ---------------------------------------------------------------------------
def _shape_bytes(token: str) -> int:
    m = re.match(r"(\w+?)\[([\d,]*)\]", token)
    if not m:
        return 0
    dt, dims = m.group(1), m.group(2)
    size = _DTYPE_BYTES.get(dt, 4)
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * size


def _group_size(line: str, default: int) -> int:
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)
    if m:
        return int(m.group(2))
    m = re.search(r"replica_groups=\{\{([\d,]+)\}", line)
    if m:
        return len(m.group(1).split(","))
    return default


def collective_bytes(hlo_text: str, n_devices: int) -> Dict[str, Any]:
    """Per-device wire bytes of every collective in the optimized HLO.

    Ring-model normalization on the RESULT shapes parsed from each op's
    defining line: all-reduce 2(g-1)/g * size, all-gather (g-1)/g * size,
    reduce-scatter (g-1) * shard size, all-to-all (g-1)/g, permute 1x.
    First-order (ignores tree algorithms / ICI contention), consistent
    across cells — exactly what the roofline comparison needs.
    """
    totals = {op: 0.0 for op in _COLLECTIVES}
    counts = {op: 0 for op in _COLLECTIVES}
    for line in hlo_text.splitlines():
        ls = line.strip()
        if not ls.startswith("%") and " = " not in ls:
            continue
        for op in _COLLECTIVES:
            if f"{op}(" in ls or f"{op}-start(" in ls or f"{op}-done(" in ls:
                if f"{op}-done(" in ls:
                    continue  # counted at -start
                lhs = ls.split(" = ", 1)[-1]
                shapes = re.findall(r"\w+\[[\d,]*\]", lhs.split("(")[0])
                size = sum(_shape_bytes(s) for s in shapes)
                g = _group_size(ls, n_devices)
                if g <= 1:
                    continue
                if op == "all-reduce":
                    wire = 2.0 * (g - 1) / g * size
                elif op == "all-gather":
                    wire = (g - 1) / g * size
                elif op == "reduce-scatter":
                    wire = float(g - 1) * size
                elif op == "all-to-all":
                    wire = (g - 1) / g * size
                else:
                    wire = float(size)
                totals[op] += wire
                counts[op] += 1
                break
    return {"bytes_by_op": totals, "counts": counts,
            "total_bytes": sum(totals.values())}


# ---------------------------------------------------------------------------
# cell construction
# ---------------------------------------------------------------------------
def _dp_axes(mesh, batch: int) -> Tuple[str, ...]:
    axes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    size = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
    if axes and batch % size == 0 and batch >= size:
        return axes
    return ()   # small batches (long_500k B=1) replicate the batch dim


def model_flops(model: ModelDef, shape: ShapeSpec) -> float:
    n = model.cfg.param_count(active_only=True)
    if shape.kind == "train":
        return 6.0 * n * shape.seq_len * shape.global_batch
    if shape.kind == "prefill":
        return 2.0 * n * shape.seq_len * shape.global_batch
    return 2.0 * n * shape.global_batch          # decode: per emitted token


def build_lowerable(model: ModelDef, shape: ShapeSpec, mesh):
    """Returns (fn, example_args, in_shardings) for the cell's step."""
    cfg = model.cfg
    batch_specs = model.batch_specs(shape)
    dp = _dp_axes(mesh, shape.global_batch)
    repl = NamedSharding(mesh, P())

    if shape.kind in ("train", "prefill"):
        params_shape = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
        psh = rules.make_shardings(mesh, rules.param_specs(params_shape), params_shape)
        bsh = rules.make_shardings(mesh, rules.batch_specs(batch_specs, dp), batch_specs)
        if shape.kind == "train":
            ocfg = optim.AdamWConfig()
            opt_shape = jax.eval_shape(optim.init, params_shape)
            osh = optim.AdamWState(step=repl, mu=psh, nu=psh)

            def step(p, o, b):
                (l, m), g = jax.value_and_grad(
                    lambda pp: model.loss(pp, b), has_aux=True)(p)
                p2, o2, om = optim.update(ocfg, g, o, p)
                return p2, o2, l

            fn = jax.jit(step, in_shardings=(psh, osh, bsh),
                         out_shardings=(psh, osh, None))
            return fn, (params_shape, opt_shape, batch_specs)

        if model.prefill is not None:
            # true prefill: fill KV caches, unembed ONLY the last position
            # (§Perf iteration 2 — the full (B,S,V) logits tensor dominated
            # the memory term for large-vocab archs)
            cache_len = min(shape.seq_len, cfg.max_seq)
            if cfg.window:
                cache_len = min(cache_len, cfg.window)
            extras = {k: v for k, v in batch_specs.items()
                      if k not in ("tokens", "labels")}

            def prefill_step(p, b):
                toks = b["tokens"]
                ex = {k: v for k, v in b.items() if k not in ("tokens", "labels")}
                return model.prefill(p, toks, cache_len, ex if ex else None,
                                     last_only=True)

            fn = jax.jit(prefill_step, in_shardings=(psh, bsh),
                         out_shardings=None)
            return fn, (params_shape, batch_specs)

        def prefill_step(p, b):
            return model.forward_logits(p, b)

        fn = jax.jit(prefill_step, in_shardings=(psh, bsh),
                     out_shardings=None)
        return fn, (params_shape, batch_specs)

    # decode: one new token against a seq_len-deep cache/state
    params_shape = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    psh = rules.make_shardings(mesh, rules.param_specs(params_shape), params_shape)
    B = shape.global_batch
    cache_len = min(shape.seq_len, cfg.max_seq)
    if cfg.window:
        cache_len = min(cache_len, cfg.window)
    extras = {k: v for k, v in batch_specs.items()
              if k not in ("tokens", "labels")}
    state_shape = jax.eval_shape(
        lambda p, ex: model.init_serve_state(p, B, cache_len, ex if ex else None),
        params_shape, extras)
    bidx = 0 if cfg.family == "hybrid" else 1
    ssh = rules.make_shardings(mesh, rules.state_specs(state_shape, dp, bidx),
                               state_shape) \
        if dp else rules.make_shardings(
            mesh, jax.tree_util.tree_map(lambda x: P(), state_shape))
    token = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    tsh = NamedSharding(mesh, P(dp)) if dp else repl

    def decode(p, s, t):
        return model.serve_step(p, s, t, jnp.int32(cache_len - 1))

    fn = jax.jit(decode, in_shardings=(psh, ssh, tsh),
                 out_shardings=(None, ssh))
    return fn, (params_shape, state_shape, token)


def moe_flops_deflator(cfg) -> float:
    """XLA's cost model charges ragged_dot as DENSE over all experts; the
    true per-row cost is one expert.  Deflator ~= (counted/true), estimated
    by the param-proportional flop split between routed-expert matmuls and
    everything else.  1.0 for non-MoE archs."""
    m = cfg.moe
    if m is None:
        return 1.0
    d = cfg.d_model
    hd = cfg.resolved_head_dim()
    attn = d * (cfg.num_heads * hd) + 2 * d * (cfg.num_kv_heads * hd) \
        + (cfg.num_heads * hd) * d
    shared = 3 * d * m.shared_ff if (m.num_shared and m.shared_ff) else 0
    routed_active = m.top_k * 3 * d * m.expert_ff
    routed_counted = m.num_experts * 3 * d * m.expert_ff
    true = attn + shared + routed_active
    counted = attn + shared + routed_counted
    return counted / true


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: Optional[str] = None, verbose: bool = True,
             unroll: bool = False) -> Dict[str, Any]:
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(arch, shape_name)
    if not ok:
        rec = {"arch": arch, "shape": shape_name,
               "mesh": "multi" if multi_pod else "single",
               "skipped": True, "reason": why}
        _write(rec, out_dir)
        return rec

    # same arch builder as launch/prune.py (repro.api) — the two drivers
    # must not drift on how an arch name resolves to a config
    model = api.load_model(arch)
    if unroll:  # unrolled layers: accurate HLO cost accounting (scan bodies
        # are otherwise counted ONCE by XLA's cost analysis)
        from repro.models.registry import model_def
        model = model_def(model.cfg.replace(scan_layers=False))
    need = 512 if multi_pod else 256
    if jax.device_count() >= need:
        mesh = make_production_mesh(multi_pod=multi_pod)
    else:  # REPRO_DRYRUN_DEVICES reduced run (CI): same axes, smaller mesh
        from repro.launch.mesh import make_debug_mesh
        mesh = make_debug_mesh(jax.device_count(), multi_pod=multi_pod)
    n_dev = int(np.prod(list(mesh.shape.values())))
    t0 = time.perf_counter()
    with mesh, jax.sharding.set_mesh(mesh):
        fn, args = build_lowerable(model, shape, mesh)
        lowered = fn.lower(*args)
        t_lower = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis()
        coll = collective_bytes(compiled.as_text(), n_dev)

    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "kind": shape.kind, "chips": n_dev, "skipped": False,
        "seq_len": shape.seq_len, "global_batch": shape.global_batch,
        "flops_per_device": float(ca.get("flops", 0.0)),
        "bytes_per_device": float(ca.get("bytes accessed", 0.0)),
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "peak_bytes": int(ma.argument_size_in_bytes + ma.temp_size_in_bytes
                          + ma.output_size_in_bytes),
        "collectives": coll,
        "model_flops_global": model_flops(model, shape),
        "params": int(model.cfg.param_count()),
        "params_active": int(model.cfg.param_count(active_only=True)),
        "moe_flops_deflator": moe_flops_deflator(model.cfg),
        "unrolled": unroll,
        "lower_seconds": t_lower, "compile_seconds": t_compile,
    }
    if verbose:
        print(f"[{rec['mesh']}] {arch} x {shape_name}: "
              f"compile {t_compile:.1f}s  "
              f"mem/dev {rec['peak_bytes']/1e9:.2f} GB  "
              f"flops/dev {rec['flops_per_device']:.3e}  "
              f"coll/dev {coll['total_bytes']/1e6:.1f} MB")
        print("  memory_analysis:", ma)
    _write(rec, out_dir)
    return rec


def _write(rec: Dict[str, Any], out_dir: Optional[str]) -> None:
    if not out_dir:
        return
    os.makedirs(out_dir, exist_ok=True)
    name = f"{rec['mesh']}__{rec['arch']}__{rec['shape']}.json"
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(rec, f, indent=1)


# ---------------------------------------------------------------------------
# accurate cost accounting via two-point depth extrapolation
# ---------------------------------------------------------------------------
def _reduced_cfg(cfg, n_layers: int):
    """Same arch at ``n_layers`` layers, unrolled (for cost extrapolation)."""
    kw = {"num_layers": n_layers, "scan_layers": False}
    if cfg.encdec is not None:
        import dataclasses as dc
        kw["encdec"] = dc.replace(cfg.encdec, enc_layers=n_layers // 2,
                                  dec_layers=n_layers // 2)
    return cfg.replace(**kw)


def _cell_costs(model: ModelDef, shape: ShapeSpec, mesh, n_dev: int) -> Dict[str, Any]:
    fn, args = build_lowerable(model, shape, mesh)
    compiled = fn.lower(*args).compile()
    ca = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text(), n_dev)
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "coll": coll["total_bytes"],
            "coll_by_op": coll["bytes_by_op"]}


def flash_attn_analytic(cfg, shape: ShapeSpec, n_dev: int, dp: int) -> Dict[str, float]:
    """Analytic per-device fwd attention cost when the flash kernel is in
    use (the pallas grid body is counted once by XLA, like a scan).
    flops = 4 * B * Hq * S * K_eff * D (QK^T + PV), K_eff = S/2 causal or
    the window; bytes = Q + K + V + O only (the kernel's whole point)."""
    B = max(shape.global_batch // max(dp, 1), 1)
    S = min(shape.seq_len, cfg.max_seq)
    D = cfg.resolved_head_dim()
    Hq_local = max(cfg.num_heads // 16, 1)   # model-axis sharding of heads
    k_eff = min(cfg.window or S, S) if cfg.window else S / 2.0
    L = cfg.num_layers
    flops = 4.0 * B * Hq_local * S * k_eff * D * L
    bytes_ = 2.0 * B * S * D * (2 * Hq_local + 2 * max(cfg.num_kv_heads // 16, 1)) * L
    return {"flops": flops, "bytes": bytes_}


def run_cell_extrapolated(arch: str, shape_name: str, multi_pod: bool,
                          out_dir: Optional[str] = None,
                          verbose: bool = True, flash: bool = False) -> Dict[str, Any]:
    """Accurate cost accounting: XLA counts a lax.scan body ONCE regardless
    of trip count, so the scan-mode records undercount flops/bytes/
    collectives by ~num_layers.  Here the same cell is lowered UNROLLED at
    two small pattern-complete depths L1 < L2, the exact linear model
    cost = outside + depth * per_layer is solved, and extrapolated to the
    full depth.  Memory numbers still come from the scan-mode dry-run
    (that IS the production execution)."""
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(arch, shape_name)
    if not ok:
        rec = {"arch": arch, "shape": shape_name,
               "mesh": "multi" if multi_pod else "single",
               "skipped": True, "reason": why}
        _write(rec, out_dir)
        return rec

    model = api.load_model(arch)
    if flash:
        from repro.models.registry import model_def as _md
        model = _md(model.cfg.replace(attn_impl="flash"))
    cfg = model.cfg
    if cfg.rglru is not None:
        period = len(cfg.rglru.block_pattern)
    elif cfg.encdec is not None:
        period = 2                      # one enc + one dec layer
    else:
        period = 1
    L1, L2 = period, 2 * period
    full_depth = cfg.num_layers

    need = 512 if multi_pod else 256
    if jax.device_count() >= need:
        mesh = make_production_mesh(multi_pod=multi_pod)
    else:
        from repro.launch.mesh import make_debug_mesh
        mesh = make_debug_mesh(jax.device_count(), multi_pod=multi_pod)
    n_dev = int(np.prod(list(mesh.shape.values())))

    from repro.models.registry import model_def
    t0 = time.perf_counter()
    with mesh, jax.sharding.set_mesh(mesh):
        c1 = _cell_costs(model_def(_reduced_cfg(cfg, L1)), shape, mesh, n_dev)
        c2 = _cell_costs(model_def(_reduced_cfg(cfg, L2)), shape, mesh, n_dev)
    elapsed = time.perf_counter() - t0

    def extrap(a, b):
        per_layer = (b - a) / (L2 - L1)
        outside = a - per_layer * L1
        return max(outside + per_layer * full_depth, 0.0)

    coll_by_op = {op: extrap(c1["coll_by_op"][op], c2["coll_by_op"][op])
                  for op in c1["coll_by_op"]}
    flops_x = extrap(c1["flops"], c2["flops"])
    bytes_x = extrap(c1["bytes"], c2["bytes"])
    flash_add = None
    if flash and shape.kind in ("train", "prefill"):
        dp = 1
        for a in ("pod", "data"):
            if a in mesh.shape and shape.global_batch % (dp * mesh.shape[a]) == 0:
                dp *= mesh.shape[a]
        flash_add = flash_attn_analytic(cfg, shape, n_dev, dp)
        flops_x += flash_add["flops"]
        bytes_x += flash_add["bytes"]
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "kind": shape.kind, "chips": n_dev, "skipped": False,
        "seq_len": shape.seq_len, "global_batch": shape.global_batch,
        "flops_per_device": flops_x,
        "bytes_per_device": bytes_x,
        "flash": flash, "flash_analytic_add": flash_add,
        "collectives": {"total_bytes": extrap(c1["coll"], c2["coll"]),
                        "bytes_by_op": coll_by_op},
        "model_flops_global": model_flops(model, shape),
        "params": int(cfg.param_count()),
        "params_active": int(cfg.param_count(active_only=True)),
        "moe_flops_deflator": moe_flops_deflator(cfg),
        "method": f"two-point depth extrapolation (L={L1},{L2} -> {full_depth})",
        "compile_seconds": elapsed,
    }
    if verbose:
        print(f"[extrap/{rec['mesh']}] {arch} x {shape_name}: "
              f"flops/dev {rec['flops_per_device']:.3e}  "
              f"bytes/dev {rec['bytes_per_device']:.3e}  "
              f"coll/dev {rec['collectives']['total_bytes']/1e6:.1f} MB  "
              f"({elapsed:.1f}s)")
    _write(rec, out_dir)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(api.ARCH_CHOICES))
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--unroll", action="store_true",
                    help="unroll layer scans for accurate cost accounting")
    ap.add_argument("--extrapolate", action="store_true",
                    help="two-point depth extrapolation cost records")
    ap.add_argument("--flash", action="store_true",
                    help="use the Pallas flash-attention kernel (Perf it. 3)")
    args = ap.parse_args()
    enable_compile_cache()

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    cells = []
    if args.all:
        for arch in ALL_ARCHS:
            for shape in SHAPES:
                cells.append((arch, shape))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    failures = []
    for multi in meshes:
        for arch, shape in cells:
            try:
                if args.extrapolate:
                    run_cell_extrapolated(arch, shape, multi, args.out,
                                          flash=args.flash)
                else:
                    run_cell(arch, shape, multi, args.out, unroll=args.unroll)
            except Exception as exc:  # noqa: BLE001 — report-all driver
                failures.append((arch, shape, multi, repr(exc)))
                print(f"FAILED [{'multi' if multi else 'single'}] {arch} x {shape}: {exc}")
    if failures:
        raise SystemExit(f"{len(failures)} dry-run cells failed: "
                         + "; ".join(f"{a}/{s}" for a, s, _, _ in failures))
    print("dry-run complete: all cells lowered + compiled")


if __name__ == "__main__":
    main()
