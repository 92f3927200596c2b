"""Pruning driver: train (or load) a model, prune it with any registered
solver, report perplexity before/after.

    python -m repro.launch.prune --arch opt125m-proxy --method fista \
        --sparsity 50% --workers 4 --ckpt-dir /tmp/prune_ckpts
    python -m repro.launch.prune --method admm --sparsity 2:4 --smoke
    python -m repro.launch.prune --recipe my_recipe.json

``--arch`` runs at its published widths; ``--smoke`` swaps in the
reduced config (CPU-friendly) and records that choice in the checkpoint,
so ``launch/evaluate.py`` and ``launch/serve.py`` reload the same model.

This is the end-to-end path of the paper: calibration data -> layer-wise
pruning with intra-layer error correction -> pruned checkpoint ->
WikiText-style perplexity table row.  All pruning configuration flows
through one ``repro.api.PruneRecipe`` (serialized into the JSON report,
so any run is reproducible from its report alone).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from typing import List, Optional

from repro import api, obs
from repro.launch import enable_compile_cache
from repro.checkpoint import store
from repro.core.solvers import registered_solvers
from repro.data import CorpusConfig, MarkovCorpus
from repro.train import AdamWConfig, TrainConfig, Trainer, evaluate_ppl
from repro.utils import get_logger

log = get_logger("launch.prune")

#: model checkpoints a prune run leaves in its checkpoint dir (next to the
#: scheduler's per-unit checkpoints) — `launch/evaluate.py` and the serve
#: path consume these by name
DENSE_MODEL, PRUNED_MODEL = api.DENSE_MODEL, api.PRUNED_MODEL


def save_run_models(ckpt_dir: str, recipe: api.PruneRecipe, dense_params,
                    pruned_params=None, reports=None, save_dense: bool = True,
                    **extra) -> None:
    """Persist the run's dense (and pruned) model params with everything
    needed to re-evaluate them: the recipe, the corpus seed, and the
    per-operator solver reports (the error-budget audit's budgets).
    ``save_dense=False`` skips the dense write when an identical snapshot
    was already saved (the pre-prune call)."""
    meta = dict(extra, recipe=recipe.to_dict())
    if save_dense:
        store.save(ckpt_dir, DENSE_MODEL, {"params": dense_params},
                   extra=meta)
    if pruned_params is not None:
        meta = dict(meta, reports=[dataclasses.asdict(r)
                                   for r in (reports or [])])
        store.save(ckpt_dir, PRUNED_MODEL, {"params": pruned_params},
                   extra=meta)


def recipe_from_args(args: argparse.Namespace) -> api.PruneRecipe:
    """CLI flags -> PruneRecipe (the only place flags map onto config)."""
    mesh = api.MeshConfig.parse(args.mesh).to_dict() if args.mesh else {}
    if args.recipe:
        recipe = api.PruneRecipe.from_json(args.recipe)
        if mesh:      # --mesh overrides the recipe's mesh section only
            recipe = dataclasses.replace(recipe, mesh=mesh)
        return recipe
    solver_kwargs = {}
    if args.method == "fista":
        solver_kwargs = {"warm_start": args.warm_start,
                         "outer_impl": args.outer_impl,
                         "group_batch": not args.no_group_batch,
                         "trace_len": args.solver_trace_len}
    elif args.method == "admm":
        solver_kwargs = {"warm_start": args.warm_start}
    return api.PruneRecipe(
        arch=args.arch, method=args.method, solver=solver_kwargs,
        sparsity=args.sparsity, correction=args.correction,
        calibration={"num_sequences": args.calib_sequences,
                     "seq_len": args.calib_seq_len, "batch_size": 8,
                     "seed": args.seed},
        scheduler={"workers": args.workers,
                   "checkpoint_dir": args.ckpt_dir},
        mesh=mesh)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="opt125m-proxy",
                    choices=list(api.ARCH_CHOICES))
    ap.add_argument("--smoke", action="store_true",
                    help="smoke-size config for --arch (recorded in the "
                         "checkpoint)")
    ap.add_argument("--method", default="fista",
                    choices=sorted(registered_solvers()))
    ap.add_argument("--sparsity", default="50%", help="'50%%' or '2:4'")
    ap.add_argument("--correction", default="intra",
                    choices=["intra", "none", "full", "cross"])
    ap.add_argument("--warm-start", default="wanda",
                    choices=["wanda", "sparsegpt", "magnitude", "dense"])
    ap.add_argument("--outer-impl", default="fused", choices=["fused", "host"],
                    help="Algorithm-1 outer loop: fused on-device lax.while_loop"
                         " (default) or the host-Python reference")
    ap.add_argument("--no-group-batch", action="store_true",
                    help="disable the vmap-batched solve of same-shape"
                         " operator groups (wq/wk/wv, gate/up, MoE experts)")
    ap.add_argument("--solver-trace-len", type=int, default=8,
                    help="per-operator convergence trace budget: keep this "
                         "many outer-iteration (error, lambda) pairs per "
                         "solve, recorded into repro.obs (0 disables)")
    ap.add_argument("--recipe", default=None,
                    help="load the full PruneRecipe from this JSON file "
                         "(overrides every other pruning flag)")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="device mesh 'dataxmodel' (e.g. '4x2'): Gram "
                         "accumulation shards calibration batches over "
                         "'data', solves can row-shard over 'model' "
                         "(resolved through distributed/executor.py)")
    ap.add_argument("--train-steps", type=int, default=150)
    ap.add_argument("--calib-sequences", type=int, default=32)
    ap.add_argument("--calib-seq-len", type=int, default=64)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--out", default=None, help="write a JSON report here")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    try:
        recipe = recipe_from_args(args)
        # a bad --mesh (unparseable, or more devices than visible) must
        # die HERE — before the dense model is trained — with the same
        # clean error/exit-2 contract as the evaluate and serve CLIs
        executor = recipe.build_executor()
    except (ValueError, FileNotFoundError) as exc:
        print(f"error: {exc}", file=sys.stderr)
        return 2
    model = recipe.load_model(smoke=args.smoke)
    corpus = MarkovCorpus(CorpusConfig(vocab=model.cfg.vocab, seed=args.seed))

    log.info("training the dense model (%d steps)", args.train_steps)
    seq_len = recipe.calib_config().seq_len
    tr = Trainer(model, corpus, TrainConfig(
        steps=args.train_steps, batch=8, seq=seq_len,
        optim=AdamWConfig(lr=3e-3, warmup_steps=10, total_steps=args.train_steps)))
    tr.run()
    dense_ppl = evaluate_ppl(model, tr.params, corpus, 8, seq_len, 4)

    ckpt_dir = recipe.scheduler_config().checkpoint_dir
    if ckpt_dir:
        # dense snapshot BEFORE pruning: a run killed mid-prune leaves
        # dense_model + the scheduler's unit_* checkpoints, which
        # launch/evaluate.py can assemble into the pruned model
        save_run_models(ckpt_dir, recipe, tr.params,
                        corpus_seed=args.seed, smoke=args.smoke,
                        dense_ppl=dense_ppl)

    if executor is not None:
        log.info("mesh-native run: %s", executor.describe())
    calib = api.calibration_for(recipe, corpus)
    obs.enable()            # spans + prune metrics for the whole prune phase
    pruned, reports, stats = api.prune(model, tr.params, calib, recipe,
                                       executor=executor)
    pruned_ppl = evaluate_ppl(model, pruned, corpus, 8, seq_len, 4)

    if ckpt_dir:
        save_run_models(ckpt_dir, recipe, tr.params, pruned, reports,
                        save_dense=False,   # identical snapshot saved above
                        corpus_seed=args.seed, smoke=args.smoke,
                        dense_ppl=dense_ppl, pruned_ppl=pruned_ppl)
        log.info("saved %s + %s under %s", DENSE_MODEL, PRUNED_MODEL, ckpt_dir)
        obs_dir = obs.save_run_dir(ckpt_dir)
        if obs_dir:
            log.info("obs artifacts under %s — render with "
                     "`python -m repro.obs report %s`", obs_dir, ckpt_dir)

    rel = sum(r.rel_error for r in reports) / max(len(reports), 1)
    batched = sum(1 for r in reports if r.group_size > 1)
    print(f"arch={recipe.arch} method={recipe.method} "
          f"sparsity={recipe.sparsity} correction={recipe.correction}")
    print(f"dense_ppl={dense_ppl:.3f} pruned_ppl={pruned_ppl:.3f} "
          f"mean_rel_err={rel:.4f} units={stats.get('completed', 'n/a')} "
          f"group_batched_ops={batched}/{len(reports)}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"arch": recipe.arch, "method": recipe.method,
                       "sparsity": recipe.sparsity, "dense_ppl": dense_ppl,
                       "pruned_ppl": pruned_ppl, "mean_rel_err": rel,
                       "group_batched_ops": batched,
                       "recipe": recipe.to_dict()}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
