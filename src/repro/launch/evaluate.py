"""Quality-evaluation driver: perplexity / KL / error budget on any
checkpoint-store run.

    # a prune run (dense_model + pruned_model saved by launch/prune.py):
    python -m repro.launch.evaluate --checkpoint /tmp/run --against-dense

    # a training run (step_* checkpoints): dense perplexity only
    python -m repro.launch.evaluate --checkpoint /tmp/train_run

    # override eval knobs via a recipe's `eval` section
    python -m repro.launch.evaluate --checkpoint /tmp/run --recipe r.json

The evaluated checkpoint is resolved in order: ``pruned_model`` (saved by
launch/prune.py), a ``dense_model`` + per-unit ``unit_*`` scheduler
checkpoints (a prune run that died before its final save — units are
merged back into the dense params), then the latest trainer ``step_*``.
``--against-dense`` additionally loads the dense reference and reports
KL divergence, greedy-decode agreement and the per-unit error-budget
audit (DESIGN.md §8).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
from typing import Any, Dict, List, Optional, Tuple

import jax

from repro import api
from repro.checkpoint import store
from repro.core import sequential as seq_lib
from repro.data import CorpusConfig, MarkovCorpus
from repro.eval import quality_report
from repro.launch import enable_compile_cache
from repro.utils import get_logger

log = get_logger("launch.evaluate")

DENSE_MODEL, PRUNED_MODEL = api.DENSE_MODEL, api.PRUNED_MODEL


def _load_params(run_dir: str, name: str, like) -> Tuple[Any, Dict]:
    tree, extra = store.load(run_dir, name, {"params": like})
    return tree["params"], extra


def _assemble_from_units(model, dense_params, run_dir: str
                         ) -> Tuple[Any, List[Dict]]:
    """Merge a prune run's per-unit checkpoints into the dense params."""
    params, reports = dense_params, []
    merged = 0
    for spec in model.units():
        name = f"unit_{spec.name}"
        if not store.exists(run_dir, name):
            continue
        like = {"unit_params": seq_lib._unit_params_of(dense_params, spec)}
        tree, extra = store.load(run_dir, name, like)
        params = seq_lib._write_unit_params(params, spec, tree["unit_params"])
        reports.extend(extra.get("reports", []))
        merged += 1
    if merged == 0:
        raise FileNotFoundError(f"no unit_* checkpoints under {run_dir}")
    log.info("assembled pruned params from %d unit checkpoints", merged)
    return params, reports


def resolve_run(run_dir: str, recipe_path: Optional[str] = None
                ) -> Dict[str, Any]:
    """Inspect a checkpoint-store run dir; returns what it holds.

    {kind: "prune" | "units" | "train", recipe, smoke, corpus_seed, extra}

    The run's own recipe (persisted with its checkpoints) stays the
    source of truth for what was pruned — a ``--recipe`` file only
    overrides the evaluation: its ``eval`` section replaces the stored
    one.  Without a stored recipe (e.g. a bare train run with no
    recorded arch) the ``--recipe`` file is used wholesale.
    """
    # a typo'd recipe (e.g. an unknown `eval` key) must die before any
    # checkpoint is touched, matching PruneRecipe's load-time strictness
    override = api.PruneRecipe.from_json(recipe_path) if recipe_path else None
    if not os.path.isdir(run_dir):
        raise FileNotFoundError(f"checkpoint run dir not found: {run_dir}")
    extra: Dict[str, Any] = {}
    if store.exists(run_dir, PRUNED_MODEL):
        kind = "prune"
        with open(os.path.join(run_dir, PRUNED_MODEL, "MANIFEST.json")) as f:
            extra = json.load(f)["extra"]
    elif store.exists(run_dir, DENSE_MODEL):
        kind = "units"
        with open(os.path.join(run_dir, DENSE_MODEL, "MANIFEST.json")) as f:
            extra = json.load(f)["extra"]
    elif store.latest_step(run_dir) is not None:
        kind = "train"
        name = store.step_name(store.latest_step(run_dir))
        with open(os.path.join(run_dir, name, "MANIFEST.json")) as f:
            extra = json.load(f)["extra"]
    else:
        raise FileNotFoundError(
            f"{run_dir} holds no pruned_model/dense_model/step_* checkpoint")
    if "recipe" in extra:
        recipe = api.PruneRecipe.from_dict(extra["recipe"])
    elif "arch" in extra:
        # train runs record arch/smoke but no recipe
        recipe = api.PruneRecipe(arch=extra["arch"])
    else:
        recipe = override if override is not None else api.PruneRecipe()
    if override is not None and recipe is not override:
        recipe = dataclasses.replace(recipe, eval=override.eval)
    return {"kind": kind, "recipe": recipe, "extra": extra,
            "smoke": bool(extra.get("smoke", True)),
            "corpus_seed": int(extra.get("corpus_seed", 0))}


def evaluate_run(run_dir: str, recipe_path: Optional[str] = None,
                 against_dense: bool = False, corpus_seed: Optional[int] = None,
                 mesh: Optional[str] = None):
    """Evaluate a checkpoint-store run; returns a QualityReport.

    ``mesh`` ("DATAxMODEL", e.g. "4x2") shards the perplexity/KL batches
    over the mesh "data" axis via one MeshExecutor (distributed layer);
    it overrides the mesh recorded in the run's recipe."""
    run = resolve_run(run_dir, recipe_path)
    recipe, kind = run["recipe"], run["kind"]
    if mesh is not None:
        executor = api.MeshExecutor.from_spec(mesh)   # explicit: fail loudly
    else:
        try:
            executor = recipe.build_executor()
        except ValueError as exc:
            # the run was pruned on a mesh this machine doesn't have —
            # a checkpoint must stay evaluable anywhere, so degrade to
            # the (bitwise-identical) single-device eval path
            log.warning("recorded mesh unavailable (%s); evaluating "
                        "single-device", exc)
            executor = None
    model = recipe.load_model(smoke=run["smoke"])
    like = model.init(jax.random.PRNGKey(0))
    seed = run["corpus_seed"] if corpus_seed is None else corpus_seed
    corpus = MarkovCorpus(CorpusConfig(vocab=model.cfg.vocab, seed=seed))
    cfg = recipe.eval_config()

    dense_params = reports = None
    if kind == "train":
        step = store.latest_step(run_dir)
        params, _ = _load_params(run_dir, store.step_name(step), like)
        source = store.step_name(step)
    elif kind == "prune":
        params, extra = _load_params(run_dir, PRUNED_MODEL, like)
        reports = extra.get("reports") or None
        source = PRUNED_MODEL
    else:  # units: dense_model + unit_* scheduler checkpoints
        dense0, _ = _load_params(run_dir, DENSE_MODEL, like)
        params, reports = _assemble_from_units(model, dense0, run_dir)
        source = "dense_model+unit_*"
    if against_dense:
        if kind == "train":
            raise ValueError("--against-dense needs a prune run "
                             "(dense_model checkpoint); this is a train run")
        dense_params = (dense0 if kind == "units"
                        else _load_params(run_dir, DENSE_MODEL, like)[0])

    report = quality_report(
        model, params, corpus, cfg, dense_params=dense_params,
        reports=reports,
        meta={"checkpoint": run_dir, "source": source, "kind": kind,
              "arch": recipe.arch, "method": recipe.method,
              "sparsity": recipe.sparsity},
        executor=executor)
    return report


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--checkpoint", required=True,
                    help="checkpoint-store run dir (a launch/prune.py "
                         "--ckpt-dir or a launch/train.py --ckpt-dir)")
    ap.add_argument("--recipe", default=None,
                    help="PruneRecipe JSON overriding the one stored with "
                         "the checkpoint (its `eval` section configures "
                         "this evaluation)")
    ap.add_argument("--against-dense", action="store_true",
                    help="also evaluate the run's dense reference: dense "
                         "perplexity, KL(dense||pruned), greedy agreement "
                         "and the per-unit error-budget audit")
    ap.add_argument("--corpus-seed", type=int, default=None,
                    help="override the corpus seed recorded with the run")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="device mesh 'dataxmodel' (e.g. '4x2'): shard the "
                         "eval batches over the mesh 'data' axis")
    ap.add_argument("--out", default=None, help="write the JSON report here")
    args = ap.parse_args(argv)
    enable_compile_cache()

    try:
        report = evaluate_run(args.checkpoint, args.recipe,
                              args.against_dense, args.corpus_seed,
                              mesh=args.mesh)
    except (FileNotFoundError, ValueError, KeyError) as exc:
        print(f"error: {exc}", file=sys.stderr)
        return 2
    meta = report.meta
    print(f"checkpoint={meta['checkpoint']} source={meta['source']} "
          f"arch={meta['arch']} method={meta['method']} "
          f"sparsity={meta['sparsity']}")
    print(report.summary())
    if report.error_budget:
        worst = max(report.error_budget,
                    key=lambda r: r["output_rel_err"])
        print(f"error budget: {len(report.error_budget)} units audited, "
              f"worst {worst['unit']} rel_err={worst['output_rel_err']:.4f} "
              f"budget={worst['op_budget']:.4f} within={worst['within_budget']}")
    if args.out:
        report.to_json(args.out)
        print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
