"""Trace-budget enforcement (rule family TRB, DESIGN.md §12).

Generalizes the serving suite's ``_step_fn._cache_size() == 1`` pin
(tests/test_serve_stack.py): every ``jax.jit`` in the repo declares a
*trace budget* — the maximum number of compiled executables its cache
may hold after the standard tier-1 entry points have run.  PR 6's
CPU-compiler segfault came from silently accumulated executables; a jit
without a declared owner is how that class regresses unnoticed.

``TRACE_BUDGETS`` maps ``"module:qualname"`` keys to budgets.  The same
table backs two checks:

* static (JAX004 in ``rules_jax``): every ``jax.jit`` *site* found in
  the AST must have an entry;
* runtime (``--runtime`` here): ``jax.jit`` is patched *before* any
  repro module is imported, the four entry-point scenarios run (batcher
  step, engine generate, evaluate_perplexity, api.prune), and every
  recorded jit is checked — TRB001 undeclared, TRB002 budget exceeded.

On Python < 3.11 there is no ``co_qualname``, so the creation-site
fallback key (for jits wrapped around lambdas/params, e.g. the
executor's ``_cached``) is coarse: ``module:function_name``.  A runtime
record passes TRB001 if *any* of its candidate keys is declared.
"""
from __future__ import annotations

import contextlib
import functools
import sys
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

from .core import Finding

# ---------------------------------------------------------------------------
# the declaration table: "module:qualname" -> max executables
# ---------------------------------------------------------------------------
# Budget semantics: the cache size allowed after ALL runtime scenarios
# have run (one shape per hot loop => 1; shape-polymorphic helpers get
# the number of distinct shapes the scenarios legitimately feed them).
# Entries not reached by the scenarios are static declarations of cache
# ownership — JAX004 requires every jit site in src/ to appear here.
TRACE_BUDGETS: Dict[str, int] = {
    # -- serving hot loop: joins/retirements/token steps must never
    #    re-specialize (the test_serve_stack.py:67 pin, generalized) ----
    "repro.serve.batcher:ContinuousBatcher.__init__.<locals>.step": 1,
    # chunked prefill: fixed chunk width + fixed pool shapes => one
    # executable regardless of prompt length / chunk offset / hit depth
    "repro.serve.batcher:ContinuousBatcher.__init__.<locals>.chunk_step": 1,
    "repro.serve.engine:Engine._decode_step": 1,
    # engine chunked prefill retraces per distinct prompt-block count
    # (the private per-row pool is sized ceil(P/bs)+1 blocks)
    "repro.serve.engine:Engine._chunk_step": 4,
    # -- eval: one CE/KL closure per model, cached weak-keyed ----------
    "repro.eval.perplexity:_ce_fn.<locals>.fn": 1,
    "repro.eval.divergence:kl_divergence.<locals>._stats": 1,
    # -- solver core: shape-polymorphic over (m, n) unit shapes --------
    "repro.core.fista:solve": 8,
    "repro.core.fista:kkt_residual": 8,
    "repro.core.admm:_admm_single": 8,
    "repro.core.admm:_admm_group": 8,
    "repro.core.frankwolfe:_fw_single": 8,
    "repro.core.frankwolfe:_fw_group": 8,
    "repro.core.baselines:_sparsegpt_block": 8,
    "repro.core.gram:accumulate": 8,
    "repro.core.gram:target_correlation": 8,
    "repro.core.gram:frob_error_sq": 8,
    "repro.core.gram:max_eigval": 8,
    "repro.core.pruner:_fused_single": 8,
    "repro.core.pruner:_fused_single_warm": 8,
    "repro.core.pruner:_fused_group": 8,
    "repro.core.sparsity:round_unstructured": 8,
    "repro.core.sparsity:round_nm": 16,
    "repro.core.sparsity:mask_unstructured_by_score": 8,
    "repro.core.sparsity:mask_rowwise_by_score": 8,
    "repro.core.sparsity:mask_nm_by_score": 8,
    # one capture closure per (param_path, layer) unit; cached per key
    "repro.core.sequential:_capture_forward.<locals>.fn": 2,
    "repro.core.sequential:_group_stats_scan": 8,
    # -- Pallas wrappers: retrace per (shape, static-arg) combo --------
    "repro.kernels.spmm24:spmm24": 8,
    "repro.kernels.round24:round24": 8,
    "repro.kernels.fista_step:fista_prox_step": 8,
    "repro.kernels.flash_attention:flash_attention": 8,
    "repro.kernels.paged_attention:paged_decode_attn": 8,
    "repro.kernels.paged_attention:paged_decode_attn_lanes": 8,
    "repro.kernels.paged_attention:fused_mlp24": 8,
    # -- mesh substrate: one executable per cached (fn, spec) key ------
    "repro.distributed.executor:MeshExecutor.sharded_group_stats.<locals>.build": 2,
    "repro.distributed.executor:MeshExecutor.data_map.<locals>.build": 2,
    "repro.distributed.train:make_train_step.<locals>.build": 2,
    "repro.distributed.train:make_serve_step.<locals>.build": 2,
    # -- trainer: one step family per Trainer ---------------------------
    "repro.train.trainer:make_train_step.<locals>.train_step": 2,
    "repro.train.trainer:make_train_step.<locals>.grad_step": 2,
    "repro.train.trainer:make_train_step.<locals>.apply_grads": 2,
    # -- launch dry-run lowering helpers: lowered once, never executed --
    "repro.launch.dryrun:build_lowerable.<locals>.step": 2,
    "repro.launch.dryrun:build_lowerable.<locals>.prefill_step": 2,
    "repro.launch.dryrun:build_lowerable.<locals>.decode": 2,
}


class JitRecord:
    """One jax.jit creation observed by the runtime recorder."""

    def __init__(self, keys: Tuple[str, ...], line: str,
                 jitted: Any) -> None:
        self.keys = keys            # candidate TRACE_BUDGETS keys
        self.where = line           # "file:lineno" of the creation site
        # Strong reference: budgets are read after the scenario returns,
        # and a weakref would report 0 for any jit whose owner was a
        # scenario local (vacuously passing the check).  The recorder
        # only lives for one analysis process, so pinning is harmless.
        self._fn = jitted

    def cache_size(self) -> int:
        try:
            return int(self._fn._cache_size())
        except Exception:
            return 0


def _creation_site_key(prefixes: Tuple[str, ...],
                       depth: int = 2) -> Tuple[Optional[str], str]:
    """(coarse "module:funcname" key, "file:line") of the nearest
    in-scope frame above the recorder."""
    frame = sys._getframe(depth)
    while frame is not None:
        mod = frame.f_globals.get("__name__", "")
        if mod.startswith(prefixes) and \
                not mod.startswith("repro.analysis"):
            qn = getattr(frame.f_code, "co_qualname", frame.f_code.co_name)
            return (f"{mod}:{qn}",
                    f"{frame.f_code.co_filename}:{frame.f_lineno}")
        frame = frame.f_back
    return None, "<unknown>"


@contextlib.contextmanager
def record_jits(prefixes: Tuple[str, ...] = ("repro",)
                ) -> Iterator[List[JitRecord]]:
    """Patch ``jax.jit`` so every jit created while the context is active
    (wrapping a function from a ``prefixes`` module, or created from
    one) is recorded with its candidate budget keys.  Must be entered
    BEFORE importing the modules under test (module-level ``@jax.jit``
    decorators run at import)."""
    import jax

    records: List[JitRecord] = []
    real = jax.jit

    @functools.wraps(real)
    def wrapper(fun: Optional[Callable[..., Any]] = None,
                **kw: Any) -> Any:
        if fun is None:
            return functools.partial(wrapper, **kw)
        jitted = real(fun, **kw)
        keys = []
        mod = getattr(fun, "__module__", "") or ""
        qn = getattr(fun, "__qualname__", "") or ""
        if mod.startswith(prefixes):
            keys.append(f"{mod}:{qn}")
        site_key, where = _creation_site_key(prefixes)
        if site_key is not None:
            keys.append(site_key)
        if keys:  # jits created outside repro code are not ours to budget
            records.append(JitRecord(tuple(dict.fromkeys(keys)), where,
                                     jitted))
        return jitted

    jax.jit = wrapper  # type: ignore[assignment]
    try:
        yield records
    finally:
        jax.jit = real  # type: ignore[assignment]


# ---------------------------------------------------------------------------
# tier-1 entry-point scenarios (tiny CPU configs, mirror the test suite)
# ---------------------------------------------------------------------------
def _tiny_model(vocab: int = 128) -> Tuple[Any, Any]:
    import jax
    from repro.configs.opt125m_proxy import tiny_config
    from repro.models.registry import model_def
    cfg = tiny_config().replace(num_layers=2, d_model=32, d_ff=64,
                                num_heads=4, num_kv_heads=4, vocab=vocab)
    model = model_def(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def scenario_batcher() -> None:
    """Mixed-length continuous batching — joins/retirements must not
    re-specialize the step."""
    import numpy as np
    from repro.serve import BatchConfig, ContinuousBatcher, Request
    model, params = _tiny_model()
    bc = BatchConfig(slots=3, block_size=8, max_blocks_per_request=4,
                     num_blocks=16)
    rng = np.random.default_rng(3)
    reqs = [Request(id=i, prompt=rng.integers(0, 128, size=p).astype(np.int32),
                    max_new_tokens=n, temperature=0.0)
            for i, (p, n) in enumerate([(5, 6), (9, 4), (3, 8)])]
    ContinuousBatcher(model, params, bc).run(reqs)
    # chunked prefill + prefix cache: shared prefixes, varying tail
    # lengths and chunk offsets must all hit ONE chunk executable
    bc2 = BatchConfig(slots=3, block_size=8, max_blocks_per_request=4,
                      num_blocks=16, prefill_chunk=8, prefix_cache=True)
    prefix = rng.integers(0, 128, size=9).astype(np.int32)
    reqs2 = [Request(id=i, prompt=np.concatenate(
                         [prefix, rng.integers(0, 128, size=p)]
                     ).astype(np.int32),
                     max_new_tokens=n, temperature=0.0, arrival=0.0)
             for i, (p, n) in enumerate([(4, 4), (7, 3), (2, 5)])]
    ContinuousBatcher(model, params, bc2).run(reqs2)


def scenario_engine_generate() -> None:
    """Two same-shape generate calls: the decode step traces once."""
    import jax.numpy as jnp
    import numpy as np
    from repro.serve import Engine, ServeConfig
    model, params = _tiny_model()
    eng = Engine(model, params, ServeConfig(cache_len=32))
    rng = np.random.default_rng(0)
    for rid in (0, 1):
        prompt = rng.integers(0, 128, size=6).astype(np.int32)
        eng.generate(jnp.asarray(prompt[None, :]), max_new_tokens=4,
                     request_ids=[rid])


def scenario_evaluate() -> None:
    """evaluate_perplexity twice on the same model — the per-model CE
    closure must be cached, not re-jitted."""
    from repro.data import CorpusConfig, MarkovCorpus
    from repro.eval import EvalConfig, evaluate_perplexity
    model, params = _tiny_model()
    corpus = MarkovCorpus(CorpusConfig(vocab=128, seed=5))
    ec = EvalConfig(num_batches=2, batch_size=2, seq_len=16, kl_batches=1,
                    budget_batches=1)
    evaluate_perplexity(model, params, corpus, ec)
    evaluate_perplexity(model, params, corpus, ec)


def scenario_prune_unit() -> None:
    """One tiny api.prune pass (the sequential prune_unit driver)."""
    import jax
    from repro import api
    from repro.data import (CalibConfig, CorpusConfig, MarkovCorpus,
                            calibration_batches)
    model, params = _tiny_model()
    corpus = MarkovCorpus(CorpusConfig(vocab=128, seed=5))
    calib = calibration_batches(corpus, CalibConfig(num_sequences=2,
                                                    seq_len=16,
                                                    batch_size=2))
    recipe = api.PruneRecipe(
        method="fista", sparsity="50%",
        solver={"fista_iters": 4, "max_outer": 2, "patience": 1,
                "eps": 1e-3},
        scheduler={"workers": 1})
    api.prune(model, params, calib, recipe)


SCENARIOS: Dict[str, Callable[[], None]] = {
    "batcher": scenario_batcher,
    "engine_generate": scenario_engine_generate,
    "evaluate": scenario_evaluate,
    "prune_unit": scenario_prune_unit,
}


def check_records(records: List[JitRecord],
                  budgets: Optional[Dict[str, int]] = None,
                  scenario: str = "") -> List[Finding]:
    """TRB001/TRB002 over one scenario's recorded jits."""
    budgets = TRACE_BUDGETS if budgets is None else budgets
    findings: List[Finding] = []
    for rec in records:
        declared = [k for k in rec.keys if k in budgets]
        if not declared:
            findings.append(Finding(
                rule="TRB001", path=rec.keys[0].split(":")[0], line=0,
                context=scenario, detail=rec.keys[0],
                message=f"jit {rec.keys[0]} (created at {rec.where}) has "
                        f"no declared trace budget in TRACE_BUDGETS"))
            continue
        budget = max(budgets[k] for k in declared)
        size = rec.cache_size()
        if size > budget:
            findings.append(Finding(
                rule="TRB002", path=declared[0].split(":")[0], line=0,
                context=scenario, detail=declared[0],
                message=f"jit {declared[0]} holds {size} executables "
                        f"after scenario '{scenario}' — budget is "
                        f"{budget} (retrace regression)"))
    return findings


def run_runtime_check(budgets: Optional[Dict[str, int]] = None,
                      scenarios: Optional[Dict[str, Callable[[], None]]]
                      = None) -> List[Finding]:
    """Run every scenario under the recorder and enforce budgets.

    Cache sizes are checked once, AFTER all scenarios have run, so
    budgets bound the *cumulative* trace count a jit accumulates across
    the tier-1 entry points (module-level jits created at first import
    are attributed to the scenario that triggered the import).  Run in a
    fresh process — ``python -m repro.analysis --runtime`` — so the
    recorder sees every module-level ``@jax.jit``."""
    findings: List[Finding] = []
    recorded: List[Tuple[str, List[JitRecord]]] = []
    for name, fn in (scenarios or SCENARIOS).items():
        with record_jits() as records:
            try:
                fn()
            except Exception as e:
                findings.append(Finding(
                    rule="TRB001", path="repro.analysis.trace_budget",
                    line=0, context=name, detail=f"scenario-error:{name}",
                    message=f"runtime scenario '{name}' failed: "
                            f"{type(e).__name__}: {e}"))
                continue
        recorded.append((name, records))
    for name, records in recorded:
        findings += check_records(records, budgets, scenario=name)
    return findings
