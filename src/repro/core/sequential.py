"""Layer-wise pruning with intra-layer cumulative error correction.

This module turns the per-operator pruner (core/pruner.py) into the
paper's full pipeline (Sec. 3.1, Fig. 2):

* each decoder layer is an independent **pruning unit** — its pruned
  stream starts from the DENSE activation at the unit input, which is
  exactly what makes units independent and layer-parallel (Sec. 3.4);
* inside a unit, operators are pruned **sequentially in groups**
  (peers like wq/wk/wv share an input); each group's Gram statistics
  use X (dense-path input) and X* (input produced by the already-pruned
  prefix of the unit), implementing Eq. (2);
* ``error_correction``:
    - "intra" (paper)   : X* relayed within the unit, dense across units
    - "none"  (ablation): X* = X everywhere (Fig. 4a baseline)
    - "full"  (beyond-paper): X* relayed ACROSS units too — potentially
      more accurate, but serializes layers (noted in DESIGN.md)
    - "cross" (beyond-paper): downstream units calibrate from the
      REALIZED pruned activations of upstream units — both X and X*
      start from the pruned relay at each unit input (the LLM-Surgeon
      view: minimize ||Y X~ - W X~|| at the input the pruned net really
      sees), with X* still relayed within the unit.  Serial, like "full".

Which calibration statistics a unit accumulates is driven by the
solver's DECLARED stat dependencies (core/solvers.py ``stat_deps``):
the pruned-path forward runs only when a declared stat needs it, and
novel registered stats are provisioned generically into
``GramStats.extras`` — zero per-solver edits here.

Memory: the relay keeps one unit's activations for the current
calibration set (the group-stats scan stacks the micro-batches of that
unit's captures); Gram statistics are O(n^2) per operator.
"""
from __future__ import annotations

import dataclasses
import functools
import time
import warnings
import weakref
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import gram as gram_lib
from repro.core import solvers as solvers_lib
from repro.core.gram import GramStats
from repro.core.pruner import PrunerConfig
from repro.core.solvers import LayerSolver
from repro.core.sparsity import SparsitySpec
from repro.models.registry import ModelDef
from repro.models.transformer import UnitSpec
from repro.utils import get_logger
from repro.utils.tree import (flatten_with_paths, get_path, set_path,
                              tree_index, tree_stack)

log = get_logger("sequential")


def _record_solve_obs(unit: str, key: str, res: Any, seconds: float) -> None:
    """Prune-side observability (repro.obs): per-operator solver counters,
    iteration/rel-err histograms and — when the solver carried a
    ``trace_len``-bounded convergence history out of its while_loop — one
    series record per operator.  No-op while obs is disabled; everything
    recorded here is already on the host (PruneResult fields)."""
    if not obs.enabled():
        return
    reg = obs.registry()
    reg.counter("prune.operators").inc()
    reg.counter("prune.lambda_bisection_steps").inc(
        int(getattr(res, "outer_iters", 0)))
    reg.histogram("prune.outer_iters", obs.COUNT_BUCKETS).observe(
        getattr(res, "outer_iters", 0))
    reg.histogram("prune.fista_iters", obs.COUNT_BUCKETS).observe(
        getattr(res, "fista_iters", 0))
    reg.histogram("prune.rel_err", obs.FRACTION_BUCKETS).observe(res.rel_error)
    reg.histogram("prune.solve_s", obs.LATENCY_BUCKETS_S).observe(seconds)
    trace = getattr(res, "trace", None)
    if trace is not None:
        reg.series("prune.solver_trace").append({
            "unit": unit, "key": key,
            "rel_error": float(res.rel_error),
            "outer_iters": int(res.outer_iters),
            "e_total": [float(x) for x in trace["e_total"]],
            "lam": [float(x) for x in trace["lam"]]})


@dataclasses.dataclass(frozen=True)
class SequentialConfig:
    spec: SparsitySpec = SparsitySpec(ratio=0.5)
    pruner: PrunerConfig = PrunerConfig()    # legacy fista knobs (see below)
    method: str = "fista"            # registry name (core/solvers.py)
    error_correction: str = "intra"  # intra | none | full | cross
    # canonical solver handle; when None the legacy (method, pruner) pair is
    # resolved through the registry with a DeprecationWarning.  PruneRecipe
    # (repro/api.py) always sets this.
    solver: Optional[LayerSolver] = None
    # MeshExecutor (distributed/executor.py): when set, Gram accumulation
    # goes data-parallel over the calibration micro-batches and solvers
    # that can row-shard do so over "model".  Duck-typed (never imported
    # here) so core keeps zero dependencies on the distribution layer.
    executor: Optional[Any] = None

    def resolve_solver(self) -> LayerSolver:
        if self.solver is not None:
            return self.solver
        warnings.warn(
            "SequentialConfig(method=...) without an explicit solver is "
            "deprecated; build a PruneRecipe (repro.api) or pass "
            "solver=repro.core.solvers.get_solver(name, ...)",
            DeprecationWarning, stacklevel=3)
        return solvers_lib.from_legacy(self.method, self.pruner)

    def with_solver(self) -> "SequentialConfig":
        """Return a config whose ``solver`` field is materialized."""
        if self.solver is not None:
            return self
        return dataclasses.replace(self, solver=self.resolve_solver())


@dataclasses.dataclass
class OperatorReport:
    unit: str
    key: str
    shape: Tuple[int, int]
    error: float
    rel_error: float
    lam: float = 0.0
    outer_iters: int = 0
    fista_iters: int = 0
    seconds: float = 0.0
    solver: str = ""        # "host" | "fused" | "fused-group" | baseline name
    group_size: int = 1     # operators solved in the same batched dispatch


# ---------------------------------------------------------------------------
# capture-key -> param-leaf resolution (handles stacked MoE experts)
# ---------------------------------------------------------------------------
def resolve_param(unit_params: Any, key: str) -> Tuple[str, Optional[int]]:
    """Map a capture key to (param path within the unit, expert index)."""
    if "/expert" in key:
        prefix, rest = key.split("/expert", 1)
        e, op = rest.split("/")
        return f"{prefix}/w_{op}", int(e)
    return key, None


def get_weight(unit_params: Any, key: str) -> jnp.ndarray:
    path, e = resolve_param(unit_params, key)
    w = get_path(unit_params, path)
    return w[e] if e is not None else w


def set_weight(unit_params: Any, key: str, value: jnp.ndarray) -> Any:
    path, e = resolve_param(unit_params, key)
    if e is not None:
        stacked = get_path(unit_params, path)
        return set_path(unit_params, path, stacked.at[e].set(value.astype(stacked.dtype)))
    old = get_path(unit_params, path)
    return set_path(unit_params, path, value.astype(old.dtype))


# ---------------------------------------------------------------------------
# unit pruning
# ---------------------------------------------------------------------------
def _unit_params_of(params: Any, spec: UnitSpec) -> Any:
    node = get_path(params, spec.param_path)
    return tree_index(node, spec.layer_index) if spec.stacked else node


def _write_unit_params(params: Any, spec: UnitSpec, new_unit: Any) -> Any:
    if not spec.stacked:
        return set_path(params, spec.param_path, new_unit)
    stacked = get_path(params, spec.param_path)
    updated = jax.tree_util.tree_map(
        lambda s, n: s.at[spec.layer_index].set(n.astype(s.dtype)), stacked, new_unit)
    return set_path(params, spec.param_path, updated)


_CAPTURE_FWD_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _capture_forward(model: ModelDef, spec: UnitSpec):
    """jitted (unit_params, state) -> (next_state, captures).

    Cached per (model, layer) so repeated prune calls (scheduler retries,
    straggler duplicates, benchmarks) reuse the compiled forward instead of
    re-tracing a fresh closure every time.  Weak-keyed on the ModelDef so a
    discarded model's closures and compiled executables are not pinned."""
    per_model = _CAPTURE_FWD_CACHE.get(model)
    if per_model is None:
        per_model = {}
        _CAPTURE_FWD_CACHE[model] = per_model
    # param_path disambiguates units sharing a layer index (encdec enc/dec)
    cache_key = (spec.param_path, spec.layer_index)
    fwd = per_model.get(cache_key)
    if fwd is None:
        unit_apply, layer_index = model.unit_apply, spec.layer_index

        def fn(unit_params, state):
            cap: Dict[str, jnp.ndarray] = {}
            nxt = unit_apply(unit_params, layer_index, state, cap)
            return nxt, cap

        fwd = jax.jit(fn)
        per_model[cache_key] = fwd
    return fwd


@functools.partial(jax.jit, static_argnames=("unit_apply", "layer_index",
                                             "group_keys", "ec_none",
                                             "extra_specs"))
def _group_stats_scan(init: Dict[str, GramStats], current: Any,
                      ws: Dict[str, jnp.ndarray],
                      dense_caps: Dict[str, jnp.ndarray],
                      pruned_states: Dict[str, jnp.ndarray], *,
                      unit_apply, layer_index: int,
                      group_keys: Tuple[str, ...], ec_none: bool,
                      extra_specs: Tuple[Any, ...] = ()
                      ) -> Dict[str, GramStats]:
    """Accumulate a whole group's GramStats in ONE jitted scan over the
    calibration micro-batches, continuing from ``init``.

    ``dense_caps[key]`` / ``pruned_states`` leaves carry a leading
    micro-batch axis (stacked by the caller).  The pruned-path forward of
    ``current`` and every operator's G/C/H/h update run inside the scan
    body, so there is a single dispatch per same-shape run of batches
    instead of the seed's per-batch x per-key Python loops.  With
    ``ec_none`` the pruned path is skipped entirely (X* = X: the Fig. 4a
    ablation, and every solver whose declared stats are dense-path only).

    ``extra_specs`` (StatSpec tuple, core/solvers.py) are the NOVEL
    declared stats; their ``update`` hooks run in the same scan body and
    their accumulators live in ``GramStats.extras`` — statically keyed,
    so a re-registered hook re-traces instead of reusing a stale cache.
    """

    def body(acc, xs):
        cap_d, ps = xs
        if ec_none:
            cap_p = cap_d
        else:
            cap_p = {}
            unit_apply(current, layer_index, ps, cap_p)
        new = {}
        for key in group_keys:
            xd, xp = cap_d[key], cap_p[key]
            wx = xd @ ws[key]
            st = gram_lib.accumulate(acc[key], xd, xp, wx)
            if extra_specs:
                flat = lambda a: a.reshape(-1, a.shape[-1])
                extras = dict(st.extras)
                for sp in extra_specs:
                    extras[sp.name] = sp.update(extras[sp.name], flat(xd),
                                                flat(xp), flat(wx))
                st = dataclasses.replace(st, extras=extras)
            new[key] = st
        return new, None

    out, _ = jax.lax.scan(body, init, (dense_caps, pruned_states))
    return out


def _shape_buckets(states: Sequence[Dict]) -> List[List[int]]:
    """Partition micro-batch indices into same-shape buckets (a ragged
    final calibration batch must not be stacked with the full ones)."""
    buckets: Dict[Tuple, List[int]] = {}
    for i, s in enumerate(states):
        key = tuple((p, tuple(x.shape)) for p, x in flatten_with_paths(s))
        buckets.setdefault(key, []).append(i)
    return list(buckets.values())


def _shape_subgroups(group: Sequence[str], dense_unit: Any) -> List[List[str]]:
    """Partition a group's keys into maximal same-shape runs (order kept)."""
    by_shape: Dict[Tuple[int, ...], List[str]] = {}
    for key in group:
        shape = tuple(get_weight(dense_unit, key).shape)
        by_shape.setdefault(shape, []).append(key)
    return list(by_shape.values())


def prune_unit(model: ModelDef, spec: UnitSpec, dense_unit: Any,
               dense_states: Sequence[Dict], pruned_states: Sequence[Dict],
               cfg: SequentialConfig
               ) -> Tuple[Any, List[OperatorReport], List[Dict]]:
    """Prune one unit.  Returns (pruned unit params, reports, pruned next
    states) — dense next states are computed by the caller's relay.

    ``dense_states[b]`` / ``pruned_states[b]`` are the unit-input states of
    calibration micro-batch b on the dense / pruned paths.
    """
    cfg = cfg.with_solver()
    solver = cfg.solver
    executor = cfg.executor
    if executor is not None and hasattr(solver, "bind_executor"):
        solver.bind_executor(executor)   # row-sharded solves (rowfista path)
    fwd = _capture_forward(model, spec)
    current = dense_unit  # progressively replaced with pruned weights
    reports: List[OperatorReport] = []
    # dense-path captures don't change while the unit is pruned: one pass
    dense_caps = [fwd(dense_unit, s)[1] for s in dense_states]
    # provision exactly the solver's DECLARED stats (core/solvers.py):
    # the pruned-path forward is skipped in the "none" ablation AND when
    # no declared stat needs the pruned path.  In the latter case the
    # weights are unaffected, but the reported per-operator error becomes
    # the dense-path reconstruction error ||YX - WX|| (the standard metric
    # of the Wanda/SparseGPT literature) instead of the relay error
    # ||YX* - WX|| — cross-solver rel_error comparisons must account for
    # this (benchmarks tag each row with its error_stats mode).
    stat_specs = tuple(solvers_lib.stat_spec(s)
                       for s in solver.stats_required())
    extra_specs = tuple(sp for sp in stat_specs if sp.is_extra)
    ec_none = (cfg.error_correction == "none"
               or not any(sp.needs_pruned_path for sp in stat_specs))
    buckets = _shape_buckets(dense_states)
    # the scan body never reads the pruned states when ec_none —
    # pass cheap placeholders instead of stacking a copy of every state
    pruned_stacked = [jnp.zeros((len(idx),), jnp.float32) if ec_none
                      else tree_stack([dict(pruned_states[i]) for i in idx])
                      for idx in buckets]

    for group in spec.groups:
        # accumulate Gram statistics for every operator of the group in one
        # jitted scan per same-shape run of calibration batches (DESIGN.md §4)
        group_keys = tuple(group)
        ws = {k: get_weight(dense_unit, k) for k in group_keys}
        stats: Dict[str, GramStats] = {
            k: gram_lib.init_stats(
                ws[k].shape[0],
                extras={sp.name: sp.init(ws[k].shape[0])
                        for sp in extra_specs})
            for k in group_keys}
        t_gram = time.perf_counter()
        with obs.span("prune.gram", unit=spec.name, ops=len(group_keys)):
            for idx, pstacked in zip(buckets, pruned_stacked):
                caps_stacked = tree_stack(
                    [{k: dense_caps[i][k] for k in group_keys} for i in idx])
                static_kw = dict(unit_apply=model.unit_apply,
                                 layer_index=spec.layer_index,
                                 group_keys=group_keys, ec_none=ec_none,
                                 extra_specs=extra_specs)
                if executor is not None and executor.can_shard_batches(len(idx)):
                    # data-parallel accumulation: per-shard Gram scan + one
                    # psum over "data" (DESIGN.md §10)
                    stats = executor.sharded_group_stats(
                        _group_stats_scan, stats, current, ws, caps_stacked,
                        pstacked, **static_kw)
                else:
                    stats = _group_stats_scan(stats, current, ws, caps_stacked,
                                              pstacked, **static_kw)
            if obs.enabled():
                # the scan is dispatched asynchronously: while recording,
                # the span and the histogram end at its completion
                jax.block_until_ready(stats)
        if obs.enabled():
            obs.registry().histogram(
                "prune.gram_scan_s", obs.LATENCY_BUCKETS_S).observe(
                time.perf_counter() - t_gram)

        # prune the group's operators against their statistics: same-shape
        # operators are solved in one batched dispatch when the solver can
        for sub in _shape_subgroups(group, dense_unit):
            if solver.supports_group_batch and len(sub) > 1:
                t0 = time.perf_counter()
                with obs.span("prune.solve_group", unit=spec.name,
                              ops=len(sub)):
                    results = solver.solve_group(
                        [jnp.asarray(ws[k], jnp.float32).T for k in sub],
                        [stats[k] for k in sub], cfg.spec)
                per_op = (time.perf_counter() - t0) / len(sub)
                for key, res in zip(sub, results):
                    rep = OperatorReport(
                        spec.name, key, tuple(res.weight.shape), res.error,
                        res.rel_error, res.lam, res.outer_iters,
                        res.fista_iters, per_op, solver.group_label, len(sub))
                    reports.append(rep)
                    current = set_weight(current, key, res.weight.T)
                    _record_solve_obs(spec.name, key, res, per_op)
                continue
            for key in sub:
                w_paper = jnp.asarray(ws[key], jnp.float32).T   # (out, in)
                t0 = time.perf_counter()
                with obs.span("prune.solve", unit=spec.name, op=key):
                    res = solver.solve(w_paper, stats[key], cfg.spec)
                rep = OperatorReport(spec.name, key, tuple(w_paper.shape),
                                     res.error, res.rel_error, res.lam,
                                     res.outer_iters, res.fista_iters,
                                     solver=solver.op_label)
                rep.seconds = time.perf_counter() - t0
                reports.append(rep)
                current = set_weight(current, key, res.weight.T)
                _record_solve_obs(spec.name, key, res, rep.seconds)

    # relay: pruned next states through the fully-pruned unit — only the
    # serial cross-unit modes consume them.  Under "intra"/"none" the
    # caller discards the relay, so skip the capture forwards entirely
    # (on grouped MoE units each one is a per-expert capture loop).
    if cfg.error_correction in ("full", "cross"):
        pruned_next = [fwd(current, s)[0] for s in pruned_states]
    else:
        pruned_next = []
    return current, reports, pruned_next


# ---------------------------------------------------------------------------
# whole-model pruning (the serial reference path; the scheduler distributes)
# ---------------------------------------------------------------------------
def prune_model(model: ModelDef, params: Any, calib_batches: Sequence[Dict],
                cfg: SequentialConfig,
                units: Optional[Sequence[UnitSpec]] = None,
                progress: Optional[Callable[[str], None]] = None
                ) -> Tuple[Any, List[OperatorReport]]:
    """Prune every unit of ``params`` using the calibration batches."""
    cfg = cfg.with_solver()   # resolve the legacy (method, pruner) pair once
    units = list(units if units is not None else model.units())
    dense_states = [model.embed(params, b) for b in calib_batches]
    pruned_states = [dict(s) for s in dense_states]
    new_params = params
    reports: List[OperatorReport] = []

    for spec in units:
        dense_unit = _unit_params_of(params, spec)
        if cfg.error_correction == "full":
            # beyond-paper: X stays dense-relayed, X* relays across units
            unit_in_dense, unit_in_pruned = dense_states, pruned_states
        elif cfg.error_correction == "cross":
            # cross-unit realized calibration: BOTH paths start from the
            # activations the pruned net actually produces at this unit's
            # input (targets become W X~, LLM-Surgeon style); X* still
            # relays within the unit through the pruned prefix
            unit_in_dense = pruned_states
            unit_in_pruned = [dict(s) for s in pruned_states]
        else:  # paper: units are independent — pruned stream restarts at
            unit_in_dense = dense_states                      # the dense input
            unit_in_pruned = [dict(s) for s in dense_states]
        pruned_unit, reps, pruned_next = prune_unit(
            model, spec, dense_unit, unit_in_dense, unit_in_pruned, cfg)
        reports.extend(reps)
        new_params = _write_unit_params(new_params, spec, pruned_unit)
        # advance the dense relay (and post-unit hooks, e.g. whisper enc_norm)
        fwd = _capture_forward(model, spec)
        if cfg.error_correction != "cross":   # cross never reads it again
            dense_states = [fwd(dense_unit, s)[0] for s in dense_states]
            dense_states = [model.post_unit(params, spec.layer_index, s)
                            for s in dense_states]
        if cfg.error_correction in ("full", "cross"):
            pruned_states = [model.post_unit(new_params, spec.layer_index, s)
                             for s in pruned_next]
        if progress is not None:
            err = float(np.mean([r.rel_error for r in reps])) if reps else 0.0
            progress(f"{spec.name}: mean rel err {err:.4f}")
        log.info("unit %s pruned (%d ops)", spec.name, len(reps))

    return new_params, reports


def unit_output_error(model: ModelDef, spec: UnitSpec, dense_unit: Any,
                      pruned_unit: Any, states: Sequence[Dict]) -> float:
    """||unit_pruned(x) - unit_dense(x)||_F / ||unit_dense(x)||_F over batches
    (used by the error-correction ablation, Fig. 4a analog)."""
    fwd = _capture_forward(model, spec)
    num, den = 0.0, 0.0
    for s in states:
        yd = fwd(dense_unit, s)[0]["x"]
        yp = fwd(pruned_unit, s)[0]["x"]
        num += float(jnp.sum((yp.astype(jnp.float32) - yd.astype(jnp.float32)) ** 2))
        den += float(jnp.sum(yd.astype(jnp.float32) ** 2))
    return float(np.sqrt(num / max(den, 1e-30)))
