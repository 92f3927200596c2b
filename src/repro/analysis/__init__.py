"""repro.analysis — the repo's own static-analysis pass (DESIGN.md §12).

Four rule families, each encoding a bug class this reproduction has
actually shipped and reverted:

* ``rules_jax``    JAX001-JAX004: traced-value branching, PRNG key
                   reuse, hot-path host syncs, undeclared jit caches.
* ``rules_pallas`` PAL001-PAL004: BlockSpec index-map bounds, VMEM
                   budgets, tile alignment, oracle + dispatch gates.
* ``rules_mesh``   MESH001-MESH002: explicit shard_map check_vma,
                   replicate-before-sample domination.
* ``rules_obs``    OBS001: obs recording calls inside jitted function
                   bodies or hot-path loop bodies.
* ``trace_budget`` TRB001-TRB002: runtime jit trace budgets over the
                   tier-1 entry points (``--runtime``).

Run ``python -m repro.analysis src/`` (see README).
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional

from .core import (Finding, ModuleCtx, apply_baseline, iter_py_files,
                   load_baseline)

__all__ = ["Finding", "ModuleCtx", "apply_baseline", "iter_py_files",
           "load_baseline", "run_source_rules"]


def run_source_rules(paths: Iterable[str],
                     hot: Optional[Iterable[str]] = None,
                     budgets: Optional[Dict[str, int]] = None
                     ) -> List[Finding]:
    """AST rule families (JAX + MESH + OBS) over every .py under
    ``paths``."""
    from . import rules_jax, rules_mesh, rules_obs
    findings: List[Finding] = []
    for path in iter_py_files(paths):
        try:
            ctx = ModuleCtx.parse(path)
        except (SyntaxError, UnicodeDecodeError) as e:
            findings.append(Finding(
                rule="JAX000", path=path, line=getattr(e, "lineno", 0) or 0,
                context="", detail="parse-error",
                message=f"could not parse: {e}"))
            continue
        findings += rules_jax.check_module(ctx, hot=hot, budgets=budgets)
        findings += rules_mesh.check_module(ctx)
        findings += rules_obs.check_module(ctx, hot=hot)
    return findings
