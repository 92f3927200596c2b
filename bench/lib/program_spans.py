"""The program's own spans in a profiler trace, beside the device's idle gaps.

``repro.obs`` writes every span of the program (``serve.*``, ``prune.*``)
into a running ``jax.profiler`` trace as a ``TraceAnnotation`` in the
``/host:`` plane.  ``trace.reduce_planes`` reads them from the stretch
marked ``bench.window`` and returns ``program_spans`` and
``idle_by_program_span`` (its docstring says what they hold); the
readers of these keys take them from here.
"""
from __future__ import annotations

import sys
from typing import Any, Dict, Optional

from lib import trace

OUTSIDE = trace.PROGRAM_OUTSIDE


def read(ctx: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The traced run's reduction where its trace holds a program span;
    None where the run left no trace, or a trace without them (a
    program older than its annotations)."""
    t = ctx.get("trace")
    return t if t and t.get("program_spans") else None


def stderr_split(title: str, parts: Dict[str, float], per: float) -> None:
    """One line: each part in ms per ``per`` (a tick), largest first."""
    body = ", ".join(f"{n} {1e3 * s / per:.3f}" for n, s in
                     sorted(parts.items(), key=lambda kv: -kv[1]))
    print(f"bench: {title} (ms per tick): {body}", file=sys.stderr,
          flush=True)
