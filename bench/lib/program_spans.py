"""The program's own spans in a profiler trace, beside the device's idle gaps.

``repro.obs`` writes every span of the program (``serve.*``, ``prune.*``)
into a running ``jax.profiler`` trace as a ``TraceAnnotation`` in the
``/host:`` plane.  ``reduce_planes`` reads those spans from the same
trace that ``trace.reduce_planes`` reduces, inside the stretch marked
``bench.window``, and returns:

* ``program_spans``: span name -> ``{"seconds", "count"}``, the spans'
  time clipped to the stretch and how many of them it holds;
* ``idle_by_program_span``: span name -> idle seconds on the device,
  each gap put down to the innermost program span open at its middle
  (``OUTSIDE`` when none is), averaged over the chips as ``trace``'s
  ``idle_gaps`` are.

A program that writes no spans (one older than its annotations) leaves
both empty, and the readers of these keys then report nothing.
"""
from __future__ import annotations

import collections
import glob
import os
import re
import sys
from typing import Any, Dict, List, Optional, Tuple

from lib import harness, trace

PREFIXES = ("serve.", "prune.")
OUTSIDE = "outside the program's spans"
#: where ``bench/run.py`` has the profiler write a traced run's trace
TRACE_DIR = os.path.join(harness.ROOT, ".bench_trace")

Span = Tuple[str, float, float]


def _innermost(spans: List[Span], points: List[float]) -> List[Optional[str]]:
    """For each of ``points`` (ascending), the shortest span with
    start <= t <= end, or None."""
    spans = sorted(spans, key=lambda h: h[1])
    out: List[Optional[str]] = []
    active: List[Span] = []
    i = 0
    for t in points:
        while i < len(spans) and spans[i][1] <= t:
            active.append(spans[i])
            i += 1
        active = [h for h in active if h[2] >= t]
        out.append(min(active, key=lambda h: h[2] - h[1])[0]
                   if active else None)
    return out


def reduce_planes(planes, chips: int = 1) -> Optional[Dict[str, Any]]:
    """The two keys above, from the planes of one ``ProfileData``; None
    without a ``bench.window`` span or a device plane."""
    planes = list(planes)
    window: List[Span] = []
    spans: List[Span] = []
    for p in planes:
        if p.name.startswith("/host:"):
            for line in p.lines:
                for e in trace._events(line):
                    if e[0] == "bench.window":
                        window.append(e)
                    elif e[0].startswith(PREFIXES):
                        spans.append(e)
    devs = sorted((p for p in planes if p.name.startswith("/device:TPU:")
                   and re.fullmatch(r"/device:TPU:\d+", p.name)),
                  key=lambda p: int(p.name.rsplit(":", 1)[1]))[:chips]
    if not window or not devs:
        return None
    w0, w1 = window[0][1], window[0][2]
    inside = [(n, max(s, w0), min(e, w1)) for n, s, e in spans
              if e > w0 and s < w1]
    seconds, count = collections.Counter(), collections.Counter()
    for n, s, e in inside:
        seconds[n] += (e - s) * 1e-9
        count[n] += 1
    idle = collections.Counter()
    for p in devs:
        lines = {ln.name: ln for ln in p.lines}
        ops = [(max(s, w0), min(e, w1)) for _, s, e in
               (trace._events(lines["XLA Ops"]) if "XLA Ops" in lines else [])
               if e > w0 and s < w1]
        busy = trace._union(ops)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        names = _innermost(inside, [0.5 * (a + b) for a, b in gaps])
        for (a, b), n in zip(gaps, names):
            idle[n or OUTSIDE] += b - a
    ns = 1e-9 / len(devs)
    return {"program_spans": {n: {"seconds": seconds[n], "count": count[n]}
                              for n in sorted(seconds)},
            "idle_by_program_span": {n: t * ns
                                     for n, t in idle.most_common()}}


def read(ctx: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The reduction of the traced run's trace, once per run: None when
    the run left no trace, or its trace holds no program span."""
    if "program_spans" not in ctx:
        paths = sorted(glob.glob(os.path.join(
            TRACE_DIR, "plugins", "profile", "*", "*.xplane.pb")))
        out = None
        if paths:
            from jax.profiler import ProfileData
            out = reduce_planes(ProfileData.from_file(paths[-1]).planes)
        ctx["program_spans"] = out if out and out["program_spans"] else None
    return ctx["program_spans"]


def stderr_split(title: str, parts: Dict[str, float], per: float) -> None:
    """One line: each part in ms per ``per`` (a tick), largest first."""
    body = ", ".join(f"{n} {1e3 * s / per:.3f}" for n, s in
                     sorted(parts.items(), key=lambda kv: -kv[1]))
    print(f"bench: {title} (ms per tick): {body}", file=sys.stderr,
          flush=True)
