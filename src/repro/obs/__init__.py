"""repro.obs — unified tracing + metrics (DESIGN.md §14).

One process-global :class:`~repro.obs.spans.SpanRecorder` and one
:class:`~repro.obs.metrics.MetricsRegistry`, toggled by
:func:`enable`/:func:`disable`.  Instrumentation sites follow two rules:

* **spans** go through :func:`span` (or :func:`step_span` for one
  iteration of a loop) — it returns a shared no-op context manager
  while disabled and no profiler trace runs, so span sites cost one
  function call and one check;
* **metrics** in hot loops fetch their instruments ONCE at construction
  behind an ``enabled()`` check (see ``serve/batcher.py``) so the
  per-tick cost is a guarded attribute access + a bisect, never a
  registry lookup; the registry itself is reached via :func:`registry`.

Spans reach the profiler too: while a ``jax.profiler`` trace runs,
each span enters a ``jax.profiler.TraceAnnotation`` of its name, with
its attributes, whether or not recording is on.  So every span of the
program (``prune.*``, ``mesh.*``, ``serve.*``) lies in the trace's
``/host:`` plane beside the device's events, with no :func:`enable`.

Recording never touches device values before they are already on the
host: solver convergence traces come out of the fused while_loops as
device arrays and are transferred once post-solve (the JAX003 rule and
its OBS001 sibling keep this honest).

``save_run_dir(run_dir)`` persists everything next to the checkpoint
store's artifacts: ``<run_dir>/obs/spans.jsonl``, ``metrics.jsonl`` and
a Perfetto-loadable ``trace.json``.  ``python -m repro.obs report`` (see
``report.py``) renders a saved run.
"""
from __future__ import annotations

import os
from typing import Optional

from repro.obs import metrics as metrics_lib
from repro.obs import spans as spans_lib
from repro.obs.metrics import (COUNT_BUCKETS, FRACTION_BUCKETS,
                               LATENCY_BUCKETS_S, MetricsRegistry)
from repro.obs.spans import NULL_SPAN, Span, SpanRecorder, TraceAnnotation

__all__ = ["enable", "disable", "enabled", "span", "step_span", "registry",
           "recorder",
           "save_run_dir", "MetricsRegistry", "SpanRecorder", "Span",
           "LATENCY_BUCKETS_S", "COUNT_BUCKETS", "FRACTION_BUCKETS",
           "OBS_SUBDIR"]

#: subdirectory of a run dir holding the persisted obs artifacts
OBS_SUBDIR = "obs"

_enabled = False
_recorder = SpanRecorder()
_registry = MetricsRegistry()


def enable(capacity: int = 4096, reset: bool = True) -> None:
    """Turn recording on.  ``reset`` (default) starts from a fresh
    recorder/registry so back-to-back runs don't bleed into each other
    (benchmarks interleave instrumented and bare runs)."""
    global _enabled, _recorder, _registry
    if reset or _recorder.capacity != capacity:
        _recorder = SpanRecorder(capacity)
        _registry = MetricsRegistry()
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def enabled() -> bool:
    return _enabled


def span(name: str, **attrs):
    """A context manager timing ``name``: a ring span while recording,
    and a profiler annotation while a trace runs; else a no-op."""
    if _enabled:
        return _recorder.span(name, **attrs)
    if TraceAnnotation.is_enabled():
        return TraceAnnotation(name, **attrs)
    return NULL_SPAN


def step_span(name: str, step: int, **attrs):
    """:func:`span` for iteration ``step`` of a loop: in a profiler trace
    a ``StepTraceAnnotation``, in the ring a plain span."""
    if _enabled:
        return _recorder.step_span(name, step, **attrs)
    if TraceAnnotation.is_enabled():
        return spans_lib.annotation(name, attrs, step)
    return NULL_SPAN


# named `registry` (not `metrics`) so the accessor never shadows the
# `repro.obs.metrics` submodule attribute on the package
def registry() -> MetricsRegistry:
    return _registry


def recorder() -> SpanRecorder:
    return _recorder


def save_run_dir(run_dir: str, subdir: str = OBS_SUBDIR) -> Optional[str]:
    """Persist spans + metrics + Perfetto trace under ``run_dir/obs/``.
    Returns the obs directory, or None when nothing was recorded."""
    if _recorder.total == 0 and len(_registry) == 0:
        return None
    out = os.path.join(run_dir, subdir)
    os.makedirs(out, exist_ok=True)
    sps = _recorder.spans()
    spans_lib.dump_jsonl(sps, os.path.join(out, "spans.jsonl"))
    _registry.dump_jsonl(os.path.join(out, "metrics.jsonl"))
    spans_lib.export_perfetto(sps, os.path.join(out, "trace.json"))
    return out
