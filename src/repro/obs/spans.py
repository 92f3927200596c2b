"""Nestable wall-clock spans in a fixed-capacity ring buffer.

A :class:`SpanRecorder` hands out context managers::

    with recorder.span("prune.unit", unit="layer_3"):
        ...

Each finished span is one immutable :class:`Span` appended to a ring of
``capacity`` entries (old spans are overwritten, the total count keeps
climbing), so a long serve run records the *recent* timeline at a bounded
memory cost.  Nesting is tracked per thread — the scheduler's worker
threads each get their own stack, and their spans land on separate
Perfetto tracks via ``tid``.

While a ``jax.profiler`` trace runs, every span also enters a
``jax.profiler.TraceAnnotation`` of the same name (attributes as its
arguments), so it lands in the trace's ``/host:`` plane on the clock
the device events use; :meth:`SpanRecorder.step_span` enters a
``StepTraceAnnotation`` instead, the profiler's marker of one loop
iteration.  An annotation entered while no trace runs records nothing,
so without a trace none is entered.

Overhead budget: a span costs two ``time.perf_counter()`` calls, one
lock-guarded id allocation, one lock-guarded ring write, one small
object and a check whether a trace runs — single-digit microseconds,
against serve decode steps of hundreds of microseconds (gated ≤2% in
benchmarks/serve_bench.py).  The process-global recorder in
``repro.obs`` additionally returns a shared no-op context manager when
observability is disabled and no trace runs, so uninstrumented runs
pay only a function call per span site.

Persistence: ``dump_jsonl`` writes one JSON object per span;
``export_perfetto`` emits the Chrome trace-event format
(``{"traceEvents": [{"ph": "X", ...}]}``, timestamps in microseconds)
that chrome://tracing and https://ui.perfetto.dev load directly.
"""
from __future__ import annotations

import dataclasses
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

from jax.profiler import StepTraceAnnotation, TraceAnnotation


@dataclasses.dataclass(frozen=True)
class Span:
    """One finished span.  ``t0`` is seconds from the recorder's epoch
    (NOT unix time — see ``SpanRecorder.epoch_unix``)."""

    index: int              # allocation order, unique within a recorder
    parent: int             # enclosing span's index, -1 at top level
    name: str               # dotted, e.g. "prune.unit"
    t0: float               # start, seconds from recorder epoch
    dur: float              # wall seconds
    tid: int                # thread ident of the recording thread
    depth: int              # nesting depth within its thread (0 = top)
    attrs: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "Span":
        return cls(index=int(d["index"]), parent=int(d["parent"]),
                   name=str(d["name"]), t0=float(d["t0"]),
                   dur=float(d["dur"]), tid=int(d["tid"]),
                   depth=int(d["depth"]), attrs=dict(d.get("attrs") or {}))


class _NullSpan:
    """Shared no-op context manager returned when obs is disabled."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


NULL_SPAN = _NullSpan()


def annotation(name: str, attrs: Dict[str, Any], step: Optional[int] = None):
    """The profiler annotation of a span: ``StepTraceAnnotation`` when it
    marks loop iteration ``step``, else ``TraceAnnotation``."""
    if step is not None:
        return StepTraceAnnotation(name, step_num=step, **attrs)
    return TraceAnnotation(name, **attrs)


class _ActiveSpan:
    """Context manager for one live span (see :meth:`SpanRecorder.span`)."""

    __slots__ = ("_rec", "name", "attrs", "_step", "_ann", "_index",
                 "_parent", "_depth", "_t0")

    def __init__(self, rec: "SpanRecorder", name: str,
                 attrs: Dict[str, Any], step: Optional[int] = None) -> None:
        self._rec = rec
        self.name = name
        self.attrs = attrs
        self._step = step
        self._ann = None

    def __enter__(self) -> "_ActiveSpan":
        rec = self._rec
        with rec._lock:
            self._index = rec._next_index
            rec._next_index += 1
        stack = rec._stack()
        self._parent = stack[-1] if stack else -1
        self._depth = len(stack)
        stack.append(self._index)
        if TraceAnnotation.is_enabled():
            self._ann = annotation(self.name, self.attrs, self._step)
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, *exc) -> bool:
        dur = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(exc_type, *exc)
            self._ann = None
        rec = self._rec
        rec._stack().pop()
        attrs = self.attrs
        if exc_type is not None:
            attrs = dict(attrs, error=exc_type.__name__)
        rec._record(Span(
            index=self._index, parent=self._parent, name=self.name,
            t0=self._t0 - rec.epoch, dur=dur,
            tid=threading.get_ident(), depth=self._depth, attrs=attrs))
        return False


class SpanRecorder:
    """Fixed-capacity ring of finished spans; thread-safe."""

    def __init__(self, capacity: int = 4096) -> None:
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self._ring: List[Optional[Span]] = [None] * capacity
        self._count = 0           # total spans ever recorded
        self._next_index = 0
        self._lock = threading.Lock()
        self._tls = threading.local()
        self.epoch = time.perf_counter()
        self.epoch_unix = time.time()   # for correlating with log lines

    def _stack(self) -> List[int]:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def span(self, name: str, **attrs: Any) -> _ActiveSpan:
        return _ActiveSpan(self, name, attrs)

    def step_span(self, name: str, step: int, **attrs: Any) -> _ActiveSpan:
        """A span that marks loop iteration ``step`` in a profiler trace
        (``StepTraceAnnotation``); in the ring it is a plain span."""
        return _ActiveSpan(self, name, attrs, step)

    def _record(self, span: Span) -> None:
        with self._lock:
            self._ring[self._count % self.capacity] = span
            self._count += 1

    @property
    def total(self) -> int:
        """Spans recorded over the recorder's lifetime (>= len(spans()))."""
        return self._count

    def spans(self) -> List[Span]:
        """The retained spans, oldest first (last ``capacity`` recorded)."""
        with self._lock:
            n = min(self._count, self.capacity)
            start = self._count - n
            return [self._ring[(start + i) % self.capacity]  # type: ignore
                    for i in range(n)]

    def clear(self) -> None:
        with self._lock:
            self._ring = [None] * self.capacity
            self._count = 0

    def dump_jsonl(self, path: str) -> None:
        dump_jsonl(self.spans(), path)


# ---------------------------------------------------------------------------
# persistence / export
# ---------------------------------------------------------------------------
def dump_jsonl(spans: List[Span], path: str) -> None:
    """One JSON object per line; round-trips through :func:`load_jsonl`."""
    parent = os.path.dirname(path)
    if parent:
        os.makedirs(parent, exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        for sp in spans:
            f.write(json.dumps(sp.to_dict(), default=str) + "\n")


def load_jsonl(path: str) -> List[Span]:
    out: List[Span] = []
    with open(path, "r", encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(Span.from_dict(json.loads(line)))
    return out


def perfetto_events(spans: List[Span],
                    pid: Optional[int] = None) -> List[Dict[str, Any]]:
    """Chrome trace-event list: one complete ("X") event per span plus
    thread_name metadata.  Thread idents are compacted to small track
    ids so the Perfetto timeline stays readable."""
    pid = os.getpid() if pid is None else pid
    tids: Dict[int, int] = {}
    events: List[Dict[str, Any]] = []
    for sp in spans:
        tid = tids.setdefault(sp.tid, len(tids))
        events.append({
            "ph": "X", "name": sp.name,
            "cat": sp.name.split(".", 1)[0],
            "ts": sp.t0 * 1e6, "dur": sp.dur * 1e6,
            "pid": pid, "tid": tid,
            "args": {k: (v if isinstance(v, (int, float, bool, str)
                              or v is None) else str(v))
                     for k, v in sp.attrs.items()},
        })
    for ident, tid in tids.items():
        events.append({"ph": "M", "name": "thread_name", "pid": pid,
                       "tid": tid, "args": {"name": f"thread-{ident}"}})
    return events


def export_perfetto(spans: List[Span], path: str,
                    pid: Optional[int] = None) -> None:
    parent = os.path.dirname(path)
    if parent:
        os.makedirs(parent, exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        json.dump({"traceEvents": perfetto_events(spans, pid),
                   "displayTimeUnit": "ms"}, f)
