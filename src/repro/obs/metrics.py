"""Counters, gauges, fixed-bucket histograms and series — pure Python.

Instruments are deliberately numpy-free: the serve hot loop records a
handful of floats per decode tick, and a ``bisect`` into a small tuple
of bucket edges plus two additions is cheaper than any array round-trip
(the obs-overhead gate in benchmarks/serve_bench.py holds instrumented
step latency within 2% of bare).

Histogram semantics are Prometheus-style upper edges: a histogram with
``buckets=(1, 2, 4)`` has four counts — values ``<= 1``, ``(1, 2]``,
``(2, 4]`` and the overflow ``> 4``.  ``bisect_left`` places a value
exactly on an edge into that edge's bucket.

``Series`` is the odd one out: an append-only list of small records for
data that isn't scalar — per-operator solver convergence traces
(``e_total``/``lam`` per outer iteration, bounded by
``PrunerConfig.trace_len``) ride in one series record per operator.

The registry's ``dump_jsonl``/``load_jsonl`` round-trip one JSON object
per metric, tagged with ``kind``.
"""
from __future__ import annotations

import json
import math
import os
import threading
from bisect import bisect_left
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

#: default edges for wall-time observations, seconds (100µs .. 10s)
LATENCY_BUCKETS_S: Tuple[float, ...] = (
    1e-4, 2.5e-4, 5e-4, 1e-3, 2.5e-3, 5e-3, 1e-2, 2.5e-2, 5e-2,
    0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0)

#: default edges for small nonnegative counts (queue depth, iterations)
COUNT_BUCKETS: Tuple[float, ...] = (0, 1, 2, 4, 8, 16, 32, 64, 128, 256)

#: default edges for ratios in [0, 1] (pool occupancy, error shares)
FRACTION_BUCKETS: Tuple[float, ...] = (
    0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8, 0.9, 0.95, 1.0)


class Counter:
    kind = "counter"
    __slots__ = ("name", "value")

    def __init__(self, name: str) -> None:
        self.name = name
        self.value = 0

    def inc(self, n: int = 1) -> None:
        self.value += n

    def to_dict(self) -> Dict[str, Any]:
        return {"kind": self.kind, "name": self.name, "value": self.value}

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "Counter":
        c = cls(d["name"])
        c.value = d["value"]
        return c


class Gauge:
    """Last-write-wins scalar; tracks min/max over its lifetime."""

    kind = "gauge"
    __slots__ = ("name", "value", "vmin", "vmax", "n")

    def __init__(self, name: str) -> None:
        self.name = name
        self.value = 0.0
        self.vmin = math.inf
        self.vmax = -math.inf
        self.n = 0

    def set(self, v: float) -> None:
        self.value = v
        if v < self.vmin:
            self.vmin = v
        if v > self.vmax:
            self.vmax = v
        self.n += 1

    def to_dict(self) -> Dict[str, Any]:
        return {"kind": self.kind, "name": self.name, "value": self.value,
                "min": None if self.n == 0 else self.vmin,
                "max": None if self.n == 0 else self.vmax, "n": self.n}

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "Gauge":
        g = cls(d["name"])
        g.value, g.n = d["value"], d.get("n", 0)
        if g.n:
            g.vmin, g.vmax = d["min"], d["max"]
        return g


class Histogram:
    """Fixed ascending upper-edge buckets + one overflow bucket."""

    kind = "histogram"
    __slots__ = ("name", "buckets", "counts", "total", "sum", "vmin", "vmax")

    def __init__(self, name: str,
                 buckets: Sequence[float] = LATENCY_BUCKETS_S) -> None:
        edges = tuple(float(b) for b in buckets)
        if not edges or any(a >= b for a, b in zip(edges, edges[1:])):
            raise ValueError(
                f"histogram {name!r}: buckets must be strictly ascending "
                f"upper edges, got {buckets!r}")
        self.name = name
        self.buckets = edges
        self.counts = [0] * (len(edges) + 1)
        self.total = 0
        self.sum = 0.0
        self.vmin = math.inf
        self.vmax = -math.inf

    def observe(self, v: float) -> None:
        v = float(v)
        self.counts[bisect_left(self.buckets, v)] += 1
        self.total += 1
        self.sum += v
        if v < self.vmin:
            self.vmin = v
        if v > self.vmax:
            self.vmax = v

    def observe_many(self, values: Sequence[float]) -> None:
        """:meth:`observe` each of ``values``: one recording call for a
        batch buffered on the host (a decode tick's per-slot gaps)."""
        for v in values:
            self.observe(v)

    @property
    def mean(self) -> Optional[float]:
        return self.sum / self.total if self.total else None

    def quantile(self, q: float) -> Optional[float]:
        """Upper-edge estimate of the q-quantile (the smallest bucket edge
        covering rank ceil(q * total); overflow resolves to the observed
        max).  Coarse by construction — SLO checks against fixed edges,
        not exact order statistics."""
        if self.total == 0:
            return None
        need = max(1, math.ceil(q * self.total))
        cum = 0
        for i, c in enumerate(self.counts):
            cum += c
            if cum >= need:
                return self.buckets[i] if i < len(self.buckets) else self.vmax
        return self.vmax

    def to_dict(self) -> Dict[str, Any]:
        return {"kind": self.kind, "name": self.name,
                "buckets": list(self.buckets), "counts": list(self.counts),
                "total": self.total, "sum": self.sum,
                "min": None if self.total == 0 else self.vmin,
                "max": None if self.total == 0 else self.vmax}

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "Histogram":
        h = cls(d["name"], d["buckets"])
        h.counts = [int(c) for c in d["counts"]]
        h.total, h.sum = d["total"], d["sum"]
        if h.total:
            h.vmin, h.vmax = d["min"], d["max"]
        return h


class Series:
    """Append-only list of small JSON-able records (non-scalar data,
    e.g. per-operator solver convergence traces)."""

    kind = "series"
    __slots__ = ("name", "records")

    def __init__(self, name: str) -> None:
        self.name = name
        self.records: List[Dict[str, Any]] = []

    def append(self, record: Dict[str, Any]) -> None:
        self.records.append(record)

    def __len__(self) -> int:
        return len(self.records)

    def __iter__(self) -> Iterator[Dict[str, Any]]:
        return iter(self.records)

    def to_dict(self) -> Dict[str, Any]:
        return {"kind": self.kind, "name": self.name, "records": self.records}

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "Series":
        s = cls(d["name"])
        s.records = list(d["records"])
        return s


_KINDS = {cls.kind: cls for cls in (Counter, Gauge, Histogram, Series)}


class MetricsRegistry:
    """Get-or-create instrument registry; creation is lock-guarded, the
    instruments themselves are single-writer by convention (the batcher
    loop and each scheduler worker record into distinct instruments or
    tolerate the GIL-level interleaving of int/float adds)."""

    def __init__(self) -> None:
        self._metrics: Dict[str, Any] = {}
        self._lock = threading.Lock()

    def _get(self, name: str, cls, *args):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = cls(name, *args)
            elif not isinstance(m, cls):
                raise ValueError(f"metric {name!r} already registered as "
                                 f"{m.kind}, requested {cls.kind}")
            return m

    def counter(self, name: str) -> Counter:
        return self._get(name, Counter)

    def gauge(self, name: str) -> Gauge:
        return self._get(name, Gauge)

    def histogram(self, name: str,
                  buckets: Sequence[float] = LATENCY_BUCKETS_S) -> Histogram:
        return self._get(name, Histogram, buckets)

    def series(self, name: str) -> Series:
        return self._get(name, Series)

    def get(self, name: str) -> Optional[Any]:
        return self._metrics.get(name)

    def snapshot(self) -> Dict[str, Dict[str, Any]]:
        with self._lock:
            return {name: m.to_dict()
                    for name, m in sorted(self._metrics.items())}

    def clear(self) -> None:
        with self._lock:
            self._metrics.clear()

    def __len__(self) -> int:
        return len(self._metrics)

    def dump_jsonl(self, path: str) -> None:
        parent = os.path.dirname(path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        with open(path, "w", encoding="utf-8") as f:
            for payload in self.snapshot().values():
                f.write(json.dumps(payload, default=float) + "\n")

    @classmethod
    def load_jsonl(cls, path: str) -> "MetricsRegistry":
        reg = cls()
        with open(path, "r", encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                d = json.loads(line)
                reg._metrics[d["name"]] = _KINDS[d["kind"]].from_dict(d)
        return reg
