"""Profiler trace of a steady stretch of the window, reduced to numbers.

``Tracer`` starts and stops ``jax.profiler`` around the stretch and
marks it with the harness's own span ``bench.window``; around its calls
into the program it adds spans ``bench.<step>`` so that idle gaps on
the device can be put down to what the host was doing.  ``reduce``
reads the ``.xplane.pb`` with nothing but JAX and returns:

* ``window_s``, ``busy_s``: the stretch's length, and the union of the
  intervals in which an operation ran on the device (averaged over the
  chips), clipped to the stretch;
* per program (a jitted function, by the name JAX gives its module,
  ``jit_<fn>`` without the prefix): device time and executions;
* per program, the device time of each kernel inside it;
* ``breakdown``: the ten device operations that took most time, and
  idle time by the harness span that was open in the middle of each gap;
* the program's own spans (``repro.obs`` writes each ``serve.*`` and
  ``prune.*`` span into a running trace as a ``TraceAnnotation``):
  ``program_spans``, span name -> ``{"seconds", "count"}`` clipped to
  the stretch, and ``idle_by_program_span``, idle seconds on the device
  by the innermost program span open at each gap's middle
  (``PROGRAM_OUTSIDE`` where none is), averaged over the chips as the
  idle gaps are.  A program that writes no spans leaves both empty.

A kernel is found by its HLO instruction's name.  ``KERNELS`` are the
ones every reduction knows; a reader under ``bench/metrics/`` adds its
own by a module constant ``KERNELS = ("<name>", ...)``.
"""
from __future__ import annotations

import ast
import bisect
import collections
import glob
import os
import re
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

#: kernels of the system under test: a Pallas call's HLO instruction
#: takes the name of the jitted function that wraps it (``%spmm24.41``)
KERNELS = ("spmm24", "fused_mlp24", "paged_decode_attn", "flash_attention")
#: the per-layer readers, each of which may declare kernels of its own
METRICS_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")
#: spans of the program (``repro.obs``), by prefix
PROGRAM_PREFIXES = ("serve.", "prune.")
PROGRAM_OUTSIDE = "outside the program's spans"

MODULE_RE = re.compile(r"^(?:jit_)?(?P<name>.*?)(?:\(\d+\))?$")
#: an op event's name is its HLO text; the name proper comes first
OP_RE = re.compile(r"^%?(?P<name>[\w.\-]+) = ")
#: ops that hold other ops (a scan's loop), left out of the op ranking
CONTAINER_RE = re.compile(r"\) (?:while|conditional|call)\(")


class Tracer:
    def __init__(self, logdir: str):
        self.logdir = logdir
        self.state = "idle"
        self._window = None

    def start(self) -> None:
        import jax
        if self.state != "idle":
            return
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.logdir, profiler_options=opts)
        self._window = jax.profiler.TraceAnnotation("bench.window")
        self._window.__enter__()
        self.state = "on"

    def stop(self) -> None:
        import jax
        if self.state != "on":
            return
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state = "done"

    @staticmethod
    def span(name: str, fn: Callable) -> Callable:
        import jax

        def wrapped(*a, **k):
            with jax.profiler.TraceAnnotation(name):
                return fn(*a, **k)
        return wrapped

    def at(self, elapsed: float, seconds: float) -> None:
        """Called at each tick of a window of ``seconds``, ``elapsed``
        into it: the trace covers from 30% into the window for 40% of
        it, at most 10 s."""
        start_at = 0.3 * seconds
        if elapsed >= start_at + min(0.4 * seconds, 10.0):
            self.stop()
        elif elapsed >= start_at:
            self.start()

    def wrap_serve(self, batcher: Any) -> None:
        """Spans around the batcher's scheduler steps."""
        batcher._tick = self.span("bench.decode_tick", batcher._tick)
        batcher._admit = self.span("bench.admit", batcher._admit)
        batcher._prefill_tick = self.span("bench.prefill_chunk",
                                          batcher._prefill_tick)
        batcher._sample_first = self.span("bench.sample_first",
                                          batcher._sample_first)

    def wrap_prune(self) -> None:
        """Spans around the prune's relay, Gram scans and solves."""
        from repro.core import driver, sequential, solvers
        driver._dense_unit_inputs = self.span("bench.relay",
                                              driver._dense_unit_inputs)
        sequential._group_stats_scan = self.span(
            "bench.gram", sequential._group_stats_scan)
        for name in ("solve", "solve_group"):
            setattr(solvers.FistaSolver, name,
                    self.span("bench.solve", getattr(solvers.FistaSolver,
                                                     name)))

    def reduce(self, chips: int = 1) -> Optional[Dict[str, Any]]:
        paths = sorted(glob.glob(os.path.join(
            self.logdir, "plugins", "profile", "*", "*.xplane.pb")))
        if not paths:
            return None
        from jax.profiler import ProfileData
        return reduce_planes(ProfileData.from_file(paths[-1]).planes, chips)


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _events(line) -> List[Tuple[str, float, float]]:
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for ev in line.events]


def reader_kernels() -> Tuple[str, ...]:
    """Kernel names the readers in ``METRICS_DIR`` declare as
    ``KERNELS = (...)``, read from the files, not run."""
    names: List[str] = []
    for path in sorted(glob.glob(os.path.join(METRICS_DIR, "*.py"))):
        with open(path, encoding="utf-8") as f:
            body = ast.parse(f.read(), path).body
        for node in body:
            if isinstance(node, ast.Assign) and any(
                    isinstance(t, ast.Name) and t.id == "KERNELS"
                    for t in node.targets):
                names += ast.literal_eval(node.value)
    return tuple(names)


def kernel_patterns() -> Dict[str, "re.Pattern"]:
    """``KERNELS`` and the readers' kernels, each to the pattern of its
    op's HLO text."""
    return {k: re.compile(rf"^%?{re.escape(k)}(?:\.\d+)? = ")
            for k in dict.fromkeys(KERNELS + reader_kernels())}


def _kernel(op: str, kernels) -> Optional[str]:
    return next((k for k, rx in kernels.items() if rx.search(op)), None)


def _short(op: str, kernels) -> str:
    """An op's HLO name, with the kernel it calls where it calls one."""
    m = OP_RE.match(op)
    name = m.group("name") if m else op[:64]
    kern = _kernel(op, kernels)
    return f"{name} ({kern})" if kern else name


def _label(spans, starts, t: float, look: int = 64) -> str:
    """Innermost harness span open at time ``t`` (spans sorted by start;
    the open ones are among the last few that start before ``t``)."""
    i = bisect.bisect_right(starts, t)
    open_ = [h for h in spans[max(0, i - look):i] if h[2] >= t]
    if not open_:
        return "outside the harness's spans"
    return min(open_, key=lambda h: h[2] - h[1])[0]


def _innermost(spans: List[Tuple[str, float, float]],
               points: List[float]) -> List[Optional[str]]:
    """For each of ``points`` (ascending), the shortest span with
    start <= t <= end, or None."""
    spans = sorted(spans, key=lambda h: h[1])
    out: List[Optional[str]] = []
    active: List[Tuple[str, float, float]] = []
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
    """The numbers above, from the planes of one ``ProfileData``."""
    planes = list(planes)
    kernels = kernel_patterns()
    host_spans: List[Tuple[str, float, float]] = []
    own_spans: List[Tuple[str, float, float]] = []
    for p in planes:
        if p.name.startswith("/host:"):
            for line in p.lines:
                for e in _events(line):
                    if e[0].startswith("bench."):
                        host_spans.append(e)
                    elif e[0].startswith(PROGRAM_PREFIXES):
                        own_spans.append(e)
    win = [e for e in host_spans if e[0] == "bench.window"]
    devs = sorted((p for p in planes if p.name.startswith("/device:TPU:")
                   and re.fullmatch(r"/device:TPU:\d+", p.name)),
                  key=lambda p: int(p.name.rsplit(":", 1)[1]))[:chips]
    if not win or not devs:
        return None
    w0, w1 = win[0][1], win[0][2]
    spans = sorted((h for h in host_spans if h[0] != "bench.window"),
                   key=lambda h: h[1])
    span_starts = [h[1] for h in spans]
    own = [(n, max(s, w0), min(e, w1)) for n, s, e in own_spans
           if e > w0 and s < w1]
    own_s, own_n = collections.Counter(), collections.Counter()
    for n, s, e in own:
        own_s[n] += (e - s) * 1e-9
        own_n[n] += 1
    busy_total, ops_t = 0.0, collections.Counter()
    prog_t, prog_n = collections.Counter(), collections.Counter()
    kern_t: Dict[str, collections.Counter] = collections.defaultdict(
        collections.Counter)
    gaps, own_idle = collections.Counter(), collections.Counter()
    for p in devs:
        lines = {ln.name: _events(ln) for ln in p.lines}
        ops = [(n, max(s, w0), min(e, w1)) for n, s, e in
               lines.get("XLA Ops", []) if e > w0 and s < w1]
        mods = [(MODULE_RE.match(n).group("name"), max(s, w0), min(e, w1))
                for n, s, e in lines.get("XLA Modules", [])
                if e > w0 and s < w1]
        busy = _union([(s, e) for _, s, e in ops])
        busy_total += sum(e - s for s, e in busy)
        for n, s, e in ops:
            if not CONTAINER_RE.search(n):
                ops_t[_short(n, kernels)] += e - s
        for n, s, e in mods:
            prog_t[n] += e - s
            prog_n[n] += 1
        # kernels by the program they ran in (ops lie inside their module)
        mods.sort(key=lambda m: m[1])
        starts = [m[1] for m in mods]
        for n, s, e in ops:
            kern = _kernel(n, kernels)
            if kern is None:
                continue
            i = bisect.bisect_right(starts, s) - 1
            owner = mods[i][0] if i >= 0 and mods[i][2] >= e else "?"
            kern_t[owner][kern] += e - s
        # idle gaps, by the innermost harness span and program span open
        # at their middle
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        idle = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        for a, b in idle:
            gaps[_label(spans, span_starts, 0.5 * (a + b))] += b - a
        names = _innermost(own, [0.5 * (a + b) for a, b in idle])
        for (a, b), n in zip(idle, names):
            own_idle[n or PROGRAM_OUTSIDE] += b - a
    k = len(devs)
    ns = 1e-9 / k
    prog_s = {n: t * ns for n, t in prog_t.items()}
    prog_c = {n: c / k for n, c in prog_n.items()}

    def program_time(*names):
        ts = [prog_s[n] for n in names if n in prog_s]
        return sum(ts) if ts else None

    def program_count(name):
        return prog_c.get(name, 0)

    def kernel_time(program, *kernels):
        c = kern_t.get(program, {})
        return sum(c.get(kk, 0.0) for kk in kernels) * ns or None

    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_total * ns,
        "programs": prog_s, "program_counts": prog_c,
        "kernels": {p: {kk: v * ns for kk, v in c.items()}
                    for p, c in kern_t.items()},
        "program_time": program_time, "program_count": program_count,
        "kernel_time": kernel_time,
        "program_spans": {n: {"seconds": own_s[n], "count": own_n[n]}
                          for n in sorted(own_s)},
        "idle_by_program_span": {n: t * ns
                                 for n, t in own_idle.most_common()},
        "breakdown": {
            "device_ops": [[n, t * ns] for n, t in ops_t.most_common(10)],
            "idle_gaps": [[n, t * ns] for n, t in gaps.most_common(10)]},
    }
