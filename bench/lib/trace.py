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
  idle time by the harness span that was open in the middle of each gap.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

#: kernels of the system under test: a Pallas call's HLO instruction
#: takes the name of the jitted function that wraps it (``%spmm24.41``)
KERNELS = {k: re.compile(rf"^%?{k}(?:\.\d+)? = ") for k in
           ("spmm24", "fused_mlp24", "paged_decode_attn", "flash_attention")}

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


def _short(op: str) -> str:
    """An op's HLO name, with the kernel it calls where it calls one."""
    m = OP_RE.match(op)
    name = m.group("name") if m else op[:64]
    kern = next((k for k, rx in KERNELS.items() if rx.search(op)), None)
    return f"{name} ({kern})" if kern else name


def _label(spans, starts, t: float, look: int = 64) -> str:
    """Innermost harness span open at time ``t`` (spans sorted by start;
    the open ones are among the last few that start before ``t``)."""
    i = bisect.bisect_right(starts, t)
    open_ = [h for h in spans[max(0, i - look):i] if h[2] >= t]
    if not open_:
        return "outside the harness's spans"
    return min(open_, key=lambda h: h[2] - h[1])[0]


def reduce_planes(planes, chips: int = 1) -> Optional[Dict[str, Any]]:
    """The numbers above, from the planes of one ``ProfileData``."""
    planes = list(planes)
    host_spans: List[Tuple[str, float, float]] = []
    for p in planes:
        if p.name.startswith("/host:"):
            for line in p.lines:
                host_spans += [e for e in _events(line)
                               if e[0].startswith("bench.")]
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
    busy_total, ops_t = 0.0, collections.Counter()
    prog_t, prog_n = collections.Counter(), collections.Counter()
    kern_t: Dict[str, collections.Counter] = collections.defaultdict(
        collections.Counter)
    gaps = collections.Counter()
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
                ops_t[_short(n)] += e - s
        for n, s, e in mods:
            prog_t[n] += e - s
            prog_n[n] += 1
        # kernels by the program they ran in (ops lie inside their module)
        mods.sort(key=lambda m: m[1])
        starts = [m[1] for m in mods]
        for n, s, e in ops:
            kern = next((k for k, rx in KERNELS.items() if rx.search(n)),
                        None)
            if kern is None:
                continue
            i = bisect.bisect_right(starts, s) - 1
            owner = mods[i][0] if i >= 0 and mods[i][2] >= e else "?"
            kern_t[owner][kern] += e - s
        # idle gaps, by the innermost harness span open at their middle
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps[_label(spans, span_starts, 0.5 * (a + b))] += b - a
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
        "breakdown": {
            "device_ops": [[n, t * ns] for n, t in ops_t.most_common(10)],
            "idle_gaps": [[n, t * ns] for n, t in gaps.most_common(10)]},
    }
