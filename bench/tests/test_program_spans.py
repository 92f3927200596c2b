"""CPU tests of the readers of the program's own spans and phase counters.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They cover ``lib/program_spans.reduce_planes`` on synthetic planes and
on the trace recorded on the chip (which holds no program span), and
the readers ``serve.host_ms_per_tick`` and
``serve.idle_in_host_ms_per_tick`` on run records with and without what
they read.
"""
from __future__ import annotations

import gzip
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

from lib import harness, program_spans, trace  # noqa: E402

TESTDATA = os.path.join(BENCH, "testdata")


class _Ev:
    def __init__(self, name, start, dur):
        self.name, self.start_ns, self.duration_ns = name, start, dur


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, [_Ev(*e) for e in events]


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, [_Line(*ln) for ln in lines]


def _planes():
    """Window [100, 1100); a tick [100, 700) holding prepare [100, 300),
    wait [300, 500) and emit [500, 700); an admit [750, 850); the bench's
    own wrapper around the tick, which a program span never is."""
    host = _Plane("/host:CPU", [("python", [
        ("bench.window", 100, 1000), ("bench.decode_tick", 100, 600),
        ("serve.tick", 100, 600), ("serve.tick.prepare", 100, 200),
        ("serve.tick.wait", 300, 200), ("serve.tick.emit", 500, 200),
        ("serve.admit", 750, 100), ("prune.gram", 2000, 50)])])
    dev = _Plane("/device:TPU:0", [
        ("XLA Modules", [("jit_step(1)", 250, 300)]),
        ("XLA Ops", [("%fusion.1 = f32[8] fusion(x)", 250, 300),
                     ("%dot.4 = f32[8] dot(a, b)", 1000, 200)])])
    return [host, dev]


def test_idle_by_program_span_by_hand():
    """Each idle gap goes to the innermost program span open at its
    middle; the bench's own spans are not program spans."""
    t = program_spans.reduce_planes(_planes())
    # busy [250, 550) and [1000, 1100); gaps [100, 250) middle 175: the
    # prepare; [550, 1000) middle 775: the admit
    assert t["idle_by_program_span"] == pytest.approx(
        {"serve.tick.prepare": 150e-9, "serve.admit": 450e-9})
    spans = t["program_spans"]
    assert set(spans) == {"serve.tick", "serve.tick.prepare",
                          "serve.tick.wait", "serve.tick.emit", "serve.admit"}
    assert spans["serve.tick"] == {"seconds": pytest.approx(600e-9),
                                   "count": 1}
    # the same gaps, and the same idle total, as the harness's reduction
    base = trace.reduce_planes(_planes())
    assert sum(t["idle_by_program_span"].values()) == pytest.approx(
        base["window_s"] - base["busy_s"])


def test_idle_outside_program_spans():
    host = _Plane("/host:CPU", [("python", [
        ("bench.window", 0, 100), ("serve.tick", 0, 30)])])
    dev = _Plane("/device:TPU:0", [("XLA Ops", [("%a = f32[] add(x)",
                                                 40, 20)])])
    t = program_spans.reduce_planes([host, dev])
    assert t["idle_by_program_span"] == pytest.approx(
        {"serve.tick": 40e-9, program_spans.OUTSIDE: 40e-9})


def test_innermost_span_at_points():
    spans = [("a", 0, 10), ("b", 2, 4), ("c", 6, 8), ("d", 12, 20)]
    assert program_spans._innermost(spans, [1, 3, 5, 7, 11, 15, 25]) == \
        ["a", "b", "a", "c", None, "d", None]


def test_recorded_chip_trace_has_no_program_spans():
    """The recorded trace predates the annotations: both keys are empty,
    and the idle reader reports nothing on it."""
    from jax.profiler import ProfileData
    with gzip.open(os.path.join(TESTDATA, "serve_packed.xplane.pb.gz")) as f:
        planes = list(ProfileData.from_serialized_xspace(f.read()).planes)
    t = program_spans.reduce_planes(planes)
    assert t["program_spans"] == {}
    base = trace.reduce_planes(planes)
    assert t["idle_by_program_span"] == pytest.approx(
        {program_spans.OUTSIDE: base["window_s"] - base["busy_s"]})
    ctx = {"trace": base, "program_spans": None}
    reader = harness.metric_reader("serve.idle_in_host_ms_per_tick")
    assert reader.read(ctx) is None


def test_idle_reader_per_decode_step(capsys):
    ctx = {"trace": {"program_count": lambda name: 2 if name == "step"
                     else 0},
           "program_spans": program_spans.reduce_planes(_planes())}
    reader = harness.metric_reader("serve.idle_in_host_ms_per_tick")
    # prepare 150 ns and admit 450 ns of idle, over 2 steps
    assert reader.read(ctx) == pytest.approx(1e3 * 600e-9 / 2)
    assert "serve.admit" in capsys.readouterr().err


def test_idle_reader_without_a_trace_on_disk(tmp_path, monkeypatch):
    monkeypatch.setattr(program_spans, "TRACE_DIR", str(tmp_path))
    ctx = {"trace": {"program_count": lambda name: 5}}
    reader = harness.metric_reader("serve.idle_in_host_ms_per_tick")
    assert reader.read(ctx) is None


STATS = {"steps": 10, "admit_s": 0.01, "sample_first_s": 0.07,
         "sample_first_wait_s": 0.05, "prefill_dispatch_s": 0.03, "tick_prepare_s": 0.04,
         "tick_wait_s": 1.5, "tick_emit_s": 0.05}


def test_host_reader_sums_the_host_phases(capsys):
    """The waits are left out, and so is what the wall holds beyond the
    counters (the harness's wrappers, the profiler's start and stop)."""
    reader = harness.metric_reader("serve.host_ms_per_tick")
    ctx = {"run": {"stats": STATS, "wall": 2.0}}
    assert reader.read(ctx) == pytest.approx(1e3 * 0.15 / 10)
    err = capsys.readouterr().err
    assert "tick_emit_s 5.000" in err
    assert "sample_first_s 2.000" in err
    assert "rest of the window's wall 30.000" in err


@pytest.mark.parametrize("run", [
    {"stats": {"steps": 10, "active_slot_steps": 80}, "wall": 2.0},
    {"stats": dict(STATS, steps=0), "wall": 2.0}],
    ids=["no phase counters", "no steps"])
def test_host_reader_reports_nothing_without_counters(run):
    reader = harness.metric_reader("serve.host_ms_per_tick")
    assert reader.read({"run": run}) is None
