"""repro.obs spans on the profiler's clock, and the serving scheduler's
phases as spans and counters.

The pins: with a ``jax.profiler`` trace running and recording off, a
short ``ContinuousBatcher.run`` leaves every scheduler span in the
trace's ``/host:`` plane, the tick's three phases nested in
``serve.tick``; with both off a span is the shared no-op; the profiler
changes no token; the phase counters are disjoint shares of the run's
wall; first tokens and gaps are observed from the host-receipt stamps
``RequestResult.recv_times``; the step programs keep the names the
benchmark's trace readers look for; and ``prune.gram`` waits for its
scan only while recording.
"""
import glob
import re
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.configs.opt125m_proxy import tiny_config
from repro.core import gram as gram_lib
from repro.core.pruner import PrunerConfig
from repro.core.sequential import SequentialConfig, prune_model
from repro.core.sparsity import SparsitySpec
from repro.data import (CalibConfig, CorpusConfig, MarkovCorpus,
                        calibration_batches)
from repro.models.registry import model_def
from repro.obs.spans import NULL_SPAN
from repro.serve import BatchConfig, ContinuousBatcher, Request

#: chunked prefill, so that every scheduler span has a site to fire at
CHUNKED = BatchConfig(slots=3, block_size=8, max_blocks_per_request=4,
                      num_blocks=16, prefill_chunk=8)
EAGER = BatchConfig(slots=3, block_size=8, max_blocks_per_request=4,
                    num_blocks=16)

TICK_PHASES = ("serve.tick.prepare", "serve.tick.wait", "serve.tick.emit")
SCHEDULER_SPANS = ("serve.tick",) + TICK_PHASES + (
    "serve.admit", "serve.sample_first", "serve.prefill_chunk",
    "serve.await_arrival")
PHASE_COUNTERS = ("admit_s", "sample_first_s", "prefill_dispatch_s",
                  "tick_prepare_s", "tick_wait_s", "tick_emit_s")


@pytest.fixture(autouse=True)
def _obs_clean():
    obs.disable()
    yield
    obs.disable()


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config().replace(num_layers=2, d_model=64, d_ff=128,
                                num_heads=4, num_kv_heads=4, vocab=128)
    model = model_def(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _requests(vocab, arrival=0.0, first=0):
    """Prompts of one to two chunks; with ``arrival`` > 0 the run starts
    by waiting for them."""
    rng = np.random.default_rng(11)
    spec = [(5, 6), (12, 4), (3, 7), (10, 5)]
    return [Request(id=first + i,
                    prompt=rng.integers(0, vocab, p).astype(np.int32),
                    max_new_tokens=m, arrival=arrival)
            for i, (p, m) in enumerate(spec)]


def _host_events(logdir):
    """(name, start_ns, end_ns, line) of every event in the /host: plane."""
    from jax.profiler import ProfileData
    path, = glob.glob(f"{logdir}/plugins/profile/*/*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                         line.name) for ev in line.events]
    return out


def _traced_run(tiny, tmp_path, cfg=CHUNKED):
    """Warm-up run, then a second run under a profiler trace."""
    model, params = tiny
    b = ContinuousBatcher(model, params, cfg)
    b.run(_requests(model.cfg.vocab))
    logdir = str(tmp_path / "trace")
    jax.profiler.start_trace(logdir)
    try:
        results = b.run(_requests(model.cfg.vocab, arrival=0.05, first=100))
    finally:
        jax.profiler.stop_trace()
    return results, _host_events(logdir)


class TestSpansInProfilerTrace:
    def test_scheduler_spans_land_in_host_plane(self, tiny, tmp_path):
        assert not obs.enabled()
        _, events = _traced_run(tiny, tmp_path)
        names = {e[0] for e in events}
        for name in SCHEDULER_SPANS:
            assert name in names, name
        ticks = [e for e in events if e[0] == "serve.tick"]
        for phase in TICK_PHASES:
            evs = [e for e in events if e[0] == phase]
            assert len(evs) == len(ticks)
            for _, s, t, line in evs:
                assert any(ts <= s and t <= te and tl == line
                           for _, ts, te, tl in ticks), phase
        # the phases of one tick come in order and do not overlap
        for _, ts, te, line in ticks:
            inner = sorted((e for e in events if e[0] in TICK_PHASES
                            and e[3] == line and ts <= e[1] and e[2] <= te),
                           key=lambda e: e[1])
            assert [e[0] for e in inner] == list(TICK_PHASES)
            assert all(a[2] <= b[1] for a, b in zip(inner, inner[1:]))

    def test_recorded_spans_also_annotate(self, tiny, tmp_path):
        """Recording on and a trace running: the span lands in both."""
        obs.enable()
        _, events = _traced_run(tiny, tmp_path)
        names = {e[0] for e in events}
        assert set(SCHEDULER_SPANS) <= names
        ring = {s.name for s in obs.recorder().spans()}
        assert set(SCHEDULER_SPANS) <= ring

    def test_off_means_null_span(self):
        assert not obs.enabled()
        assert not jax.profiler.TraceAnnotation.is_enabled()
        assert obs.span("serve.tick.wait", req=3) is NULL_SPAN
        assert obs.step_span("serve.tick", 7) is NULL_SPAN

    def test_tokens_identical_with_profiler_on(self, tiny, tmp_path):
        model, params = tiny
        bare = ContinuousBatcher(model, params, CHUNKED).run(
            _requests(model.cfg.vocab, arrival=0.05, first=100))
        traced, _ = _traced_run(tiny, tmp_path)
        traced = [r for r in traced if r.id >= 100]   # not the warm-up's
        assert [r.id for r in bare] == [r.id for r in traced]
        for b, t in zip(bare, traced):
            np.testing.assert_array_equal(b.tokens, t.tokens)
            assert b.reason == t.reason

    def test_prune_spans_land_in_host_plane(self, tmp_path):
        model, params, calib = _tiny_prune()
        logdir = str(tmp_path / "trace")
        jax.profiler.start_trace(logdir)
        try:
            prune_model(model, params, calib, PRUNE_CFG,
                        units=model.units()[:1])
        finally:
            jax.profiler.stop_trace()
        names = {e[0] for e in _host_events(logdir)}
        assert "prune.gram" in names
        assert names & {"prune.solve", "prune.solve_group"}


class TestPhaseCounters:
    @pytest.mark.parametrize("cfg", [CHUNKED, EAGER], ids=["chunked", "eager"])
    def test_counters_are_disjoint_shares_of_the_wall(self, tiny, cfg):
        model, params = tiny
        b = ContinuousBatcher(model, params, cfg)
        t0 = time.perf_counter()
        b.run(_requests(model.cfg.vocab))
        wall = time.perf_counter() - t0
        for k in PHASE_COUNTERS:
            assert isinstance(b.stats[k], float) and b.stats[k] >= 0.0, k
        assert b.stats["tick_wait_s"] > 0.0
        assert b.stats["sample_first_s"] > 0.0
        assert 0.0 < b.stats["sample_first_wait_s"] <= \
            b.stats["sample_first_s"]
        assert sum(b.stats[k] for k in PHASE_COUNTERS) <= wall
        # every stats entry but the per-tick walls is a number, so a
        # window over the run can subtract one snapshot from another
        assert all(isinstance(v, (int, float)) for k, v in b.stats.items()
                   if k != "step_walls")

    def test_step_programs_keep_their_names(self, tiny):
        """The benchmark finds the step programs in a trace by name."""
        model, params = tiny
        b = ContinuousBatcher(model, params, CHUNKED)
        step = b._step_fn.lower(
            b._exec_params, b.pool_state, jnp.asarray(b._tables),
            jnp.asarray(b._pos), jnp.asarray(b._token),
            jnp.asarray(b._req_ids), jnp.asarray(b._tok_idx),
            jnp.asarray(b._active), jnp.asarray(b._temps))
        assert re.search(r"module @jit_step\b", step.as_text())
        chunk = b._chunk_fn.lower(
            b._exec_params, b.pool_state, jnp.asarray(b._tables[0]),
            jnp.zeros((1, CHUNKED.prefill_chunk), jnp.int32), jnp.int32(0),
            jnp.int32(CHUNKED.prefill_chunk))
        assert re.search(r"module @jit_chunk_step\b", chunk.as_text())


class TestReceiptStamps:
    @pytest.mark.parametrize("cfg", [CHUNKED, EAGER], ids=["chunked", "eager"])
    def test_ttft_and_itl_read_receipt_stamps(self, tiny, cfg):
        model, params = tiny
        obs.enable()
        results = ContinuousBatcher(model, params, cfg).run(
            _requests(model.cfg.vocab))
        for r in results:
            assert len(r.recv_times) == len(r.tokens)
            assert np.all(np.diff(r.recv_times) >= 0)
            # a token is received after the tick that makes it starts
            assert np.all(r.recv_times >= r.token_times)
            assert r.recv_times[0] >= r.arrival
        reg = obs.registry()
        ttft = reg.get("serve.ttft_s")
        assert ttft.total == len(results)
        assert ttft.sum == pytest.approx(
            sum(r.recv_times[0] - r.arrival for r in results))
        itl = reg.get("serve.inter_token_s")
        assert itl.total == sum(len(r.tokens) - 1 for r in results)
        assert itl.sum == pytest.approx(
            sum(float(np.diff(r.recv_times).sum()) for r in results))
        assert reg.get("serve.active_slots") is None
        assert reg.get("serve.prefill_pending_tokens") is None


# ---------------------------------------------------------------------------
# prune.gram ends at the scan's completion while recording
# ---------------------------------------------------------------------------
PRUNE_CFG = SequentialConfig(
    spec=SparsitySpec(kind="nm", n=2, m=4),
    pruner=PrunerConfig(fista_iters=4, max_outer=2, patience=1, eps=1e-4),
    method="fista")


def _tiny_prune():
    cfg = tiny_config().replace(num_layers=2, d_model=64, d_ff=128,
                                num_heads=4, num_kv_heads=4, vocab=128)
    model = model_def(cfg)
    params = model.init(jax.random.PRNGKey(0))
    corpus = MarkovCorpus(CorpusConfig(vocab=cfg.vocab, seed=5))
    calib = calibration_batches(corpus, CalibConfig(num_sequences=8,
                                                    seq_len=32, batch_size=4))
    return model, params, calib


@pytest.mark.parametrize("recording", [False, True], ids=["off", "on"])
def test_gram_scan_synced_only_while_recording(monkeypatch, recording):
    model, params, calib = _tiny_prune()
    synced = []
    real = jax.block_until_ready

    def spy(x):
        if isinstance(x, dict) and x and all(
                isinstance(v, gram_lib.GramStats) for v in x.values()):
            synced.append(sorted(x))
        return real(x)
    monkeypatch.setattr(jax, "block_until_ready", spy)
    if recording:
        obs.enable()
    prune_model(model, params, calib, PRUNE_CFG, units=model.units()[:1])
    groups = model.units()[0].groups
    if recording:
        assert synced == [sorted(g) for g in groups]
        assert obs.registry().get("prune.gram_scan_s").total == len(groups)
    else:
        assert synced == []
