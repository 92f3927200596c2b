"""Observability subsystem (repro.obs): spans, metrics, persistence,
serve/prune instrumentation.

The load-bearing pins: the span ring retains exactly the last
``capacity`` spans with nesting/parenting intact; histogram bucket
edges follow Prometheus upper-edge semantics; spans and metrics
round-trip through JSONL and the Perfetto export is Chrome-trace
loadable; the batcher records SLO metrics under defrag and EOS retire
without changing a single emitted token; and the fused solver's
convergence trace matches the host oracle's.
"""
import json
import os
import subprocess
import sys
import threading

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.obs import metrics as metrics_lib
from repro.obs import report as report_lib
from repro.obs import spans as spans_lib
from repro.configs.opt125m_proxy import tiny_config
from repro.core import gram as gram_lib
from repro.core.pruner import PrunerConfig, prune_operator
from repro.core.sparsity import SparsitySpec
from repro.models.registry import model_def
from repro.serve import BatchConfig, ContinuousBatcher, Request


@pytest.fixture(autouse=True)
def _obs_clean():
    """Global obs state must never leak between tests (or into the rest
    of the suite — batcher/solver tests assume uninstrumented runs)."""
    obs.disable()
    yield
    obs.disable()


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------
class TestSpanRecorder:
    def test_nesting_parent_and_depth(self):
        rec = spans_lib.SpanRecorder(capacity=16)
        with rec.span("outer", unit="u0"):
            with rec.span("inner"):
                pass
            with rec.span("inner"):
                pass
        sps = rec.spans()
        # children finish before the parent, so they precede it in the ring
        assert [s.name for s in sps] == ["inner", "inner", "outer"]
        outer = sps[2]
        assert outer.depth == 0 and outer.parent == -1
        assert outer.attrs == {"unit": "u0"}
        for child in sps[:2]:
            assert child.depth == 1 and child.parent == outer.index
        assert all(s.dur >= 0 for s in sps)

    def test_ring_wraparound_keeps_last_capacity(self):
        rec = spans_lib.SpanRecorder(capacity=4)
        for i in range(8):
            with rec.span(f"s{i}"):
                pass
        assert rec.total == 8
        kept = rec.spans()
        assert [s.name for s in kept] == ["s4", "s5", "s6", "s7"]
        # allocation indices keep climbing across the overwrite
        assert [s.index for s in kept] == [4, 5, 6, 7]

    def test_threads_get_independent_stacks(self):
        rec = spans_lib.SpanRecorder(capacity=32)
        barrier = threading.Barrier(2)

        def work(tag):
            with rec.span("worker", tag=tag):
                barrier.wait()    # both spans live at once...
                with rec.span("step", tag=tag):
                    pass

        threads = [threading.Thread(target=work, args=(t,)) for t in "ab"]
        with rec.span("main"):
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        by_name = {}
        for s in rec.spans():
            by_name.setdefault(s.name, []).append(s)
        # ...yet neither nests under the other: each thread's "step" has
        # its own thread's "worker" as parent, and "worker" is top-level
        assert all(w.depth == 0 for w in by_name["worker"])
        workers = {w.tid: w.index for w in by_name["worker"]}
        for st in by_name["step"]:
            assert st.parent == workers[st.tid] and st.depth == 1
        assert len({s.tid for s in rec.spans()}) == 3

    def test_exception_annotates_and_propagates(self):
        rec = spans_lib.SpanRecorder(capacity=4)
        with pytest.raises(ValueError):
            with rec.span("boom", unit="u1"):
                raise ValueError("nope")
        (sp,) = rec.spans()
        assert sp.attrs == {"unit": "u1", "error": "ValueError"}

    def test_capacity_validation(self):
        with pytest.raises(ValueError, match="capacity"):
            spans_lib.SpanRecorder(capacity=0)


class TestGlobalToggle:
    def test_disabled_span_is_shared_noop(self):
        assert not obs.enabled()
        assert obs.span("x", a=1) is spans_lib.NULL_SPAN
        with obs.span("x"):
            pass
        assert obs.recorder().total == 0

    def test_enable_resets_state(self):
        obs.enable(capacity=8)
        with obs.span("first"):
            pass
        obs.registry().counter("c").inc()
        obs.enable(capacity=8)          # reset=True default
        assert obs.recorder().total == 0
        assert len(obs.registry()) == 0
        obs.registry().counter("c").inc(3)
        obs.enable(capacity=8, reset=False)
        assert obs.registry().counter("c").value == 3

    def test_save_run_dir_empty_returns_none(self, tmp_path):
        obs.enable()
        assert obs.save_run_dir(str(tmp_path)) is None
        assert not os.path.exists(tmp_path / obs.OBS_SUBDIR)

    def test_save_run_dir_writes_all_artifacts(self, tmp_path):
        obs.enable()
        with obs.span("phase", unit="u0"):
            pass
        obs.registry().counter("ops").inc(2)
        out = obs.save_run_dir(str(tmp_path))
        assert out == str(tmp_path / obs.OBS_SUBDIR)
        for fname in ("spans.jsonl", "metrics.jsonl", "trace.json"):
            assert os.path.exists(os.path.join(out, fname)), fname


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------
class TestHistogram:
    def test_upper_edge_bucketing(self):
        h = metrics_lib.Histogram("h", buckets=(1, 2, 4))
        for v in (0.5, 1.0, 1.5, 2.0, 4.0, 5.0):
            h.observe(v)
        # <=1, (1,2], (2,4], >4 — values ON an edge land in that edge
        assert h.counts == [2, 2, 1, 1]
        assert h.total == 6 and h.vmin == 0.5 and h.vmax == 5.0
        assert h.quantile(0.5) == 2.0          # rank 3 of 6 -> edge 2
        assert h.quantile(1.0) == 5.0          # overflow resolves to max

    def test_empty_histogram(self):
        h = metrics_lib.Histogram("h", buckets=(1, 2))
        assert h.mean is None and h.quantile(0.5) is None

    def test_rejects_unsorted_buckets(self):
        with pytest.raises(ValueError, match="ascending"):
            metrics_lib.Histogram("h", buckets=(2, 1))

    def test_registry_kind_conflict(self):
        reg = metrics_lib.MetricsRegistry()
        reg.counter("x")
        with pytest.raises(ValueError, match="already registered"):
            reg.histogram("x")

    def test_get_or_create_is_idempotent(self):
        reg = metrics_lib.MetricsRegistry()
        assert reg.counter("c") is reg.counter("c")
        reg.counter("c").inc(5)
        assert reg.get("c").value == 5
        assert reg.get("missing") is None


class TestRoundTrips:
    def test_spans_jsonl_round_trip(self, tmp_path):
        rec = spans_lib.SpanRecorder(capacity=8)
        with rec.span("a", unit="u0", ops=3):
            with rec.span("b"):
                pass
        path = str(tmp_path / "deep" / "spans.jsonl")
        spans_lib.dump_jsonl(rec.spans(), path)   # makedirs the parent
        assert spans_lib.load_jsonl(path) == rec.spans()

    def test_metrics_jsonl_round_trip(self, tmp_path):
        reg = metrics_lib.MetricsRegistry()
        reg.counter("c").inc(7)
        reg.gauge("g").set(1.5)
        h = reg.histogram("h_s", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(2.0)
        reg.series("s").append({"unit": "u0", "e_total": [1.0, 0.5]})
        path = str(tmp_path / "metrics.jsonl")
        reg.dump_jsonl(path)
        back = metrics_lib.MetricsRegistry.load_jsonl(path)
        assert back.snapshot() == reg.snapshot()
        assert back.get("h_s").quantile(0.5) == 0.1

    def test_perfetto_export_structure(self, tmp_path):
        rec = spans_lib.SpanRecorder(capacity=8)
        with rec.span("prune.unit", unit="u0"):
            with rec.span("prune.solve", op="wq"):
                pass
        path = str(tmp_path / "trace.json")
        spans_lib.export_perfetto(rec.spans(), path, pid=1)
        with open(path) as f:
            doc = json.load(f)
        events = doc["traceEvents"]
        xs = [e for e in events if e["ph"] == "X"]
        metas = [e for e in events if e["ph"] == "M"]
        assert {e["name"] for e in xs} == {"prune.unit", "prune.solve"}
        assert all(e["cat"] == "prune" for e in xs)
        assert metas and metas[0]["name"] == "thread_name"
        # complete events carry microsecond ts/dur and JSON-safe args
        solve = next(e for e in xs if e["name"] == "prune.solve")
        assert solve["dur"] >= 0 and solve["args"] == {"op": "wq"}
        # the nested span is contained within its parent's window
        unit = next(e for e in xs if e["name"] == "prune.unit")
        assert unit["ts"] <= solve["ts"]
        assert solve["ts"] + solve["dur"] <= unit["ts"] + unit["dur"] + 1e-3


# ---------------------------------------------------------------------------
# serve instrumentation
# ---------------------------------------------------------------------------
#: tight pool (forces defrag-relevant churn) shared by the batcher tests
BC = BatchConfig(slots=3, block_size=8, max_blocks_per_request=4,
                 num_blocks=16)


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config().replace(num_layers=2, d_model=64, d_ff=128,
                                num_heads=4, num_kv_heads=4, vocab=128)
    model = model_def(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _requests(vocab, n=5, eos_id=None):
    rng = np.random.default_rng(7)
    spec = [(5, 6), (9, 4), (3, 8), (12, 5), (7, 7)][:n]
    return [Request(id=i, prompt=rng.integers(0, vocab, size=p).astype(np.int32),
                    max_new_tokens=m, eos_id=eos_id)
            for i, (p, m) in enumerate(spec)]


class TestBatcherMetrics:
    def test_slo_metrics_recorded(self, tiny):
        model, params = tiny
        obs.enable()
        batcher = ContinuousBatcher(model, params, BC)
        results = batcher.run(_requests(model.cfg.vocab))
        reg = obs.registry()
        n_tokens = sum(len(r.tokens) for r in results)
        assert reg.get("serve.prefills").value == 5
        assert reg.get("serve.ttft_s").total == 5
        assert reg.get("serve.admission_wait_s").total == 5
        # one ITL sample per gap between received tokens
        assert reg.get("serve.inter_token_s").total == n_tokens - 5
        steps = reg.get("serve.decode_steps").value
        assert reg.get("serve.step_s").total == steps
        assert reg.get("serve.queue_depth").total == steps
        # first token comes from prefill, the rest from decode ticks
        assert reg.get("serve.prefill_tokens").value == \
            sum(len(r.prompt) for r in _requests(model.cfg.vocab))
        assert reg.get("serve.decode_tokens").value == n_tokens - 5
        occ = reg.get("serve.pool_occupancy")
        assert occ.total == steps and 0.0 <= occ.vmax <= 1.0

    def test_defrag_and_eos_paths(self, tiny):
        model, params = tiny
        # pick an EOS the model actually emits so retire-on-EOS fires
        probe = ContinuousBatcher(model, params, BC)
        solo = probe.run(_requests(model.cfg.vocab, n=1))[0].tokens
        eos = int(solo[2])

        obs.enable()
        batcher = ContinuousBatcher(model, params, BC)
        results = batcher.run(_requests(model.cfg.vocab, eos_id=eos))
        batcher.defrag()
        reg = obs.registry()
        assert any(r.reason == "eos" for r in results)
        assert reg.get("serve.defrags").value == 1
        assert reg.get("serve.defrag_blocks_moved").value >= 0
        # one ITL sample per gap: a single-token request records none
        itl = reg.get("serve.inter_token_s")
        assert itl.total == sum(len(r.tokens) - 1 for r in results)

    def test_tokens_bitwise_identical_with_obs(self, tiny):
        """The whole point of the overhead gate: instrumentation must be
        observationally invisible to the decode path."""
        model, params = tiny
        obs.disable()
        bare = ContinuousBatcher(model, params, BC).run(
            _requests(model.cfg.vocab))
        obs.enable()
        instrumented = ContinuousBatcher(model, params, BC).run(
            _requests(model.cfg.vocab))
        for b, i in zip(bare, instrumented):
            np.testing.assert_array_equal(b.tokens, i.tokens)
            assert b.reason == i.reason
        assert obs.registry().get("serve.decode_steps").value > 0


# ---------------------------------------------------------------------------
# solver convergence traces
# ---------------------------------------------------------------------------
class TestSolverTrace:
    def _problem(self, seed=0, n=32, m=24, p=256):
        rng = np.random.default_rng(seed)
        w = rng.normal(size=(m, n)).astype(np.float32)
        x = rng.normal(size=(n, p)).astype(np.float32)
        stats = gram_lib.init_stats(n)
        stats = gram_lib.accumulate(stats, x.T, x.T, (w @ x).T)
        return jnp.asarray(w), stats

    def test_fused_trace_matches_host(self):
        w, stats = self._problem()
        spec = SparsitySpec(ratio=0.5)
        tl = 6
        host = prune_operator(w, stats, spec,
                              PrunerConfig(outer_impl="host", trace_len=tl))
        fused = prune_operator(w, stats, spec,
                               PrunerConfig(outer_impl="fused", trace_len=tl))
        assert host.trace is not None and fused.trace is not None
        n = min(host.outer_iters, tl)
        for key in ("e_total", "lam"):
            assert len(fused.trace[key]) == n
            np.testing.assert_allclose(fused.trace[key], host.trace[key],
                                       rtol=1e-4, atol=1e-6)

    def test_trace_disabled_by_default(self):
        w, stats = self._problem(seed=1)
        res = prune_operator(w, stats, SparsitySpec(ratio=0.5),
                             PrunerConfig(outer_impl="fused"))
        assert res.trace is None

    def test_trace_is_host_numpy(self):
        w, stats = self._problem(seed=2)
        res = prune_operator(w, stats, SparsitySpec(ratio=0.5),
                             PrunerConfig(outer_impl="fused", trace_len=4))
        assert isinstance(res.trace["e_total"], np.ndarray)
        assert res.trace["e_total"].dtype == np.float32


# ---------------------------------------------------------------------------
# report CLI
# ---------------------------------------------------------------------------
class TestReport:
    def _fake_run(self, tmp_path):
        obs.enable()
        with obs.span("prune.unit", unit="u0"):
            pass
        reg = obs.registry()
        reg.histogram("prune.solve_s").observe(0.2)
        reg.histogram("prune.outer_iters", obs.COUNT_BUCKETS).observe(12)
        reg.counter("prune.operators").inc(4)
        obs.save_run_dir(str(tmp_path))
        with open(tmp_path / "run_summary.json", "w") as f:
            json.dump({"total_solver_seconds": 1.5,
                       "attempts_histogram": {"1": 2},
                       "slowest_unit": {"unit": "u0", "seconds": 1.0},
                       "completed": 2, "resumed": 0, "duplicated": []}, f)
        return str(tmp_path)

    def test_summarize_and_render(self, tmp_path):
        run = self._fake_run(tmp_path)
        summary = report_lib.summarize_run(run)
        assert summary["num_spans"] == 1
        assert summary["spans"]["prune.unit"]["count"] == 1
        assert summary["metrics"]["prune.operators"]["value"] == 4
        text = report_lib.render_text(summary)
        assert "total solver seconds: 1.50" in text
        assert "slowest unit: u0" in text
        # count histograms render as plain numbers, latency ones as time
        assert "prune.outer_iters" in text and "12s" not in text
        assert "200.0ms" in text

    def test_render_empty_dir(self, tmp_path):
        text = report_lib.render_text(
            report_lib.summarize_run(str(tmp_path)))
        assert "no observability artifacts" in text

    def test_cli_report_subprocess(self, tmp_path):
        run = self._fake_run(tmp_path)
        env = dict(os.environ, PYTHONPATH="src")
        proc = subprocess.run(
            [sys.executable, "-m", "repro.obs", "report", run],
            capture_output=True, text=True, env=env, cwd="/root/repo")
        assert proc.returncode == 0, proc.stderr
        assert "prune.unit" in proc.stdout
