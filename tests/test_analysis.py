"""The static-analysis pass (repro.analysis, DESIGN.md §12).

Every rule family is pinned from both sides against the fixture corpus
in tests/analysis_fixtures/: the bad snippet must produce the finding
(true positive) AND the good twin must not (true negative) — no rule
lands without both.  The seeded-regression cases from the issue — an
out-of-bounds BlockSpec index map, sampling without replicate_logits,
a jit exceeding its trace budget — live here too, plus the dogfood
anchor: the merged tree itself is clean modulo the committed baseline,
and the trace-budget gates on Engine.generate / evaluate_perplexity
generalizing the batcher's ``_cache_size() == 1`` pin.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import core as acore
from repro.analysis import (rules_jax, rules_mesh, rules_obs, rules_pallas,
                            trace_budget)

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
FIXTURES = os.path.join(HERE, "analysis_fixtures")
HOT = ("tests.analysis_fixtures",)


def parse(name):
    return acore.ModuleCtx.parse(os.path.join(FIXTURES, name), root=ROOT)


def rules_of(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# JAX family
# ---------------------------------------------------------------------------
class TestJAX001TracedBranching:
    def test_bad_flags_if_and_while(self):
        found = rules_jax.check_traced_branching(parse("jax001_bad.py"))
        assert rules_of(found) == ["JAX001"]
        contexts = {f.context for f in found}
        assert "branch_on_tracer" in contexts
        assert "loop_on_tracer" in contexts

    def test_good_is_clean(self):
        assert rules_jax.check_traced_branching(parse("jax001_good.py")) == []


class TestJAX002KeyReuse:
    def test_bad_flags_reuse_and_unfolded_loop(self):
        found = rules_jax.check_key_reuse(parse("jax002_bad.py"))
        details = {f.detail for f in found}
        assert "reuse:key" in details
        assert "loop:key" in details

    def test_good_is_clean(self):
        assert rules_jax.check_key_reuse(parse("jax002_good.py")) == []


class TestJAX003HostSync:
    def test_bad_flags_per_iteration_syncs(self):
        found = rules_jax.check_host_syncs(parse("jax003_bad.py"), hot=HOT)
        assert len(found) == 2          # np.asarray + float, both in-loop
        assert rules_of(found) == ["JAX003"]

    def test_good_is_clean(self):
        assert rules_jax.check_host_syncs(parse("jax003_good.py"),
                                          hot=HOT) == []

    def test_out_of_hot_scope_is_ignored(self):
        assert rules_jax.check_host_syncs(parse("jax003_bad.py"),
                                          hot=("repro.serve.",)) == []


class TestJAX004DeclaredJits:
    def test_undeclared_site_flagged_declared_passes(self):
        ctx = parse("jax004_undeclared.py")
        budgets = {
            "tests.analysis_fixtures.jax004_undeclared:declared_fn": 1}
        found = rules_jax.check_jit_declared(ctx, budgets=budgets)
        assert [f.rule for f in found] == ["JAX004"]
        assert found[0].detail.endswith(":undeclared_fn")

    def test_all_declared_is_clean(self):
        ctx = parse("jax004_undeclared.py")
        budgets = {
            "tests.analysis_fixtures.jax004_undeclared:declared_fn": 1,
            "tests.analysis_fixtures.jax004_undeclared:undeclared_fn": 1}
        assert rules_jax.check_jit_declared(ctx, budgets=budgets) == []


class TestOBS001RecordingPlacement:
    def test_bad_flags_jit_and_loop_recordings(self):
        found = rules_obs.check_module(parse("obs001_bad.py"), hot=HOT)
        assert rules_of(found) == ["OBS001"]
        details = sorted(f.detail for f in found)
        # one recording traced into a jitted body...
        assert [d for d in details if d.startswith("jit:")] == \
            ["jit:m.observe(1.0)"]
        # ...and three per-iteration recordings in the hot loop: a bound
        # counter, a span-per-token, and a chained constructor record
        loops = [d for d in details if d.startswith("loop:")]
        assert len(loops) == 3
        assert "loop:self._m_tok.inc()" in loops
        assert any("obs.span" in d for d in loops)
        assert any("reg.histogram" in d for d in loops)

    def test_good_is_clean(self):
        assert rules_obs.check_module(parse("obs001_good.py"), hot=HOT) == []

    def test_loop_check_scoped_to_hot_paths(self):
        # outside the hot-path prefixes only the jit check applies
        found = rules_obs.check_module(parse("obs001_bad.py"),
                                       hot=("repro.serve.",))
        assert [f.detail for f in found] == ["jit:m.observe(1.0)"]

    def test_module_without_obs_imports_skipped(self):
        # recording-shaped calls don't fire without a repro.obs import
        assert rules_obs.check_module(parse("jax003_bad.py"), hot=HOT) == []


# ---------------------------------------------------------------------------
# MESH family
# ---------------------------------------------------------------------------
class TestMESH001CheckRep:
    def test_implicit_check_rep_flagged(self):
        found = rules_mesh.check_shard_map_check_vma(parse("mesh001_bad.py"))
        assert rules_of(found) == ["MESH001"]

    def test_explicit_check_rep_clean(self):
        assert rules_mesh.check_shard_map_check_vma(
            parse("mesh001_good.py")) == []


class TestMESH002ReplicateBeforeSample:
    def test_unreplicated_sampling_flagged(self):
        found = rules_mesh.check_sampling_replicated(parse("mesh002_bad.py"))
        assert rules_of(found) == ["MESH002"]
        assert {f.context for f in found} == {"bad_categorical",
                                              "bad_sample"}

    def test_replicated_sampling_clean(self):
        assert rules_mesh.check_sampling_replicated(
            parse("mesh002_good.py")) == []


# ---------------------------------------------------------------------------
# PAL family: seeded kernel regressions via the capture checker
# ---------------------------------------------------------------------------
def _case(build, budget=1 << 20):
    return rules_pallas.KernelCase("fixture", "fixture.py", "fn", "fn",
                                   budget, build)


def _run_fixture_kernel(index_map, block=(128, 128), budget=1 << 20):
    from jax.experimental import pallas as pl

    def build():
        def kern(x_ref, o_ref):
            o_ref[...] = x_ref[...]
        x = jnp.zeros((512, 128), jnp.float32)
        pl.pallas_call(
            kern,
            grid=(4,),
            in_specs=[pl.BlockSpec(block, index_map)],
            out_specs=pl.BlockSpec((128, 128), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((512, 128), jnp.float32),
        )(x)

    return rules_pallas.check_kernel_case(_case(build, budget))


class TestPallasChecker:
    def test_oob_index_map_flagged(self):
        # the seeded regression: corner i=3 maps to block 4 of 4
        found = _run_fixture_kernel(lambda i: (i + 1, 0))
        assert any(f.rule == "PAL001" and "out of bounds" in f.message
                   for f in found)

    def test_in_bounds_map_clean(self):
        assert _run_fixture_kernel(lambda i: (i, 0)) == []

    def test_misaligned_lane_flagged(self):
        found = _run_fixture_kernel(lambda i: (i, 0), block=(128, 64))
        assert any(f.rule == "PAL003" and f.detail == "in[0]:lane"
                   for f in found)

    def test_vmem_budget_enforced(self):
        found = _run_fixture_kernel(lambda i: (i, 0), budget=1024)
        assert [f.rule for f in found] == ["PAL002"]

    def test_oracle_gate_requires_ref_and_dispatch(self):
        case = _case(lambda: None)
        found = rules_pallas.check_oracle_gate(case, "nothing here")
        assert sorted(f.detail for f in found) == ["gate", "oracle"]
        assert rules_pallas.check_oracle_gate(
            case, "ops routes fn to ref.fn") == []

    def test_registered_kernels_are_clean(self):
        ops = os.path.join(ROOT, "src", "repro", "kernels", "ops.py")
        with open(ops) as f:
            src = f.read()
        for case in rules_pallas.KERNEL_CASES:
            assert rules_pallas.check_kernel_case(case) == [], case.name
            assert rules_pallas.check_oracle_gate(case, src) == [], case.name


# ---------------------------------------------------------------------------
# TRB family: runtime trace budgets
# ---------------------------------------------------------------------------
def _poly(x):
    return x * 2.0


KEY = f"{__name__}:_poly"


class TestTraceBudgetRuntime:
    def _record_three_shapes(self):
        with trace_budget.record_jits(prefixes=(__name__,)) as records:
            f = jax.jit(_poly)
            for n in (4, 8, 16):        # three shapes => three executables
                f(jnp.zeros((n,), jnp.float32))
        return records

    def test_exceeded_budget_flagged(self):
        records = self._record_three_shapes()
        found = trace_budget.check_records(records, {KEY: 1}, scenario="fix")
        assert [f.rule for f in found] == ["TRB002"]
        assert "3 executables" in found[0].message

    def test_within_budget_clean(self):
        records = self._record_three_shapes()
        assert trace_budget.check_records(records, {KEY: 4},
                                          scenario="fix") == []

    def test_undeclared_jit_flagged(self):
        records = self._record_three_shapes()
        found = trace_budget.check_records(records, {}, scenario="fix")
        assert [f.rule for f in found] == ["TRB001"]
        assert found[0].detail == KEY


class TestTraceBudgetGates:
    """Satellite: Engine.generate and evaluate_perplexity get the same
    retrace gate test_serve_stack.py:67 gives the batcher step."""

    def _tiny(self):
        from repro.configs.opt125m_proxy import tiny_config
        from repro.models.registry import model_def
        cfg = tiny_config().replace(num_layers=2, d_model=32, d_ff=64,
                                    num_heads=4, num_kv_heads=4, vocab=128)
        model = model_def(cfg)
        return model, model.init(jax.random.PRNGKey(0))

    def test_engine_generate_decodes_with_one_trace(self):
        from repro.serve import Engine, ServeConfig
        model, params = self._tiny()
        eng = Engine(model, params, ServeConfig(cache_len=32))
        rng = np.random.default_rng(0)
        for rid in range(3):
            prompt = rng.integers(0, 128, size=6).astype(np.int32)
            eng.generate(jnp.asarray(prompt[None, :]), max_new_tokens=4,
                         request_ids=[rid])
        assert eng._decode_fn._cache_size() == 1

    def test_evaluate_perplexity_reuses_ce_closure(self):
        from repro.data import CorpusConfig, MarkovCorpus
        from repro.eval import EvalConfig, evaluate_perplexity
        from repro.eval import perplexity
        model, params = self._tiny()
        corpus = MarkovCorpus(CorpusConfig(vocab=128, seed=5))
        ec = EvalConfig(num_batches=2, batch_size=2, seq_len=16,
                        kl_batches=1, budget_batches=1)
        a = evaluate_perplexity(model, params, corpus, ec)
        b = evaluate_perplexity(model, params, corpus, ec)
        assert a.ppl == b.ppl
        assert perplexity._ce_fn(model)._cache_size() == 1

    def test_trainer_evaluate_ppl_shares_the_eval_closure(self):
        from repro.data import CorpusConfig, MarkovCorpus
        from repro.eval import perplexity
        from repro.train.trainer import evaluate_ppl
        model, params = self._tiny()
        corpus = MarkovCorpus(CorpusConfig(vocab=128, seed=5))
        evaluate_ppl(model, params, corpus, batch=2, seq=16, n_batches=2)
        evaluate_ppl(model, params, corpus, batch=2, seq=16, n_batches=2)
        assert perplexity._ce_fn(model)._cache_size() == 1


# ---------------------------------------------------------------------------
# baseline mechanics + the dogfood anchor
# ---------------------------------------------------------------------------
class TestBaseline:
    def test_apply_baseline_splits_new_suppressed_stale(self):
        f1 = acore.Finding("R1", "a.py", 1, "f", "d1", "m")
        f2 = acore.Finding("R2", "b.py", 2, "g", "d2", "m")
        baseline = {f2.key: "accepted", "R9:gone.py::x": "stale entry"}
        new, suppressed, stale = acore.apply_baseline([f1, f2], baseline)
        assert new == [f1] and suppressed == [f2]
        assert stale == ["R9:gone.py::x"]

    def test_key_is_line_number_free(self):
        a = acore.Finding("R1", "a.py", 10, "f", "d", "m")
        b = acore.Finding("R1", "a.py", 99, "f", "d", "m")
        assert a.key == b.key

    def test_committed_baseline_loads(self):
        baseline = acore.load_baseline(
            os.path.join(ROOT, "analysis_baseline.json"))
        assert baseline  # non-empty: the two audited exceptions
        assert all(isinstance(v, str) and v for v in baseline.values())


class TestDogfood:
    """`python -m repro.analysis src/` must exit 0 on the merged tree."""

    def test_src_static_rules_clean_modulo_baseline(self, monkeypatch):
        monkeypatch.chdir(ROOT)
        from repro.analysis import run_source_rules
        findings = run_source_rules(["src"])
        baseline = acore.load_baseline("analysis_baseline.json")
        new, _, stale = acore.apply_baseline(findings, baseline)
        assert new == [], [f.format() for f in new]
        assert stale == [], stale
