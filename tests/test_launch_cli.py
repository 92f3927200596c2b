"""End-to-end CLI driver tests (subprocess): launch.train and launch.prune."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(module, *args, timeout=900, env_extra=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src") + os.pathsep + env.get("PYTHONPATH", "")
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "-m", module, *args], env=env,
                          capture_output=True, text=True, timeout=timeout,
                          cwd=ROOT)


def test_compile_cache_placement(tmp_path):
    """The launchers' compile cache: JAX_COMPILATION_CACHE_DIR when set (and
    entries land there), else the fixed ``.jax_cache/`` at the checkout root."""
    probe = ("import jax; from repro.launch import enable_compile_cache; "
             "print(enable_compile_cache()); "
             "print(jax.config.jax_compilation_cache_dir); "
             "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0); "
             "jax.jit(lambda x: x * 3 + 1)(2.0).block_until_ready()")
    cache = tmp_path / "cache"
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_COMPILATION_CACHE_DIR=str(cache))
    out = subprocess.run([sys.executable, "-c", probe], env=env, cwd=tmp_path,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == [str(cache), str(cache)]
    assert any(cache.iterdir())

    env.pop("JAX_COMPILATION_CACHE_DIR")
    probe = ("import jax; from repro.launch import enable_compile_cache; "
             "print(enable_compile_cache()); "
             "print(jax.config.jax_compilation_cache_dir)")
    out = subprocess.run([sys.executable, "-c", probe], env=env, cwd=tmp_path,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    want = os.path.join(ROOT, ".jax_cache")
    assert out.stdout.split() == [want, want]


def test_train_cli_smoke():
    out = _run("repro.launch.train", "--arch", "opt125m-proxy",
               "--steps", "20", "--batch", "4", "--seq", "32")
    assert out.returncode == 0, out.stdout + out.stderr
    assert "valid_ppl=" in out.stdout


def test_train_cli_resume(tmp_path):
    args = ["--arch", "opt125m-proxy", "--steps", "16", "--batch", "4",
            "--seq", "32", "--ckpt-dir", str(tmp_path)]
    out = _run("repro.launch.train", *args)
    assert out.returncode == 0, out.stderr
    out = _run("repro.launch.train", *args, "--resume")
    assert out.returncode == 0, out.stderr
    assert "steps=16" in out.stdout  # restored at final step, no retraining


def test_prune_cli_end_to_end(tmp_path):
    report = tmp_path / "report.json"
    out = _run("repro.launch.prune", "--arch", "opt125m-proxy", "--smoke",
               "--method", "fista", "--sparsity", "2:4",
               "--train-steps", "40", "--calib-sequences", "8",
               "--calib-seq-len", "32", "--workers", "2",
               "--out", str(report))
    assert out.returncode == 0, out.stdout + out.stderr
    rec = json.loads(report.read_text())
    assert rec["method"] == "fista" and rec["sparsity"] == "2:4"
    assert rec["pruned_ppl"] > 0 and rec["dense_ppl"] > 0
    assert rec["mean_rel_err"] < 1.0


def test_prune_then_evaluate_cli(tmp_path):
    """The quality loop of the README: prune --ckpt-dir, then evaluate the
    run's pruned checkpoint against its dense reference."""
    run_dir = tmp_path / "run"
    out = _run("repro.launch.prune", "--arch", "opt125m-proxy", "--smoke",
               "--method", "fista", "--sparsity", "2:4",
               "--train-steps", "30", "--calib-sequences", "8",
               "--calib-seq-len", "32", "--workers", "2",
               "--ckpt-dir", str(run_dir))
    assert out.returncode == 0, out.stdout + out.stderr
    assert (run_dir / "pruned_model" / "MANIFEST.json").exists()

    # the prune driver records observability artifacts alongside the
    # checkpoints: spans + metrics + Perfetto trace + scheduler summary
    obs_dir = run_dir / "obs"
    for fname in ("spans.jsonl", "metrics.jsonl", "trace.json"):
        assert (obs_dir / fname).exists(), fname
    trace = json.loads((obs_dir / "trace.json").read_text())
    assert any(e.get("name") == "prune.unit"
               for e in trace["traceEvents"])
    summary = json.loads((run_dir / "run_summary.json").read_text())
    assert summary["completed"] > 0 and summary["slowest_unit"]
    assert summary["total_solver_seconds"] > 0
    rep = _run("repro.obs", "report", str(run_dir))
    assert rep.returncode == 0, rep.stdout + rep.stderr
    assert "prune.solve" in rep.stdout and "scheduler run summary" in rep.stdout

    report = tmp_path / "quality.json"
    out = _run("repro.launch.evaluate", "--checkpoint", str(run_dir),
               "--against-dense", "--out", str(report))
    assert out.returncode == 0, out.stdout + out.stderr
    assert "ppl=" in out.stdout and "kl=" in out.stdout
    rec = json.loads(report.read_text())
    assert rec["ppl"] > 0 and rec["dense_ppl"] > 0
    assert rec["kl"] >= 0 and 0 <= rec["top1_agreement"] <= 1
    assert rec["meta"]["sparsity"] == "2:4"
    assert rec["error_budget"] and rec["budget_ok"] is not None


def test_evaluate_cli_rejects_bad_eval_recipe(tmp_path):
    """Unknown `eval` keys in a recipe must fail at load time (exit != 0),
    matching the strictness of every other recipe section."""
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"method": "fista",
                               "eval": {"num_batch": 4}}))   # typo'd key
    out = _run("repro.launch.evaluate", "--checkpoint", str(tmp_path),
               "--recipe", str(bad))
    assert out.returncode != 0
    assert "eval" in (out.stderr + out.stdout)


def test_evaluate_cli_missing_run_errors(tmp_path):
    out = _run("repro.launch.evaluate", "--checkpoint",
               str(tmp_path / "nowhere"))
    assert out.returncode == 2
    assert "not found" in out.stderr


def test_serve_cli_smoke(tmp_path):
    """Continuous-batching serve driver over a Poisson trace (random-init
    smoke model): must report throughput/latency and write the JSON."""
    report = tmp_path / "serve.json"
    metrics = tmp_path / "metrics.jsonl"
    trace = tmp_path / "trace.json"
    out = _run("repro.launch.serve", "--arch", "opt125m-proxy", "--smoke",
               "--requests", "5", "--rate", "16", "--max-new-tokens", "6",
               "--slots", "2", "--out", str(report),
               "--metrics-out", str(metrics), "--trace-out", str(trace))
    assert out.returncode == 0, out.stdout + out.stderr
    assert "tok/s" in out.stdout and "latency" in out.stdout
    rec = json.loads(report.read_text())
    assert rec["requests"] == 5 and rec["tokens"] == 30
    assert rec["steps"] > 0 and rec["latency_p99_s"] >= rec["latency_p50_s"]
    # SLO observability rides the same run: TTFT/inter-token histograms
    # in the metrics JSONL, spans in a Perfetto-loadable trace
    assert "SLO: ttft p50" in out.stdout
    names = {json.loads(line)["name"]
             for line in metrics.read_text().splitlines() if line.strip()}
    assert {"serve.ttft_s", "serve.inter_token_s", "serve.step_s",
            "serve.pool_occupancy", "serve.decode_steps"} <= names
    events = json.loads(trace.read_text())["traceEvents"]
    assert any(e.get("name") == "serve.run" for e in events)


def test_serve_cli_rejects_oversized_trace():
    """Prompt lengths that cannot fit the serving context must die with a
    clear error instead of wrapping the KV pool."""
    out = _run("repro.launch.serve", "--arch", "opt125m-proxy", "--smoke",
               "--requests", "2", "--prompt-len-min", "60",
               "--prompt-len-max", "64", "--max-new-tokens", "16",
               "--max-blocks-per-request", "4", "--block-size", "16")
    assert out.returncode == 2
    assert "context" in out.stderr


def test_serve_cli_rejects_bad_mesh():
    """--mesh must parse as DATAxMODEL and fit the visible devices; a bad
    spec (or a mesh this machine cannot build) exits 2 with the error."""
    out = _run("repro.launch.serve", "--arch", "opt125m-proxy", "--smoke",
               "--requests", "2", "--mesh", "4y2")
    assert out.returncode == 2
    assert "mesh" in out.stderr.lower()


def test_prune_cli_rejects_bad_mesh():
    """A bad --mesh must die with a clean error/exit 2 BEFORE any
    training happens — same contract as the evaluate/serve CLIs."""
    out = _run("repro.launch.prune", "--arch", "opt125m-proxy", "--smoke",
               "--train-steps", "9999", "--mesh", "4y2", timeout=120)
    assert out.returncode == 2, out.stdout + out.stderr
    assert "mesh" in out.stderr.lower()
    assert "Traceback" not in out.stderr


def test_evaluate_cli_mesh_unavailable_degrades(tmp_path):
    """A checkpoint whose recipe RECORDS a mesh must still evaluate on a
    machine without those devices (single-device fallback), while an
    EXPLICIT --mesh that cannot be built fails loudly."""
    from repro.utils.compat import force_host_devices_flags

    run_dir = tmp_path / "run"
    # prune under 8 fake host devices with --mesh 8x1 so the stored
    # recipe actually records the mesh this machine won't have
    fake8 = {"XLA_FLAGS": force_host_devices_flags(8)}
    out = _run("repro.launch.prune", "--arch", "opt125m-proxy", "--smoke",
               "--method", "wanda", "--sparsity", "2:4",
               "--train-steps", "6", "--calib-sequences", "8",
               "--calib-seq-len", "32", "--workers", "1", "--mesh", "8x1",
               "--ckpt-dir", str(run_dir), env_extra=fake8)
    assert out.returncode == 0, out.stdout + out.stderr
    rec = json.loads((run_dir / "pruned_model" / "MANIFEST.json").read_text())
    assert rec["extra"]["recipe"]["mesh"]["devices"] == 8  # mesh recorded
    # strip any inherited fake-device flag: these two runs must really
    # see fewer than 8 devices
    bare = {"XLA_FLAGS": force_host_devices_flags(1)}
    # explicit --mesh on this 1-device process must fail loudly
    out = _run("repro.launch.evaluate", "--checkpoint", str(run_dir),
               "--mesh", "8x1", env_extra=bare)
    assert out.returncode == 2 and "devices" in out.stderr
    # without --mesh the recorded mesh degrades to the single-device
    # (bitwise-identical) eval path instead of failing
    out = _run("repro.launch.evaluate", "--checkpoint", str(run_dir),
               env_extra=bare)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "ppl=" in out.stdout
