"""Continuous-batching serving benchmark -> BENCH_serve.json.

Replays closed-loop request traces (every request queued at t=0) at
increasing pressure levels against the continuous batcher, once with
dense weights and once with the same weights packed 2:4 — the serve-time
payoff the paper motivates (memory conservation -> decode throughput).

Headline numbers are **modeled TPU decode-roofline throughput**: the
scheduler run on CPU yields exact step counts, slot occupancy and
per-step context sizes (all deterministic for a greedy closed-loop
trace), and each decode step is costed at its HBM traffic
``(weight_bytes + kv_bytes) / bw`` — weights are read once per step
regardless of how many slots are active, which is precisely why
continuous batching multiplies decode throughput and why the 0.625x
packed weight traffic lifts it further at every pressure level.

Alongside the model, every mode row carries MEASURED per-step wall time
(``measured_step_us``: each level/mode is run ``MEASURE_REPEATS`` times
with dense and packed repeats interleaved, the compile tick is dropped
from each run's per-tick walls, and the minimum of the per-run medians
is reported — the scheduler is deterministic, so repeats only re-sample
CPU wall noise) and the steady-state throughput it implies
(``measured_tok_s``).  These are CPU numbers, not TPU predictions — but
they are exactly what caught the packed-slower-than-dense regression:
packed serving used to interpret the spmm24 Pallas kernel inside the
jitted per-token step.  ``serve/packed.decode_view`` now unpacks once
at construction, so the packed row's measured ratio vs dense
(``measured_packed_vs_dense``, dense step time / packed step time) must
sit at ~1.0 on CPU rather than ~0.5.

Gates vs the committed ``benchmarks/serve_baseline.json``: packed
modeled throughput within ``tolerance`` (5%) at every pressure level
(the benchmark also asserts modeled packed >= dense everywhere), and
the measured packed-vs-dense ratio within ``measured_tolerance`` (15%,
generous — CPU wall noise) of the baselined ratio — at the HIGH
pressure level only (low/mid are reported informationally: a low
pressure run decodes for ~19 steps, so its median step wall is a
handful of samples of pure CPU noise; see ``measured_gate_note`` in
the baseline).

Two serving-feature rows ride along (this PR's radix prefix cache +
chunked prefill), both MEASURED wall-clock, not modeled:

* ``bench_prefix_cache``: a shared-prefix Poisson trace replayed with
  the radix cache off then on (both chunked, so the numerics are
  identical and the decoded tokens are asserted bitwise-equal).  TTFT
  is per-request ``first_token - arrival``; ITL comes from
  ``RequestResult.token_times`` diffs.  Gates: TTFT p50 speedup
  (cache-on vs cache-off) >= ``prefix_ttft_min_speedup`` (2x), and the
  cached-vs-cold throughput ratio within ``measured_tolerance`` of the
  baselined ratio.
* ``bench_chunked_itl``: long prompts interleaved with in-flight
  decoders, eager one-shot prefill vs chunked.  Chunked prefill bounds
  the inter-token stall a decode slot sees while a neighbor prefills,
  so pooled ITL p99 (chunked / eager) is gated at
  ``chunked_itl_p99_max_ratio``.

One extra row measures the observability tax (``bench_obs_overhead``):
the high-pressure packed run repeated bare vs with ``repro.obs``
recording on, gated at ``obs_overhead_max_ratio`` (1.02 — recording is
a guarded attribute access + a bisect per tick, so the instrumented
step must stay within 2% of bare) and pinned token-identical.  The
instrumented run's spans are exported as a Perfetto trace for the CI
artifact (``TRACE_PATH``).
"""
from __future__ import annotations

import dataclasses
import json
import sys
import time
from typing import Dict, List, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks import common
from repro import obs
from repro.core.sparsity import round_tree_nm
from repro.models.registry import model_def
from repro.serve import (BatchConfig, ContinuousBatcher, Request,
                         synthetic_trace)

OUT_PATH = "BENCH_serve.json"
BASELINE_PATH = "benchmarks/serve_baseline.json"

HBM_BW = 819e9                      # v5e, as kernel_bench/quality_bench

#: serving shape of the benchmark (fixed so rows are comparable PR-to-PR)
BATCH = BatchConfig(slots=4, block_size=16, max_blocks_per_request=2,
                    num_blocks=24, seed=0)
PROMPT_LEN, MAX_NEW = (8, 14), 16
PRESSURES = {"low": 4, "mid": 8, "high": 16}     # requests per trace

#: model-parallel degree of the extra TP roofline row: params shard per
#: the Megatron column/row rules and the paged KV pool heads-shards
#: (distributed/executor.py), so each device reads weight_bytes/TP and
#: kv_bytes/TP per step — the per-step roofline divides by TP.  The TP
#: scheduler behavior (steps, occupancy, tokens) is identical to the
#: single-device packed run: TP decode is pinned token-identical in
#: tests/distributed_cases.py::case_batcher_tp_parity.
TP_DEGREE = 4

#: shared-prefix workload: every prompt is one 96-token system prefix
#: plus a short per-request tail, arriving Poisson at PREFIX_RATE req/s
#: (slow enough that the first request's prefill usually completes —
#: and inserts the prefix into the radix cache — before the next
#: arrival, so nearly every later request hits)
PREFIX_BATCH = BatchConfig(slots=4, block_size=16, max_blocks_per_request=8,
                           num_blocks=64, seed=0, prefill_chunk=16)
PREFIX_LEN, PREFIX_TAIL = 96, (4, 12)
PREFIX_REQS, PREFIX_RATE, PREFIX_MAX_NEW = 10, 25.0, 8

#: ITL workload: two long-decode requests in flight while four
#: 112-token prompts prefill behind them — eager one-shot prefill
#: stalls the decoders for a full forward; chunked bounds each stall
#: at one 16-token chunk
ITL_BATCH = BatchConfig(slots=4, block_size=16, max_blocks_per_request=8,
                        num_blocks=64, seed=0)
ITL_SHORT_P, ITL_SHORT_NEW = 8, 32
ITL_LONG_P, ITL_LONG_NEW, ITL_LONG_REQS = 112, 4, 4

#: repeats for the measured serving-feature rows (each replays the
#: Poisson trace in wall time, so repeats are seconds, not ms)
FEATURE_REPEATS = 3


def _sparse_model() -> Tuple[object, object]:
    """Tiny opt-family model with every linear rounded to exact 2:4 —
    serve throughput doesn't depend on weight values, so no training."""
    cfg = common.opt_family_config()
    model = model_def(cfg)
    return model, round_tree_nm(model.init(jax.random.PRNGKey(0)))


def _tree_bytes(params) -> int:
    return int(sum(l.size * jnp.dtype(l.dtype).itemsize
                   for l in jax.tree_util.tree_leaves(params)))


def _kv_token_bytes(cfg) -> int:
    """HBM bytes of one cached token across all layers (K + V)."""
    from repro.models.common import dtype_of
    itemsize = jnp.dtype(dtype_of(cfg.compute_dtype)).itemsize
    return 2 * cfg.num_layers * cfg.num_kv_heads * cfg.resolved_head_dim() * itemsize


def _modeled(st: Dict, results, weight_bytes: int, tok_kv: int,
             tp: int = 1) -> Dict:
    """Roofline numbers from the measured scheduler counters.  ``tp``
    divides the per-device weight and KV traffic (Megatron col/row
    sharding + heads-sharded paged pool): each model shard reads 1/tp of
    the weights and of the cached tokens per step."""
    wb, kb = weight_bytes / tp, tok_kv / tp
    step_s = (wb + kb * st["context_tokens"] / max(st["steps"], 1)) / HBM_BW
    prefill_s = (st["prefills"] * wb + st["prefill_tokens"] * kb) / HBM_BW
    modeled_total = st["steps"] * step_s + prefill_s
    tokens = int(sum(len(r.tokens) for r in results))
    # latency is modeled from *arrival* (t=0 in the closed-loop trace), so
    # queueing delay — the thing pressure buys — is included: a request
    # admitted late finishes at a later step and pays for it here
    lat = np.asarray([r.finished_step * step_s + (wb + r.prompt_len * kb) / HBM_BW
                      for r in results])
    return {
        "modeled_step_us": step_s * 1e6,
        "modeled_tok_s": tokens / max(modeled_total, 1e-12),
        "modeled_p50_ms": float(np.percentile(lat, 50)) * 1e3,
        "modeled_p99_ms": float(np.percentile(lat, 99)) * 1e3,
    }


#: measured-step repeats: the scheduler is deterministic, so re-running a
#: level only re-samples CPU wall noise — dense and packed alternate
#: within each repeat (both modes sample the same noise epochs; they run
#: bitwise-identical compute via decode_view, so any measured gap is
#: pure wall noise) and min-of-medians over the repeats is the
#: steady-state step time (the first tick's jit compile is dropped from
#: each repeat's median)
MEASURE_REPEATS = 5


def _one_run(model, params, sparse: str, n_requests: int):
    trace = synthetic_trace(n_requests, rate=0.0, vocab=model.cfg.vocab,
                            prompt_len=PROMPT_LEN, max_new_tokens=MAX_NEW,
                            seed=7)
    b = ContinuousBatcher(model, params,
                          dataclasses.replace(BATCH, sparse=sparse))
    t0 = time.perf_counter()
    res = b.run(trace)
    return b, res, time.perf_counter() - t0


def _median_step(batcher) -> float:
    walls = batcher.stats["step_walls"]
    return float(np.median(np.asarray(walls[1:] or walls)))


def _run_level_modes(model, params, n_requests: int) -> Dict[str, Dict]:
    """One pressure level, both modes, interleaved measured repeats."""
    first, meds = {}, {"dense": [], "packed": []}
    for rep in range(MEASURE_REPEATS):
        for sparse in ("dense", "packed"):
            b, res, wall = _one_run(model, params, sparse, n_requests)
            if rep == 0:
                first[sparse] = (b, res, wall)
            meds[sparse].append(_median_step(b))
    out = {}
    for sparse in ("dense", "packed"):
        batcher, results, wall = first[sparse]
        step_s = min(meds[sparse])
        st = batcher.stats
        tokens = int(sum(len(r.tokens) for r in results))
        weight_bytes = _tree_bytes(batcher.params)
        tok_kv = _kv_token_bytes(model.cfg)
        out[sparse] = {
            "mode": batcher.sparse_stats["mode"], "requests": n_requests,
            "tokens": tokens, "steps": st["steps"],
            "mean_occupancy": st["active_slot_steps"] / max(st["steps"], 1),
            "weight_bytes": weight_bytes,
            "cpu_wall_s": wall, "cpu_tok_s": tokens / max(wall, 1e-9),
            "measured_step_us": step_s * 1e6,
            "measured_tok_s": tokens / max(st["steps"] * step_s, 1e-12),
            **_modeled(st, results, weight_bytes, tok_kv),
            "token_ids": [r.tokens.tolist() for r in results],
            "_counters": (dict(st), results, weight_bytes, tok_kv),
        }
    return out


def bench_serve_matrix() -> List[Dict]:
    model, params = _sparse_model()
    rows = []
    for level, n in PRESSURES.items():
        per_mode = {}
        level_rows = _run_level_modes(model, params, n)
        for sparse in ("dense", "packed"):
            row = level_rows[sparse]
            st, results, weight_bytes, tok_kv = row.pop("_counters")
            toks = row.pop("token_ids")
            row["pressure"] = level
            per_mode[row["mode"]] = (row, toks)
            rows.append(row)
            if row["mode"] == "packed":
                # the regression this PR fixes: packed per-step wall must
                # not lag dense (same schedule, so step time IS
                # throughput).  Reported at 2 decimals — the run-to-run
                # spread of the underlying CPU walls is several percent,
                # so more digits would be noise printed as signal.
                row["measured_packed_vs_dense"] = round(
                    per_mode["dense"][0]["measured_step_us"]
                    / max(row["measured_step_us"], 1e-9), 2)
            print(f"{level:>5} {row['mode']:>6}: modeled "
                  f"{row['modeled_tok_s']:9.0f} tok/s "
                  f"(p50 {row['modeled_p50_ms']:.3f} ms, "
                  f"p99 {row['modeled_p99_ms']:.3f} ms, occupancy "
                  f"{row['mean_occupancy']:.2f}); measured "
                  f"{row['measured_step_us']:.0f} us/step, "
                  f"{row['measured_tok_s']:.1f} tok/s")
            if sparse == "packed":
                # TP row: same measured schedule (TP decode is pinned
                # token-identical), per-device traffic divided by the
                # model-parallel degree.  Only schedule-derived and
                # modeled fields appear — no cpu_wall/cpu_tok_s, since
                # no TP run was executed here, and weight_bytes is the
                # PER-DEVICE read the roofline actually charges.
                tp_row = dict(
                    mode=f"packed-tp{TP_DEGREE}", tp=TP_DEGREE,
                    requests=row["requests"], tokens=row["tokens"],
                    steps=row["steps"],
                    mean_occupancy=row["mean_occupancy"],
                    weight_bytes=weight_bytes // TP_DEGREE,
                    pressure=level,
                    **_modeled(st, results, weight_bytes, tok_kv,
                               tp=TP_DEGREE))
                rows.append(tp_row)
                print(f"{level:>5} {tp_row['mode']:>6}: modeled "
                      f"{tp_row['modeled_tok_s']:9.0f} tok/s "
                      f"(p50 {tp_row['modeled_p50_ms']:.3f} ms, "
                      f"p99 {tp_row['modeled_p99_ms']:.3f} ms)")
                assert tp_row["modeled_tok_s"] >= row["modeled_tok_s"], \
                    f"TP roofline regressed below packed at {level}"
        # packed serving is bitwise token-identical to dense, so both modes
        # schedule identically and the modeled comparison is apples-to-apples
        assert per_mode["packed"][1] == per_mode["dense"][1], \
            f"packed tokens diverged from dense at pressure {level}"
    return rows


def _latency_stats(results) -> Dict[str, float]:
    """Measured TTFT / pooled-ITL percentiles from one batcher run."""
    ttft = np.asarray([r.first_token - r.arrival for r in results])
    diffs = [np.diff(r.token_times) for r in results
             if r.token_times is not None and len(r.token_times) > 1]
    itl = np.concatenate(diffs) if diffs else np.asarray([0.0])
    return {"ttft_p50_ms": float(np.percentile(ttft, 50)) * 1e3,
            "ttft_p99_ms": float(np.percentile(ttft, 99)) * 1e3,
            "itl_p50_ms": float(np.percentile(itl, 50)) * 1e3,
            "itl_p99_ms": float(np.percentile(itl, 99)) * 1e3}


def _min_stats(per_repeat: List[Dict[str, float]]) -> Dict[str, float]:
    """min over repeats, field-wise — the deterministic scheduler means
    repeats only re-sample CPU wall noise (same convention as
    ``measured_step_us``)."""
    return {k: min(d[k] for d in per_repeat) for k in per_repeat[0]}


def _replay(batcher, trace) -> Tuple[List, Dict[str, float], Dict[str, int]]:
    """One wall-timed replay of ``trace`` on a (reused) batcher.  Request
    ids are offset per replay (the batcher retains results by id), and
    only this replay's results are returned, in trace order."""
    before = dict(batcher.stats)
    offset = getattr(batcher, "_bench_id_offset", 0)
    batcher._bench_id_offset = offset + 1000
    t0 = time.perf_counter()
    res = batcher.run([dataclasses.replace(r, id=r.id + offset)
                       for r in trace])
    wall = time.perf_counter() - t0
    res = sorted((r for r in res if offset <= r.id < offset + 1000),
                 key=lambda r: r.id)
    lat = _latency_stats(res)
    lat["wall_s"] = wall
    deltas = {k: batcher.stats[k] - before[k]
              for k in ("prefill_chunks", "prefills", "preemptions")}
    return res, lat, deltas


def bench_prefix_cache(model, params) -> List[Dict]:
    """Shared-prefix Poisson trace, radix cache off vs on.

    Each mode reuses ONE batcher across ``FEATURE_REPEATS`` timed
    replays after a warmup replay: the decode/chunk executables are
    per-batcher closures, so a fresh batcher per repeat would put a
    multi-hundred-ms jit compile inside the first requests' latency
    windows and swamp the percentiles.  For the cache-on mode the
    warmup also populates the radix cache — the timed replays measure
    steady-state serving, where even the trace's first request hits.
    The decoded tokens of the WARM cache-on replay are asserted
    bitwise-equal to the cache-off replay: that is the cache-identity
    anchor (a hit replays cached K/V, never approximates it)."""
    trace = synthetic_trace(PREFIX_REQS, rate=PREFIX_RATE,
                            vocab=model.cfg.vocab, prompt_len=PREFIX_TAIL,
                            max_new_tokens=PREFIX_MAX_NEW, seed=11,
                            shared_prefix_len=PREFIX_LEN)
    rows = []
    first: Dict[bool, List] = {}
    for cached in (False, True):
        cfg = dataclasses.replace(PREFIX_BATCH, sparse="packed",
                                  prefix_cache=cached)
        b = ContinuousBatcher(model, params, cfg)
        _replay(b, trace)                       # warmup: compiles (+ cache)
        stats, res, deltas = [], None, None
        for _ in range(FEATURE_REPEATS):
            res, lat, deltas = _replay(b, trace)
            stats.append(lat)
        first[cached] = res
        best = _min_stats(stats)
        tokens = int(sum(len(r.tokens) for r in res))
        prompt_tokens = int(sum(r.prompt_len for r in res))
        hit_tokens = int(sum(r.prefix_hit_tokens for r in res))
        rows.append({
            "mode": "prefix-cache-on" if cached else "prefix-cache-off",
            "pressure": "prefix", "requests": len(res), "tokens": tokens,
            "prefill_chunks": deltas["prefill_chunks"],
            "prefix_hit_rate": hit_tokens / max(prompt_tokens, 1),
            "measured_tok_s": tokens / max(best["wall_s"], 1e-9),
            **best})
    off, on = rows
    on["ttft_speedup"] = round(
        off["ttft_p50_ms"] / max(on["ttft_p50_ms"], 1e-9), 2)
    on["throughput_ratio"] = round(
        on["measured_tok_s"] / max(off["measured_tok_s"], 1e-9), 2)
    # the cache-hit path must be BITWISE the cold chunked path
    assert [r.tokens.tolist() for r in first[True]] == \
           [r.tokens.tolist() for r in first[False]], \
        "prefix-cache tokens diverged from cold chunked prefill"
    for row in rows:
        print(f"prefix {row['mode']:>16}: ttft p50 {row['ttft_p50_ms']:.1f} "
              f"ms / p99 {row['ttft_p99_ms']:.1f} ms, itl p99 "
              f"{row['itl_p99_ms']:.2f} ms, hit rate "
              f"{row['prefix_hit_rate']:.2f}, {row['prefill_chunks']} chunks")
    print(f"prefix ttft speedup {on['ttft_speedup']:.2f}x, throughput "
          f"ratio {on['throughput_ratio']:.2f}x (cache-on / cache-off)")
    return rows


def _itl_trace(vocab: int) -> List[Request]:
    rng = np.random.default_rng(13)
    def prompt(p):
        return rng.integers(0, vocab, size=p).astype(np.int32)
    reqs = [Request(id=i, prompt=prompt(ITL_SHORT_P),
                    max_new_tokens=ITL_SHORT_NEW) for i in range(2)]
    reqs += [Request(id=2 + i, prompt=prompt(ITL_LONG_P),
                     max_new_tokens=ITL_LONG_NEW)
             for i in range(ITL_LONG_REQS)]
    return reqs


def bench_chunked_itl(model, params) -> List[Dict]:
    """Long prompts behind live decoders: eager vs chunked prefill.
    Same warmup-replay discipline as ``bench_prefix_cache`` — the
    per-batcher jit compiles must not masquerade as prefill stalls."""
    trace = _itl_trace(model.cfg.vocab)
    rows = []
    for mode in ("eager", "chunked"):
        cfg = dataclasses.replace(
            ITL_BATCH, sparse="packed",
            prefill_chunk=None if mode == "eager" else 16)
        b = ContinuousBatcher(model, params, cfg)
        _replay(b, trace)                       # warmup: compiles
        stats, res, deltas = [], None, None
        for _ in range(FEATURE_REPEATS):
            res, lat, deltas = _replay(b, trace)
            stats.append(lat)
        best = _min_stats(stats)
        rows.append({"mode": f"prefill-{mode}", "pressure": "itl",
                     "requests": len(trace),
                     "tokens": int(sum(len(r.tokens) for r in res)),
                     "prefill_chunks": deltas["prefill_chunks"], **best})
    eager, chunked = rows
    chunked["itl_p99_ratio"] = round(
        chunked["itl_p99_ms"] / max(eager["itl_p99_ms"], 1e-9), 2)
    for row in rows:
        print(f"   itl {row['mode']:>16}: itl p50 {row['itl_p50_ms']:.2f} "
              f"ms / p99 {row['itl_p99_ms']:.2f} ms, ttft p99 "
              f"{row['ttft_p99_ms']:.1f} ms")
    print(f"   itl p99 ratio {chunked['itl_p99_ratio']:.2f} "
          f"(chunked / eager; <1 means chunking bounds the stall)")
    return rows


#: where the instrumented run's Perfetto trace lands (uploaded by CI)
TRACE_PATH = "experiments/bench/serve_trace.json"


def bench_obs_overhead(model, params) -> Dict:
    """The observability tax row: the 'high'-pressure packed run, once
    bare and once with ``repro.obs`` recording (spans + the batcher's SLO
    instruments), paired within each of ``MEASURE_REPEATS`` repeats.

    The GATED number is ``obs_overhead_ratio`` = 1 + (measured per-tick
    recording cost / bare median step time), where the recording cost
    times the batcher's own ``_record_tick_obs`` — the exact sequence the
    decode loop runs per tick.  Raw step-wall ratios cannot carry the 2%
    gate: recording happens *between* the measured step windows (OBS001
    keeps it out of the jitted step), so the off/on wall ratio is pure
    CPU noise at +-3-5% per session — it is still reported
    (``paired_wall_ratio``, median of per-repeat paired ratios) as a
    cross-check that nothing structural crept into the step.  The decoded
    tokens are asserted identical with recording on, and the instrumented
    run's spans are exported as a Perfetto trace (``TRACE_PATH``)."""
    n = PRESSURES["high"]
    meds: Dict[str, List[float]] = {"off": [], "on": []}
    first = {}
    for rep in range(MEASURE_REPEATS):
        for mode in ("off", "on"):
            # enable() resets recorder+registry, so each instrumented
            # repeat pays the same (fresh-instrument) recording cost
            obs.enable() if mode == "on" else obs.disable()
            b, res, _ = _one_run(model, params, "packed", n)
            if rep == 0:
                first[mode] = res
            meds[mode].append(_median_step(b))
    # time the real per-tick recording path on the last instrumented
    # batcher (its instruments and pool state are live), with the gap of
    # every slot buffered as a decode tick buffers them
    reps = 2000
    gaps = [1e-3] * BATCH.slots
    t0 = time.perf_counter()
    for _ in range(reps):
        b._pend_itl.extend(gaps)
        b._record_tick_obs(BATCH.slots)
    rec_s = (time.perf_counter() - t0) / reps
    from repro.obs import spans as spans_lib
    spans_lib.export_perfetto(obs.recorder().spans(), TRACE_PATH)
    obs.disable()
    assert [r.tokens.tolist() for r in first["on"]] == \
           [r.tokens.tolist() for r in first["off"]], \
        "obs recording changed the decoded tokens"
    wall_ratios = [on / max(off, 1e-12)
                   for off, on in zip(meds["off"], meds["on"])]
    off, on = min(meds["off"]), min(meds["on"])
    row = {"mode": "packed-obs", "pressure": "high", "requests": n,
           "step_us_off": off * 1e6, "step_us_on": on * 1e6,
           "recording_us_per_tick": rec_s * 1e6,
           "paired_wall_ratio": round(float(np.median(wall_ratios)), 3),
           "obs_overhead_ratio": round(1.0 + rec_s / max(off, 1e-12), 4)}
    print(f" high packed-obs: recording {row['recording_us_per_tick']:.2f} "
          f"us/tick on a {row['step_us_off']:.0f} us bare step "
          f"(overhead ratio {row['obs_overhead_ratio']:.4f}; paired wall "
          f"ratio {row['paired_wall_ratio']:.3f}); trace -> {TRACE_PATH}")
    return row


def check_regression(rows: List[Dict], baseline_path: str = BASELINE_PATH
                     ) -> Tuple[bool, str]:
    """Gate: packed modeled throughput within ``tolerance`` of the
    committed baseline at every pressure level, and the MEASURED
    packed-vs-dense step-time ratio within ``measured_tolerance``
    (generous; CPU wall noise) of the baselined ratio.  Missing or
    protocol-mismatched baseline => informational pass."""
    try:
        with open(baseline_path) as f:
            base = json.load(f)
    except FileNotFoundError:
        return True, f"no baseline at {baseline_path} (gate skipped)"
    if base.get("protocol") != _protocol():
        return True, "baseline protocol differs (gate skipped; not comparable)"
    tol = float(base.get("tolerance", 0.05))
    mtol = float(base.get("measured_tolerance", 0.15))
    mbase = base.get("measured_packed_vs_dense", {})
    gate_level = base.get("measured_gate_pressure", "high")
    msgs, ok = [], True
    for level in PRESSURES:
        row = next(r for r in rows
                   if r["pressure"] == level and r["mode"] == "packed")
        limit = float(base["levels"][level]) * (1.0 - tol)
        good = row["modeled_tok_s"] >= limit
        ok &= good
        msgs.append(f"{level} {row['modeled_tok_s']:.0f}>= {limit:.0f} "
                    f"{'PASS' if good else 'FAIL'}")
        if level in mbase:
            # the ratio is ~1.0 by construction (decode_view makes both
            # modes run the same compute on CPU); cap the reference at
            # 1.0 so a lucky-fast baseline run can't tighten the gate.
            # Only the HIGH-pressure ratio is gated: a low-pressure trace
            # decodes for ~19 steps, so its median step wall is a
            # handful of CPU-noise samples (a 0.94 reading there is
            # indistinguishable from 1.0) — low/mid stay informational.
            mlimit = min(float(mbase[level]), 1.0) * (1.0 - mtol)
            mgood = row["measured_packed_vs_dense"] >= mlimit
            if level == gate_level:
                ok &= mgood
                msgs.append(f"{level} measured-ratio "
                            f"{row['measured_packed_vs_dense']:.2f}>= "
                            f"{mlimit:.2f} {'PASS' if mgood else 'FAIL'}")
            else:
                msgs.append(f"{level} measured-ratio "
                            f"{row['measured_packed_vs_dense']:.2f} (info)")
    pbase = base.get("prefix", {})
    prow = next((r for r in rows if r.get("mode") == "prefix-cache-on"), None)
    if pbase and prow is not None:
        floor = float(pbase.get("ttft_min_speedup", 2.0))
        sgood = prow["ttft_speedup"] >= floor
        ok &= sgood
        msgs.append(f"prefix ttft-speedup {prow['ttft_speedup']:.2f}>= "
                    f"{floor:.1f} {'PASS' if sgood else 'FAIL'}")
        if "throughput_ratio" in pbase:
            tlimit = float(pbase["throughput_ratio"]) * (1.0 - mtol)
            tgood = prow["throughput_ratio"] >= tlimit
            ok &= tgood
            msgs.append(f"prefix throughput-ratio "
                        f"{prow['throughput_ratio']:.2f}>= {tlimit:.2f} "
                        f"{'PASS' if tgood else 'FAIL'}")
    icap = base.get("chunked_itl_p99_max_ratio")
    irow = next((r for r in rows if r.get("mode") == "prefill-chunked"), None)
    if icap is not None and irow is not None:
        igood = irow["itl_p99_ratio"] <= float(icap)
        ok &= igood
        msgs.append(f"chunked itl-p99-ratio {irow['itl_p99_ratio']:.2f}<= "
                    f"{float(icap):.2f} {'PASS' if igood else 'FAIL'}")
    cap = base.get("obs_overhead_max_ratio")
    orow = next((r for r in rows if r.get("mode") == "packed-obs"), None)
    if cap is not None and orow is not None:
        ogood = orow["obs_overhead_ratio"] <= float(cap)
        ok &= ogood
        msgs.append(f"obs-overhead {orow['obs_overhead_ratio']:.3f}<= "
                    f"{float(cap):.2f} {'PASS' if ogood else 'FAIL'}")
    return ok, (f"packed vs baseline (modeled -{tol:.0%}, measured ratio "
                f"-{mtol:.0%}): " + "; ".join(msgs))


def _protocol() -> Dict:
    return {"batch": dataclasses.asdict(BATCH), "prompt_len": list(PROMPT_LEN),
            "max_new": MAX_NEW, "pressures": dict(PRESSURES),
            "prefix": {"batch": dataclasses.asdict(PREFIX_BATCH),
                       "prefix_len": PREFIX_LEN, "tail": list(PREFIX_TAIL),
                       "requests": PREFIX_REQS, "rate": PREFIX_RATE,
                       "max_new": PREFIX_MAX_NEW},
            "itl": {"batch": dataclasses.asdict(ITL_BATCH),
                    "short": [ITL_SHORT_P, ITL_SHORT_NEW],
                    "long": [ITL_LONG_P, ITL_LONG_NEW, ITL_LONG_REQS]}}


def write_baseline(rows: List[Dict], path: str = BASELINE_PATH,
                   tolerance: float = 0.05,
                   measured_tolerance: float = 0.15,
                   obs_overhead_max_ratio: float = 1.02,
                   prefix_ttft_min_speedup: float = 2.0,
                   chunked_itl_p99_max_ratio: float = 1.0) -> None:
    packed = [r for r in rows if r["mode"] == "packed"]
    prow = next((r for r in rows if r.get("mode") == "prefix-cache-on"), None)
    base = {"levels": {r["pressure"]: r["modeled_tok_s"] for r in packed},
            "tolerance": tolerance,
            "measured_packed_vs_dense":
                {r["pressure"]: r["measured_packed_vs_dense"]
                 for r in packed},
            "measured_tolerance": measured_tolerance,
            # dense and packed run BITWISE-identical compute on CPU
            # (packed.decode_view unpacks once at construction), so the
            # measured ratio is pure wall noise; only the high-pressure
            # level decodes long enough (~4x the steps of 'low') for its
            # median step wall to carry signal.  A 0.94 at 'low' is ~19
            # steps of CPU jitter, not a packed regression — hence the
            # gate applies at 'high' only and low/mid print as (info).
            "measured_gate_pressure": "high",
            "measured_gate_note":
                "dense/packed run bitwise-identical compute on CPU "
                "(decode_view), so the measured ratio is wall noise; "
                "'low' decodes ~19 steps and 'mid' ~35, too few for a "
                "stable median — the 15% measured_tolerance gate "
                "applies at 'high' only, low/mid are informational",
            # a FIXED cap, not baselined-run-relative: recording is
            # a few guarded attribute accesses + bisects per tick,
            # so instrumented/bare step time must stay within 2%
            "obs_overhead_max_ratio": obs_overhead_max_ratio,
            "protocol": _protocol()}
    if prow is not None:
        # ttft_min_speedup is a FIXED floor (the feature's contract:
        # cache hits must at least halve time-to-first-token on the
        # shared-prefix trace); the throughput ratio is baselined
        # run-relative like the other measured numbers
        base["prefix"] = {"ttft_min_speedup": prefix_ttft_min_speedup,
                          "throughput_ratio": prow["throughput_ratio"]}
    if any(r.get("mode") == "prefill-chunked" for r in rows):
        # FIXED cap: chunked prefill must never make tail inter-token
        # latency WORSE than eager one-shot prefill (measured ratios sit
        # well below 1 — each stall is one chunk, not a full prompt)
        base["chunked_itl_p99_max_ratio"] = chunked_itl_p99_max_ratio
    with open(path, "w") as f:
        json.dump(base, f, indent=1)
        f.write("\n")


def run_all(out_path: str = OUT_PATH, baseline_path: str = BASELINE_PATH,
            update_baseline: bool = False) -> Dict:
    print("\n== Continuous-batching serve (modeled TPU roofline, "
          "dense vs packed 2:4) ==")
    rows = bench_serve_matrix()
    model, params = _sparse_model()
    rows.append(bench_obs_overhead(model, params))
    print("\n== Serving features (measured wall): radix prefix cache, "
          "chunked prefill ==")
    rows += bench_prefix_cache(model, params)
    rows += bench_chunked_itl(model, params)
    packed_ge_dense = all(
        next(r for r in rows if r["pressure"] == lv and r["mode"] == "packed")
        ["modeled_tok_s"] >=
        next(r for r in rows if r["pressure"] == lv and r["mode"] == "dense")
        ["modeled_tok_s"] for lv in PRESSURES)
    # measured at the HIGH pressure level only — shorter runs' step
    # medians are CPU noise (see measured_gate_note in the baseline)
    packed_ge_dense_measured = next(
        r for r in rows if r["pressure"] == "high" and r["mode"] == "packed"
    )["measured_packed_vs_dense"] >= 1.0
    ok, msg = check_regression(rows, baseline_path)
    payload = {"rows": rows, "protocol": _protocol(), "hbm_bw": HBM_BW,
               "packed_ge_dense": packed_ge_dense,
               "packed_ge_dense_measured": packed_ge_dense_measured,
               "gate_ok": ok and packed_ge_dense, "regression_gate": msg,
               "backend": jax.default_backend()}
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=1, default=float)
    common.write_result("serve_bench", payload)
    if update_baseline:
        write_baseline(rows, baseline_path)
        print(f"baseline updated: {baseline_path}")
    print(f"\nwrote {out_path}; packed>=dense modeled: {packed_ge_dense}, "
          f"measured: {packed_ge_dense_measured}; {msg}")
    return payload


if __name__ == "__main__":
    payload = run_all(update_baseline="--update-baseline" in sys.argv)
    sys.exit(0 if payload["gate_ok"] else 1)
