"""Serve cells: seeded weights -> ``ContinuousBatcher`` -> traffic window.

Set-up builds the batcher once (packing included), then warms up the
step, the prefill chunk and the first-token sampler with two requests
of the cell's own shapes.  The window replays the mix through
``ContinuousBatcher.run``: open loop, every request timed from when it
was due; or closed loop, a queue deeper than the window serves, timed
for ``--seconds`` from the end of the slots' first fill.

Timing reads the stamps the batcher records per request.  A token's
stamp in ``token_times`` is taken at the start of the scheduler tick
that produces it, so ``token_times[0]`` comes before the last prefill
chunk has even run.  ``token_times[1]`` is stamped after
``_sample_first`` has pulled the first token to the host, so TTFT is
read as ``token_times[1] - arrival``: never earlier than the host's
receipt of the first token, and later by at most the gap until the
next tick starts.  ITL is every difference of ``token_times[1:]``.
"""
from __future__ import annotations

import gc
import time
from typing import Any, Dict, List

import jax
import numpy as np

from lib import harness, traffic, weights

WARMUP_ID = 1 << 30            # ids of warm-up requests, above any window id


def _requests(reqs: List[Dict[str, Any]], first_id: int):
    from repro.serve.batcher import Request
    return [Request(id=first_id + i, prompt=r["prompt"],
                    max_new_tokens=r["max_new"], temperature=r["temperature"],
                    arrival=r["arrival"]) for i, r in enumerate(reqs)]


def setup(cell: Dict[str, Any], conf: Dict[str, Any], seed: int
          ) -> Dict[str, Any]:
    from repro.serve.batcher import BatchConfig, ContinuousBatcher
    from repro.serve.engine import prepare_serving_params
    model = harness.build_model(conf)
    dm = harness.ref_dims(conf)
    params = weights.for_config(conf, seed,
                                sparse24=cell["weights"] == "2:4-magnitude")
    sv = cell["serving"]
    bcfg = BatchConfig(slots=sv["slots"], block_size=sv["block_size"],
                       max_blocks_per_request=sv["max_blocks_per_request"],
                       num_blocks=sv["num_blocks"], sparse=cell["sparse"],
                       prefill_chunk=sv["prefill_chunk"],
                       decode_impl=sv.get("decode_impl", "fused"),
                       seed=seed % (2 ** 31))
    # pack (or not) before the batcher exists, so that the dense tree is
    # freed before the KV pool is allocated; the batcher takes the
    # prepared tree as it is
    served, _ = prepare_serving_params(params, cell["sparse"])
    del params
    batcher = ContinuousBatcher(model, served, bcfg)
    del served
    # warm-up: one prompt of two chunks and one of one, a few decode steps
    rng = np.random.default_rng(seed)
    C = sv["prefill_chunk"]
    warm = [{"prompt": rng.integers(0, dm["vocab"], n).astype(np.int32),
             "max_new": 4, "arrival": 0.0, "temperature": 0.0}
            for n in (C + 1, 1)]
    batcher.run(_requests(warm, WARMUP_ID))
    jax.block_until_ready(batcher.pool_state)
    return {"model": model, "batcher": batcher, "dm": dm}


class _WindowClosed(Exception):
    """Raised from the scheduler's tick once a closed-loop window is over."""


def _stamps(batcher, results) -> List[np.ndarray]:
    """Token stamps of every request of the window: finished ones from
    their results, and those still decoding from the batcher's slots."""
    out = [np.asarray(r.token_times) for r, _ in results]
    out += [np.asarray(batcher._emit_times[s], np.float64)
            for s in range(batcher.cfg.slots) if batcher._active[s]]
    return out


def window(st: Dict[str, Any], cell: Dict[str, Any], mix: Dict[str, Any],
           seed: int, seconds: float, tracer: Any = None) -> Dict[str, Any]:
    """One ``ContinuousBatcher.run`` over the mix.

    Open loop: the window is the whole call, which ends when the last
    request is served.  Closed loop: all ``batch`` requests are queued at
    t=0; the window opens at the first decode tick after the first
    ``slots`` prompts are prefilled (the fill counts as set-up) and
    closes after the tick that ends ``seconds`` later, with the slots
    still full and the queue not empty."""
    batcher, dm = st["batcher"], st["dm"]
    closed = mix["arrivals"] == "closed"
    rate = float(cell.get("rate", 0.0))
    reqs = traffic.generate(mix, seed, dm["vocab"],
                            traffic.count(mix, rate, seconds), rate)
    first = st.get("next_id", 0)
    st["next_id"] = first + len(reqs)
    fill = batcher.stats["prefills"] + (batcher.cfg.slots if closed else 0)
    clock: Dict[str, Any] = {}
    inner = batcher._tick

    def counters():
        return {k: v for k, v in batcher.stats.items() if k != "step_walls"}

    def tick(now):
        if "open" not in clock and batcher.stats["prefills"] >= fill:
            clock.update(open=time.perf_counter(), now0=now, stats0=counters())
        if "open" in clock and tracer is not None:
            tracer.at(time.perf_counter() - clock["open"], seconds)
        inner(now)
        if closed and "open" in clock and \
                time.perf_counter() - clock["open"] >= seconds:
            clock.update(close=time.perf_counter(), now1=now)
            raise _WindowClosed

    stats_start = counters()
    t0 = time.perf_counter()
    batcher._tick = tick
    try:
        batcher.run(_requests(reqs, first))
        if closed:
            raise RuntimeError(f"the {len(reqs)} queued requests ran out "
                               f"before the window closed; raise 'batch'")
        clock.update(open=t0, now0=-np.inf, close=time.perf_counter(),
                     now1=np.inf, stats0=stats_start)
    except _WindowClosed:
        pass
    finally:
        batcher._tick = inner
    stats = {k: v - clock["stats0"][k] for k, v in counters().items()}
    results = [(batcher.results[first + i], q) for i, q in enumerate(reqs)
               if first + i in batcher.results]
    now0, now1 = clock["now0"], clock["now1"]
    done = [(r, q) for r, q in results if r.finished >= now0]
    gaps, tokens = [], 0
    for t in _stamps(batcher, done):
        tokens += int(((t >= now0) & (t <= now1)).sum())
        tt = t[1:]
        inside = (tt[:-1] >= now0) & (tt[1:] <= now1)
        gaps.append(np.diff(tt)[inside] * 1e3)
    return {"results": results, "done": done, "offered": len(reqs),
            "attempted": len(done) if closed else len(reqs),
            "gaps_ms": np.concatenate(gaps), "tokens": tokens,
            "wall": clock["close"] - clock["open"], "stats": stats,
            "open_perf": clock["open"]}


def metrics(rec: Dict[str, Any]) -> Dict[str, Any]:
    """End-to-end numbers over every request, gap and token of the window."""
    ok = [(r, q) for r, q in rec["done"]
          if len(r.tokens) == q["max_new"] and len(r.token_times) >= 2]
    ttft = np.array([(r.token_times[1] - r.arrival) * 1e3 for r, _ in ok])
    gaps = rec["gaps_ms"]
    out = {"attempted": rec["attempted"],
           "failed": rec["attempted"] - len(ok),
           "ttft_p95_ms": float(np.percentile(ttft, 95)),
           "itl_p95_ms": float(np.percentile(gaps, 95)),
           "output_tok_s": rec["tokens"] / rec["wall"],
           "ttft_p50_ms": float(np.percentile(ttft, 50)),
           "itl_p50_ms": float(np.percentile(gaps, 50)),
           "gaps": int(gaps.size), "tokens": rec["tokens"]}
    wait = np.array([(r.admitted - r.arrival) * 1e3 for r, _ in ok])
    out["admission_wait_ms"] = {"p50": float(np.percentile(wait, 50)),
                                "p95": float(np.percentile(wait, 95)),
                                "max": float(wait.max())}
    return out


def sample(rec: Dict[str, Any], seed: int, target_tokens: int) -> List[Any]:
    """Requests to check: the one with most served tokens, the one with
    the longest context, then random others up to ``target_tokens``."""
    done = rec["results"]
    by_out = max(range(len(done)), key=lambda i: len(done[i][0].tokens))
    by_ctx = max(range(len(done)), key=lambda i: len(done[i][1]["prompt"])
                 + len(done[i][0].tokens))
    pick = [by_out] + ([by_ctx] if by_ctx != by_out else [])
    rng = np.random.default_rng(seed)
    for i in rng.permutation(len(done)):
        if sum(len(done[j][0].tokens) for j in pick) >= target_tokens:
            break
        if int(i) not in pick:
            pick.append(int(i))
    return [(done[i][1]["prompt"], np.asarray(done[i][0].tokens)) for i in pick]


def free(st: Dict[str, Any]) -> None:
    st.pop("batcher", None)
    gc.collect()


def logit_gaps(conf: Dict[str, Any], cell: Dict[str, Any], seed: int,
               picked: List[Any], control: bool = False) -> np.ndarray:
    """Per served token: how far its logit lies below the reference's best.

    The reference re-makes the weights from the seed and runs each
    prompt with its served tokens through a plain float32 forward.  With
    ``control`` the token judged at each position is the one the fp8
    control puts first, not the served one."""
    ref = harness.reference(conf)
    dm = ref.dims(conf)
    params = weights.for_config(conf, seed,
                                sparse24=cell["weights"] == "2:4-magnitude")
    fns = ref.make_forward(dm)
    out = []
    for prompt, toks in picked:
        seq = np.concatenate([prompt, toks[:-1]]).astype(np.int32)
        rows = np.arange(len(prompt) - 1, len(seq))
        lg = np.asarray(ref.logits(dm, params, seq, rows, False, fns))
        if control:
            lq = np.asarray(ref.logits(dm, params, seq, rows, True, fns))
            judged = lq.argmax(-1)
        else:
            judged = toks
        out.append(lg.max(-1) - lg[np.arange(len(rows)), judged])
    del params
    return np.concatenate(out)


def run(args, entry, cell, conf, mix, dev, peaks, bench, tracer, t_start):
    """Set-up, window (traced or not), check; returns the result line.
    ``setup_s`` ends where the window opens: for a closed loop that is
    after the fill of the slots."""
    st = setup(cell, conf, args.seed)
    if tracer is not None:
        tracer.wrap_serve(st["batcher"])
    with harness.CompileCounter() as compiles:
        rec = window(st, cell, mix, args.seed, args.seconds, tracer)
    if tracer is not None:
        tracer.stop()
    setup_s = rec["open_perf"] - t_start
    e2e = metrics(rec)
    harness.stderr(f"bench: {e2e['attempted']} requests done, "
                   f"{e2e['failed']} failed, {e2e['tokens']} tokens, "
                   f"{e2e['gaps']} gaps, wall {rec['wall']!r} s, "
                   f"{compiles.count} compiles in the window; "
                   f"ttft p50 {e2e['ttft_p50_ms']!r} ms; itl p50 "
                   f"{e2e['itl_p50_ms']!r} ms; admission wait (ms) "
                   f"{e2e['admission_wait_ms']}")
    device = dict(dev, memory_peak_bytes=harness.memory_peak(dev["count"]))
    picked = sample(rec, args.seed, cell["check"]["served_tokens"])
    run_rec = {"stats": rec["stats"], "dims": st["dm"], "cell": cell,
               "wall": rec["wall"], "sparse": st["batcher"].sparse_stats}
    layer_ctx = None
    if tracer is not None:
        layer_ctx = {"trace": tracer.reduce(), "run": run_rec,
                     "peaks": peaks, "dims": st["dm"]}
        device.update(busy_s=layer_ctx["trace"]["busy_s"],
                      window_s=layer_ctx["trace"]["window_s"])
        metrics_out = harness.per_layer(args.workload, bench["per_layer"],
                                        layer_ctx)
    else:
        metrics_out = harness.end_to_end(bench, args.workload,
                                         dict(e2e, setup_s=setup_s))
    free(st)
    t_check = time.perf_counter()
    gaps = logit_gaps(conf, cell, args.seed, picked)
    harness.stderr(f"bench: checked {len(picked)} requests, {gaps.size} "
                   f"served tokens; set-up {setup_s!r} s, window "
                   f"{rec['wall']!r} s, check "
                   f"{time.perf_counter() - t_check!r} s")
    lim = cell["check"]["limits"]
    ok, lines = harness.check_lines([("logit_gap", float(gaps.max()),
                                      lim["logit_gap"], "<=")])
    result = {"correct": ok, "attempted": e2e["attempted"],
              "failed": e2e["failed"], "metrics": metrics_out,
              "device": device}
    if layer_ctx is not None:
        result["breakdown"] = layer_ctx["trace"]["breakdown"]
    result["checks"] = {"logit_gap": {"value": float(gaps.max()),
                                      "limit": lim["logit_gap"]}}
    return {"result": result, "checks": lines}
