"""Continuous-batching serving driver over a synthetic Poisson trace.

    # smoke drive on a random-init tiny model
    python -m repro.launch.serve --arch opt125m-proxy --smoke \
        --requests 8 --rate 4 --max-new-tokens 12

    # serve a pruned run (2:4 checkpoints auto-pack onto spmm24)
    python -m repro.launch.serve --checkpoint /tmp/run --requests 32 --rate 8

Builds a Poisson(``--rate``) arrival trace of random-token prompts,
replays it through the continuous batcher (``serve/batcher.py``:
paged KV pool + one jitted decode step with active-slot masking), and
reports throughput and latency percentiles.  ``--checkpoint`` loads a
``launch/prune.py`` run dir (its ``pruned_model``, falling back to
``dense_model`` + unit checkpoints or the latest trainer step, exactly
like ``launch/evaluate.py``); otherwise ``--arch`` is random-initialized
for a scheduling smoke drive.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import List, Optional

import jax
import numpy as np

from repro import api, obs
from repro.checkpoint import store
from repro.launch import enable_compile_cache
from repro.serve import (BatchConfig, ContinuousBatcher, PoolExhausted,
                         synthetic_trace)
from repro.utils import get_logger

log = get_logger("launch.serve")


def load_serving_model(args: argparse.Namespace):
    """Returns (model, params, source string)."""
    if args.checkpoint:
        from repro.launch import evaluate as eval_cli
        run = eval_cli.resolve_run(args.checkpoint)
        model = run["recipe"].load_model(smoke=run["smoke"])
        like = model.init(jax.random.PRNGKey(0))
        if run["kind"] == "prune":
            params, _ = eval_cli._load_params(args.checkpoint,
                                              eval_cli.PRUNED_MODEL, like)
            source = f"{args.checkpoint}:{eval_cli.PRUNED_MODEL}"
        elif run["kind"] == "units":
            dense0, _ = eval_cli._load_params(args.checkpoint,
                                              eval_cli.DENSE_MODEL, like)
            params, _ = eval_cli._assemble_from_units(model, dense0,
                                                      args.checkpoint)
            source = f"{args.checkpoint}:dense_model+unit_*"
        else:
            step = store.latest_step(args.checkpoint)
            params, _ = eval_cli._load_params(args.checkpoint,
                                              store.step_name(step), like)
            source = f"{args.checkpoint}:{store.step_name(step)}"
        return model, params, source
    model = api.load_model(args.arch, smoke=args.smoke)
    params = model.init(jax.random.PRNGKey(args.seed))
    return model, params, f"random-init {args.arch}"


def serve_trace(model, params, args: argparse.Namespace) -> dict:
    if args.requests < 1:
        raise ValueError(f"--requests must be >= 1, got {args.requests}")
    cfg = BatchConfig(slots=args.slots, block_size=args.block_size,
                      max_blocks_per_request=args.max_blocks_per_request,
                      num_blocks=args.blocks, seed=args.seed,
                      sparse=args.sparse, decode_impl=args.decode_impl,
                      prefill_chunk=args.prefill_chunk,
                      prefix_cache=args.prefix_cache)
    pmax = min(args.prompt_len_max,
               cfg.context_len - args.max_new_tokens,
               model.cfg.max_seq - args.max_new_tokens)
    if pmax < args.prompt_len_min:
        raise ValueError(
            f"prompt lengths [{args.prompt_len_min}, {args.prompt_len_max}] "
            f"don't fit the serving context ({cfg.context_len}) or max_seq "
            f"({model.cfg.max_seq}) with max_new_tokens={args.max_new_tokens}")
    prefix_len = args.shared_prefix
    if prefix_len and prefix_len + args.prompt_len_min > pmax:
        raise ValueError(
            f"--shared-prefix {prefix_len} leaves no room for prompt tails "
            f"within the serving context ({cfg.context_len})")
    trace = synthetic_trace(args.requests, args.rate, model.cfg.vocab,
                            prompt_len=(args.prompt_len_min,
                                        pmax - prefix_len),
                            max_new_tokens=args.max_new_tokens,
                            temperature=args.temperature, seed=args.seed,
                            priorities=args.priorities,
                            deadline_s=args.deadline_s,
                            shared_prefix_len=prefix_len)
    executor = api.MeshExecutor.from_spec(args.mesh) if args.mesh else None
    if executor is not None:
        log.info("tensor-parallel serving: %s", executor.describe())
    batcher = ContinuousBatcher(model, params, cfg, executor=executor)
    with obs.span("serve.run", requests=len(trace)):
        results = batcher.run(trace)

    lat = np.asarray([r.latency for r in results])
    tokens = int(sum(len(r.tokens) for r in results))
    wall = max(r.finished for r in results)
    walls = batcher.stats["step_walls"]
    prompt_tokens = int(sum(len(r.prompt) for r in trace))
    hit_tokens = int(sum(r.prefix_hit_tokens for r in results))
    return {
        "sparse_mode": batcher.sparse_stats["mode"],
        "decode_impl": cfg.decode_impl,
        "requests": len(results), "tokens": tokens,
        "wall_s": wall, "tok_s": tokens / max(wall, 1e-9),
        "steps": batcher.stats["steps"],
        "measured_step_us": float(np.median(walls[1:]) * 1e6)
                            if len(walls) > 1 else None,
        "mean_occupancy": batcher.stats["active_slot_steps"]
                          / max(batcher.stats["steps"], 1),
        "latency_p50_s": float(np.percentile(lat, 50)),
        "latency_p99_s": float(np.percentile(lat, 99)),
        "prefill_chunks": batcher.stats["prefill_chunks"],
        "preemptions": batcher.stats["preemptions"],
        "resumes": batcher.stats["resumes"],
        "prefix_hit_tokens": hit_tokens,
        "prefix_hit_rate": hit_tokens / max(prompt_tokens, 1),
        # what each request generated, by request id: the output a
        # caller checks (dense against packed, one mesh against another)
        "request_tokens": {str(r.id): [int(t) for t in r.tokens]
                           for r in results},
        "config": {"slots": cfg.slots, "block_size": cfg.block_size,
                   "num_blocks": cfg.num_blocks,
                   "context_len": cfg.context_len, "rate": args.rate,
                   "decode_impl": cfg.decode_impl,
                   "prefill_chunk": cfg.prefill_chunk,
                   "prefix_cache": cfg.prefix_cache,
                   "shared_prefix": prefix_len,
                   "priorities": args.priorities,
                   "mesh": executor.describe() if executor is not None
                           else {"data": 1, "model": 1, "devices": 1}},
    }


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="opt125m-proxy",
                    choices=list(api.ARCH_CHOICES))
    ap.add_argument("--smoke", action="store_true",
                    help="smoke-size config for --arch (random init)")
    ap.add_argument("--checkpoint", default=None,
                    help="checkpoint-store run dir (launch/prune.py "
                         "--ckpt-dir); serves its pruned_model")
    ap.add_argument("--sparse", default="auto",
                    choices=("auto", "packed", "dense"))
    ap.add_argument("--decode-impl", default="fused",
                    choices=("fused", "reference"),
                    help="decode fast path: 'fused' walks the block table "
                         "in a flash-decoding Pallas kernel (falls back to "
                         "the oracle off-TPU); 'reference' is the gather "
                         "path that anchors it bitwise")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=4.0,
                    help="Poisson arrival rate (req/s); <=0: all at t=0")
    ap.add_argument("--prefill-chunk", type=int, default=None, metavar="C",
                    help="chunked prefill: admit prompts through C-token "
                         "chunks interleaved with decode ticks (bounds "
                         "inter-token stalls under long-prompt arrivals)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix prompt-prefix cache over the paged pool "
                         "(requires --prefill-chunk); cache-hit tokens are "
                         "bitwise identical to cold prefill")
    ap.add_argument("--shared-prefix", type=int, default=0, metavar="N",
                    help="prepend one shared N-token prefix to every "
                         "prompt in the synthetic trace (exercises "
                         "--prefix-cache hits)")
    ap.add_argument("--priorities", type=int, default=1, metavar="K",
                    help="draw request priorities uniformly from [0, K) "
                         "(0 = most urgent; K>1 enables preemption of "
                         "lower-priority actives under pool pressure)")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request deadline (seconds after arrival) "
                         "used as the tiebreak within a priority class")
    ap.add_argument("--prompt-len-min", type=int, default=8)
    ap.add_argument("--prompt-len-max", type=int, default=16)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--max-blocks-per-request", type=int, default=4)
    ap.add_argument("--blocks", type=int, default=64,
                    help="KV pool size in blocks (incl. reserved trash)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="device mesh 'dataxmodel' (e.g. '1x2'): serve "
                         "tensor-parallel over the 'model' axis (params "
                         "per the Megatron rules, paged KV pool "
                         "heads-sharded); tokens identical to 1-device")
    ap.add_argument("--out", default=None, help="write the JSON report here")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="record serve SLO metrics (TTFT, inter-token "
                         "latency, queue depth, pool occupancy) and write "
                         "them as metrics JSONL here")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome/Perfetto trace.json of the run's "
                         "spans here (implies recording, like --metrics-out)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.metrics_out or args.trace_out:
        # must precede the batcher build: its instruments bind in __init__
        obs.enable()
    try:
        model, params, source = load_serving_model(args)
        report = serve_trace(model, params, args)
    except (FileNotFoundError, ValueError, PoolExhausted) as exc:
        print(f"error: {exc}", file=sys.stderr)
        return 2
    report["source"] = source
    print(f"served {report['requests']} requests from {source} "
          f"(sparse={report['sparse_mode']})")
    print(f"throughput {report['tok_s']:.1f} tok/s over {report['wall_s']:.2f}s "
          f"({report['steps']} decode steps, mean occupancy "
          f"{report['mean_occupancy']:.2f}/{args.slots})")
    print(f"latency p50 {report['latency_p50_s']*1e3:.0f} ms, "
          f"p99 {report['latency_p99_s']*1e3:.0f} ms")
    if args.prefix_cache or args.prefill_chunk:
        print(f"prefix hit rate {report['prefix_hit_rate']:.2f} "
              f"({report['prefix_hit_tokens']} tokens), "
              f"{report['prefill_chunks']} prefill chunks, "
              f"{report['preemptions']} preemptions "
              f"({report['resumes']} resumed)")
    if args.metrics_out or args.trace_out:
        reg = obs.registry()
        ttft = reg.get("serve.ttft_s")
        itl = reg.get("serve.inter_token_s")
        if ttft is not None and itl is not None and ttft.total and itl.total:
            print(f"SLO: ttft p50 {ttft.quantile(0.5)*1e3:.0f} ms / "
                  f"p99 {ttft.quantile(0.99)*1e3:.0f} ms, inter-token "
                  f"p50 {itl.quantile(0.5)*1e3:.1f} ms")
        waits = [(name, reg.get(name)) for name in sorted(reg.snapshot())
                 if name.startswith("serve.admission_wait_s.p")]
        if waits:
            parts = [f"{name.rsplit('.', 1)[1]} "
                     f"{h.quantile(0.5)*1e3:.0f} ms"
                     for name, h in waits if h is not None and h.total]
            if parts:
                print("admission wait p50 by priority: " + ", ".join(parts))
        if args.metrics_out:
            reg.dump_jsonl(args.metrics_out)
            print(f"wrote {args.metrics_out}")
        if args.trace_out:
            from repro.obs import spans as spans_lib
            spans_lib.export_perfetto(obs.recorder().spans(), args.trace_out)
            print(f"wrote {args.trace_out}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
        print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
