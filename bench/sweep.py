"""Find a serve cell's knee once: the highest offered rate without a
growing backlog.

    python3 bench/sweep.py --workload <name> --rates 2,3,4,6 --seconds 20

One batcher is set up (as ``run.py`` does) and the cell's traffic mix is
offered at each rate in turn for ``--seconds``.  A rate has a growing
backlog when the median admission wait of the last quarter of its
requests (by arrival) exceeds that of the first quarter by more than
``--grow-ms``.  One JSON line per rate, then the knee.  Needs the chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

from lib import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--grow-ms", type=float, default=500.0)
    args = ap.parse_args(argv)
    entry, cell, conf, mix = harness.cell_files(args.workload)
    from repro.launch import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        harness.device_info(entry["chips"])
    except harness.NoChip as exc:
        harness.stderr(f"sweep: {exc}")
        return 2
    from lib import serve_cell
    st = serve_cell.setup(cell, conf, args.seed)
    knee = None
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        c = dict(cell, rate=rate)
        rec = serve_cell.window(st, c, mix, args.seed + i, args.seconds)
        m = serve_cell.metrics(rec)
        done = sorted((rq for rq in rec["results"] if rq[0] is not None),
                      key=lambda rq: rq[0].arrival)
        q = max(1, len(done) // 4)
        wait = [1e3 * (r.admitted - r.arrival) for r, _ in done]
        growth = float(np.median(wait[-q:]) - np.median(wait[:q]))
        drain = rec["wall"] - max(r.arrival for r, _ in done)
        row = {"rate": rate, "requests": m["attempted"], "failed": m["failed"],
               "ttft_p50_ms": m["ttft_p50_ms"], "ttft_p95_ms": m["ttft_p95_ms"],
               "itl_p50_ms": m["itl_p50_ms"], "itl_p95_ms": m["itl_p95_ms"],
               "output_tok_s": m["output_tok_s"], "wait_growth_ms": growth,
               "drain_s": drain, "occupancy": rec["stats"]["active_slot_steps"]
               / max(rec["stats"]["steps"], 1) / cell["serving"]["slots"]}
        print(json.dumps(row), flush=True)
        if growth > args.grow_ms:
            break
        knee = rate
    print(json.dumps({"knee_req_s": knee, "grow_ms": args.grow_ms}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
