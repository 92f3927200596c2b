"""Readings that set a cell's limits: the program's, and the control's.

    python3 bench/control.py --workload <name> --seeds 11,12,... \
        --control-seeds 11,12,13 --seconds 10 [--out FILE]

For each seed the program runs as in a benchmark run (set-up, a window
of ``--seconds`` at the cell's own load, the answer checked against the
plain reference) and its compared numbers are printed.  For each
control seed the control is judged by the same comparison: the
reference put in the program's place one precision lower (a served
model's logits with every matmul operand in fp8; a prune computed in
bf16).  Each reading is judged against the cell's limits by the same
``harness.check_lines`` that ``run.py`` uses, and the verdict printed:
the program's must come out correct, the control's not.  A limit goes
between the largest program reading and the smallest control reading.
One JSON line per reading goes to standard output and, with ``--out``,
to that file.  Needs the chip, like ``run.py``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

from lib import harness  # noqa: E402


def serve_readings(entry, cell, conf, mix, seed, seconds, control):
    from lib import serve_cell
    st = serve_cell.setup(cell, conf, seed)
    rec = serve_cell.window(st, cell, mix, seed, seconds)
    picked = serve_cell.sample(rec, seed, cell["check"]["served_tokens"])
    serve_cell.free(st)
    out = {"program": {"logit_gap": float(serve_cell.logit_gaps(
        conf, cell, seed, picked).max())}}
    if control:
        out["control"] = {"logit_gap": float(serve_cell.logit_gaps(
            conf, cell, seed, picked, control=True).max())}
    out["served_tokens"] = int(sum(len(t) for _, t in picked))
    return out


def prune_readings(entry, cell, conf, job, seed, seconds, control):
    from lib import prune_cell
    st = prune_cell.setup(cell, conf, job, seed)
    rec = prune_cell.window(st, 0.0)
    reports = [{"unit": r.unit, "key": r.key, "error": float(r.error),
                "rel_error": float(r.rel_error)} for r in rec["reports"]]
    prune_cell.free(st)
    out = {"program": prune_cell.check(conf, cell, job, seed, {
        "pruned": rec["pruned"], "reports": reports})}
    del rec
    if control:
        ans = prune_cell.control_answer(conf, job, seed)
        out["control"] = prune_cell.check(conf, cell, job, seed, ans)
    return out


def judge(values, limits):
    """(correct, check lines) of ``values`` under the cell's ``limits``."""
    return harness.check_lines(
        [(k, values[k], limits[k], "==" if k == "nm_violations" else "<=")
         for k in limits])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    entry, cell, conf, mix = harness.cell_files(args.workload)
    from repro.launch import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        harness.device_info(entry["chips"])
    except harness.NoChip as exc:
        harness.stderr(f"control: {exc}")
        return 2
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    fn = prune_readings if cell["kind"] == "prune" else serve_readings
    for seed in seeds:
        t0 = time.perf_counter()
        rd = fn(entry, cell, conf, mix, seed, args.seconds, seed in controls)
        for side in ("program", "control"):
            if side in rd:
                ok, lines = judge(rd[side], cell["check"]["limits"])
                rd[side + "_correct"] = ok
                for text in lines:
                    harness.stderr(f"control: seed {seed} {side}: {text}")
        line = json.dumps(dict(rd, workload=args.workload, seed=seed,
                               seconds=time.perf_counter() - t0))
        print(line, flush=True)
        if args.out:
            with open(args.out, "a", encoding="utf-8") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
