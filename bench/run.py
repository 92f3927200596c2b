"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (weights from the seed, warm-up of the cell's own shapes, every
compile) is timed as ``setup_s``; then the window runs for ``--seconds``;
then the answer the window produced is checked against the plain
reference, and each compared number is printed beside its limit on
standard error and under ``checks`` in the result.  The last line of
standard output is the JSON result.  With ``--trace 1`` a profiler trace
of a steady stretch of the window gives the per-layer metrics instead
of the end-to-end ones.  Without a TPU, or with fewer chips than the
cell asks for, the run exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

from lib import harness  # noqa: E402

TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = _args(argv)
    if args.seed < 0:
        raise SystemExit("--seed must be a whole number >= 0")
    entry, cell, conf, mix = harness.cell_files(args.workload)
    from repro.launch import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        dev = harness.device_info(entry["chips"])
    except harness.NoChip as exc:
        harness.stderr(f"bench: {exc}")
        return 2
    peaks = harness.peaks(dev["kind"])
    bench = harness.benchmark()
    tracer = None
    if args.trace:
        from lib import trace as trace_lib
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        tracer = trace_lib.Tracer(TRACE_DIR)
    if cell["kind"] == "prune":
        from lib import prune_cell as kind
    else:
        from lib import serve_cell as kind
    out = kind.run(args, entry, cell, conf, mix, dev, peaks, bench, tracer,
                   T_START)
    if tracer is not None:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    harness.emit(out["result"], out["checks"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
