"""Model step, whole: the decode step's share of the chip's peak, in %.

The larger of FLOPs / peak FLOP/s and bytes / peak bytes/s, over the
device time of one step; stderr says which of the two bounds it.
Bytes are the weights as the cell stores them plus the K/V of the
active contexts (a windowed layer's capped at its window), averaged
over the window's steps.  Nothing where the run did not count what a
windowed layer reads (``work.WINDOWED_CONTEXT``)."""
from lib import harness, work


def read(ctx):
    t, run, dm, pk = ctx["trace"], ctx["run"], ctx["dims"], ctx["peaks"]
    n = t["program_count"]("step")
    st = run["stats"]
    if not n or not st["steps"]:
        return None
    step_s = t["program_time"]("step") / n
    w = work.decode_step(dm, packed=run["sparse"].get("mode") == "packed",
                         **work.per_step(st))
    if w is None:
        return None
    tf, tb = w["flops"] / pk["bf16_flops"], w["bytes"] / pk["hbm_bytes_per_s"]
    harness.stderr(f"bench: decode step bound by "
                   f"{'bytes' if tb >= tf else 'FLOPs'}: {w}")
    return 100.0 * max(tf, tb) / step_s
