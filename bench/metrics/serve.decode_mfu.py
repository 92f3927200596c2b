"""Model step, whole: the decode step's share of the chip's peak, in %.

The larger of FLOPs / peak FLOP/s and bytes / peak bytes/s, over the
device time of one step; stderr says which of the two bounds it.
Bytes are the weights as the cell stores them plus the K/V of the
active contexts, averaged over the window's steps."""
from lib import harness, work


def read(ctx):
    t, run, dm, pk = ctx["trace"], ctx["run"], ctx["dims"], ctx["peaks"]
    n = t["program_count"]("step")
    st = run["stats"]
    if not n or not st["steps"]:
        return None
    step_s = t["program_time"]("step") / n
    w = work.decode_step(dm, st["active_slot_steps"] / st["steps"],
                         st["context_tokens"] / st["steps"],
                         run["sparse"].get("mode") == "packed")
    tf, tb = w["flops"] / pk["bf16_flops"], w["bytes"] / pk["hbm_bytes_per_s"]
    harness.stderr(f"bench: decode step bound by "
                   f"{'bytes' if tb >= tf else 'FLOPs'}: {w}")
    return 100.0 * max(tf, tb) / step_s
