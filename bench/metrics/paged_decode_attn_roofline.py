"""Kernel paged_decode_attn inside the decode step: roofline share, %.

Least time to read the K/V of the active contexts (a windowed layer's
capped at its window) and their q and output rows at the chip's peaks,
over the kernel's device time per step.  Nothing where the run did not
count what a windowed layer reads (``work.WINDOWED_CONTEXT``)."""
from lib import work


def read(ctx):
    t, run, dm, pk = ctx["trace"], ctx["run"], ctx["dims"], ctx["peaks"]
    n, st = t["program_count"]("step"), run["stats"]
    s = t["kernel_time"]("step", "paged_decode_attn")
    if not n or not s or not st["steps"]:
        return None
    w = work.decode_attention(dm, **work.per_step(st))
    if w is None:
        return None
    least = max(w["flops"] / pk["bf16_flops"], w["bytes"] / pk["hbm_bytes_per_s"])
    return 100.0 * least / (s / n)
