"""Kernel fused_mlp24 inside the decode step: roofline share, %.

Least time for the MLP of one step, over every slot row
the step computes, from their logical shapes (``work.fused_mlp24``), over
the device time of the fused_mlp24 kernel per step."""
from lib import work


def read(ctx):
    t, run, dm, pk = ctx["trace"], ctx["run"], ctx["dims"], ctx["peaks"]
    n = t["program_count"]("step")
    s = t["kernel_time"]("step", "fused_mlp24")
    if not n or not s:
        return None
    w = work.fused_mlp24(dm, run["cell"]["serving"]["slots"])
    least = max(w["flops"] / pk["bf16_flops"],
                w["bytes"] / pk["hbm_bytes_per_s"])
    return 100.0 * least / (s / n)
