"""Solver: device time of the fused FISTA solve programs per unit."""


def read(ctx):
    t = ctx["trace"]
    s = t["program_time"]("_fused_group", "_fused_single")
    return None if s is None else 1e3 * s / ctx["run"]["units"]
