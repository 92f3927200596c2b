"""Model step: device time per decode-step program (the batcher's step)."""


def read(ctx):
    t = ctx["trace"]
    s, n = t["program_time"]("step"), t["program_count"]("step")
    return None if not n else 1e3 * s / n
