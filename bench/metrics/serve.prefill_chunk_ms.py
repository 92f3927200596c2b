"""Prefill: device time per prefill-chunk program (the batcher's chunk step)."""


def read(ctx):
    t = ctx["trace"]
    s, n = t["program_time"]("chunk_step"), t["program_count"]("chunk_step")
    return None if not n else 1e3 * s / n
