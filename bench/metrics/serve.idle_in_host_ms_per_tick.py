"""Serving scheduler: device idle milliseconds per decode step during
which the host was inside a program span other than ``serve.tick`` or
``serve.tick.wait`` -- the idle time that overlapping ticks could win at
most.  Each gap of the traced stretch goes to the innermost program span
open at its middle; standard error gets that split."""

from lib import program_spans

#: idle under these is not host work: the tick as a whole, and the wait
NOT_HOST = ("serve.tick", "serve.tick.wait", program_spans.OUTSIDE)


def read(ctx):
    spans = program_spans.read(ctx)
    steps = ctx["trace"]["program_count"]("step")
    if spans is None or not steps:
        return None      # no program spans in the trace, or no decode step
    idle = spans["idle_by_program_span"]
    program_spans.stderr_split("device idle by program span", idle, steps)
    return 1e3 * sum(s for n, s in idle.items() if n not in NOT_HOST) / steps
