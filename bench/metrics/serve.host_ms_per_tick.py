"""Serving scheduler: host milliseconds per decode tick, the device wait
left out: the batcher's host-phase counters over the window (admission,
first-token sampling less its pull of the token, chunk dispatch, the
tick's prepare and emit), per decode step.  Standard error gets the
split, and what the window's wall holds beyond the counters and the two
waits: the rest of the run loop, the harness's own wrappers, and in a
traced run the profiler's start and stop, which is why the counters and
not the wall make the number."""

from lib import program_spans

PHASES = ("admit_s", "sample_first_s", "prefill_dispatch_s",
          "tick_prepare_s", "tick_emit_s")


def read(ctx):
    run = ctx["run"]
    st = run["stats"]
    if "tick_wait_s" not in st or not st["steps"]:
        return None      # a batcher without the phase counters
    parts = {k: st[k] for k in PHASES}
    parts["sample_first_s"] -= st["sample_first_wait_s"]
    host = sum(parts.values())
    if "wall" in run:
        parts["rest of the window's wall"] = run["wall"] - host - \
            st["tick_wait_s"] - st["sample_first_wait_s"]
    program_spans.stderr_split("host time by phase", parts, st["steps"])
    return 1e3 * host / st["steps"]
