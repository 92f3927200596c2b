"""Serving scheduler: active slots per decode step over slots, in %."""


def read(ctx):
    st = ctx["run"]["stats"]
    if not st["steps"]:
        return None
    slots = ctx["run"]["cell"]["serving"]["slots"]
    return 100.0 * st["active_slot_steps"] / (st["steps"] * slots)
