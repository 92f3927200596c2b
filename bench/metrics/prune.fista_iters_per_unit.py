"""Solver: FISTA iterations reported per unit (a count), last prune call."""


def read(ctx):
    reps = ctx["run"]["reports"]
    if not reps:
        return None
    return sum(r["fista_iters"] for r in reps) / ctx["dims"]["layers"]
