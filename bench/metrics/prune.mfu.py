"""Whole prune unit: FLOPs from shapes over (unit time x bf16 peak), in %."""
from lib import work


def read(ctx):
    run, dm = ctx["run"], ctx["dims"]
    c = run["job"]["calibration"]
    flops = work.prune(dm, c["num_sequences"], c["seq_len"], run["reports"])
    per_unit_s = run["wall"] / run["units"]
    return 100.0 * flops / dm["layers"] / (per_unit_s
                                           * ctx["peaks"]["bf16_flops"])
