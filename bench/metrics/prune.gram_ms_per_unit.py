"""Gram scan: device time of the group-statistics scan programs per unit."""


def read(ctx):
    t = ctx["trace"]
    s = t["program_time"]("_group_stats_scan")
    return None if s is None else 1e3 * s / ctx["run"]["units"]
