"""Loop iterations per grid call: the sum over protocols of the slowest
lane's ``iters``, averaged over the window's calls (an exact count for
a seed)."""


def read(rec):
    c = rec["counters"]
    if not c.get("calls"):
        return None
    return c["iters_slowest_lanes"] / c["calls"]
