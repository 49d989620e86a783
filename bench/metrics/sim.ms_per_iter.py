"""Milliseconds of grid-call wall time per loop iteration: the window's
call time over the summed iterations of each protocol's slowest lane
(the fleet's while loop runs until its slowest lane ends)."""


def read(rec):
    c = rec["counters"]
    if not c.get("iters_slowest_lanes"):
        return None
    return c["call_seconds"] / c["iters_slowest_lanes"] * 1e3
