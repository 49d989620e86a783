"""Median wall time of one admission tick, in milliseconds: from the
call of ``scheduler.tick`` to ``block_until_ready`` of its ``admitted``
on the host clock, over the window's ticks outside the traced part."""
import statistics


def read(rec):
    ms = rec["counters"].get("tick_ms")
    if not ms:
        return None
    return statistics.median(ms)
