"""99th percentile of the tick wall time, in milliseconds (host clock,
as ``tick.ms_per_tick``; Python's ``statistics.quantiles`` with
``n=100``, exclusive method)."""
import statistics


def read(rec):
    ms = rec["counters"].get("tick_ms")
    if not ms or len(ms) < 2:
        return None
    return statistics.quantiles(ms, n=100)[98]
