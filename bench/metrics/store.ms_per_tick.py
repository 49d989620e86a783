"""Median wall time of the keyed store's apply a tick, in milliseconds:
from the call of the apply (the admitted transactions' snapshot read,
logic and commit-order write) to the read-back of its counts, on the
host clock, over the window's steps outside the traced part."""
import statistics


def read(rec):
    ms = rec["counters"].get("store_ms")
    if not ms:
        return None
    return statistics.median(ms)
