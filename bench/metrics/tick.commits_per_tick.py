"""Transactions admitted, and so committed, per tick: the window's
count over its ticks (exact for a seed and a number of ticks)."""


def read(rec):
    c = rec["counters"]
    if not c.get("ticks"):
        return None
    return c["commits"] / c["ticks"]
