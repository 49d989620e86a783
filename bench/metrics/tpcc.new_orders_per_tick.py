"""NewOrders committed per tick, TPC-C's tpmC numerator a tick: the
window's count over its ticks (exact for a seed and a number of
ticks)."""


def read(rec):
    c = rec["counters"]
    if not c.get("ticks") or "new_orders" not in c:
        return None
    return c["new_orders"] / c["ticks"]
