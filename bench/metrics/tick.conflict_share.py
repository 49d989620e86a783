"""Share of the device's busy time in the traced ticks spent in the
keyed conflict pass (``conflict_keys`` events), in percent; the rest is
the admission scan, the commit order and the refill."""


def read(rec):
    tr = rec.get("trace")
    k = tr and tr["kernels"].get("conflict_keys")
    if not k or not k["count"] or tr["busy_s"] <= 0:
        return None
    return 100.0 * k["seconds"] / tr["busy_s"]
