"""Share of the traced window in which the device ran no operation, in
percent: 100 x (1 - busy / window), from the device trace."""


def read(rec):
    tr = rec.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
