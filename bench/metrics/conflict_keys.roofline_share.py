"""The keyed conflict pass (``conflict_keys``) against the chip's memory
roofline, in percent.

Bytes the pass must move per launch, from the shapes its entry point
receives (``kernels.ops.conflict_keys``): the key lists in (int32
[n, kr] and [n, kw]), and out the two [n, n] tables (raw, ww; bool,
1 byte each), three int32 [n] degree vectors and two bool [n]
diagonals.  Its n² x (kr + kw) x kw key compares run on the vector
unit, whose rate ``peaks.json`` does not hold, so the bytes alone set
the least time: bytes / HBM bandwidth.  Share = launches x least time
over the summed device time of the ``conflict_keys`` events.
"""


def bytes_per_launch(n: int, kr: int, kw: int) -> int:
    return n * (kr + kw) * 4 + 2 * n * n + 3 * n * 4 + 2 * n


def read(rec):
    tr = rec.get("trace")
    k = tr and tr["kernels"].get("conflict_keys")
    if not k or k["count"] == 0 or k["seconds"] <= 0:
        return None
    s = rec["shapes"]["conflict_keys"]
    total = k["count"] * bytes_per_launch(s["n"], s["kr"], s["kw"])
    least = total / rec["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / k["seconds"]
