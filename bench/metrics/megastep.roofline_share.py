"""The cohort-step relations pass (``megastep``) against the chip's
memory roofline, in percent.

Bytes the pass must move per lane, from the shapes its entry point
receives (``kernels.ops.megastep_relations``): the packed read, write
and dirty words (3 x uint32[n, W]), the five per-slot vectors (item,
is_write, active, ready, haslocks; counted at 4 bytes), and its outputs,
the four [n, n] tables (dep, ww, writers_at, readers_at; 1 byte each)
and three [n] vectors (deg int32, lockhit and dirty_hit 1 byte).  The
pass does no floating-point work worth a bound, so the bytes set the
least time: bytes / HBM bandwidth.  One launch serves every lane of the
protocol's vmapped loop, finished lanes included.  Share = least time
over the summed device time of the ``megastep`` events in the trace.
"""


def bytes_per_lane(n: int, words: int) -> int:
    return 3 * n * words * 4 + 5 * n * 4 + 4 * n * n + n * 4 + 2 * n


def read(rec):
    tr = rec.get("trace")
    k = tr and tr["kernels"].get("megastep")
    if not k or k["count"] == 0 or k["seconds"] <= 0:
        return None
    s = rec["shapes"]["megastep"]
    total = k["count"] * s["lanes"] * bytes_per_lane(s["n"], s["words"])
    least = total / rec["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / k["seconds"]
