"""Plain reference of one tick of the ``txn`` cells: TPC-C NewOrder and
Payment (TPC-C v5.11, §2.4.2 and §2.5.2) admitted under PPCC and then
executed one at a time.  It imports nothing of the system under test.

1. Key sets, as tuples (``("S", w, i)`` is the stock row of item ``i``
   in warehouse ``w``): a NewOrder reads its warehouse, district,
   customer, items and stock rows and writes its district and stock
   rows; a Payment reads and writes its warehouse, district and
   customer.  Admission and the commit order are ``ycsb_tick``'s:
   conflicts pair by pair by set intersection, the Prudent Precedence
   Rule in priority order, readers first.
2. Execution: the admitted transactions, serially in commit order, on a
   copy of the pre-tick tables, in Python integers; inserted rows are
   appended in that order.  Money columns are two limbs (hi x 10^9 +
   lo cents); the fixed-point rules are the configuration's
   (``assumed``).
3. ``mismatches``: rows of any table, appended rows and transaction
   outputs that differ between the program and this reference.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from reference import ycsb_tick

UNIT = 10 ** 9
NULL = -1


class Scale:
    def __init__(self, config: dict):
        self.w = int(config["warehouses"])
        self.d = int(config["districts_per_warehouse"])
        self.c = int(config["customers_per_district"])
        self.i = int(config["items"])

    def district(self, w, d):
        return (w - 1) * self.d + d - 1

    def customer(self, w, d, c):
        return ((w - 1) * self.d + d - 1) * self.c + c - 1

    def stock(self, w, i):
        return (w - 1) * self.i + i - 1


def _lines(txn, k):
    n = int(txn["ol_cnt"][k])
    return [(int(txn["i_id"][k][j]), int(txn["supply_w"][k][j]),
             int(txn["qty"][k][j])) for j in range(n)]


def key_sets(txn: dict):
    """(reads, writes): one set of tuples per transaction."""
    reads, writes = [], []
    for k in range(len(txn["w"])):
        w, d = int(txn["w"][k]), int(txn["d"][k])
        if txn["new_order"][k]:
            c = ("C", w, d, int(txn["c"][k]))
            lines = _lines(txn, k)
            stock = {("S", sw, i) for i, sw, _ in lines}
            reads.append({("W", w), ("D", w, d), c}
                         | {("I", i) for i, _, _ in lines} | stock)
            writes.append({("D", w, d)} | stock)
        else:
            keys = {("W", w), ("D", w, d),
                    ("C", int(txn["c_w"][k]), int(txn["c_d"][k]),
                     int(txn["c"][k]))}
            reads.append(keys)
            writes.append(set(keys))
    return reads, writes


def admit(txn: dict) -> dict:
    """``admitted``, ``commit_rank`` and the conflicts (``raw``) of the
    backlog ``txn``, every row valid."""
    reads, writes = key_sets(txn)
    raw = ycsb_tick.conflicts(reads, writes)
    admitted, follows = ycsb_tick.admit(raw, np.ones(len(reads), bool))
    return {"admitted": admitted,
            "commit_rank": ycsb_tick.commit_rank(admitted, follows),
            "raw": raw}


def _money(col, r) -> int:
    return int(col[r][0]) * UNIT + int(col[r][1])


def _set_money(col, r, cents: int) -> None:
    col[r] = divmod(cents, UNIT)


def _text(row) -> bytes:
    return bytes(np.asarray(row, np.uint8)).rstrip(b"\0")


def _put_text(col, r, value: bytes) -> None:
    buf = np.zeros(col.shape[1:], np.uint8).reshape(-1)
    buf[:len(value)] = np.frombuffer(value, np.uint8)
    col[r] = buf.reshape(col.shape[1:])


def _round(x: int) -> int:
    """x / 10 000 to the nearest integer, half up (x >= 0)."""
    return (x + 5_000) // 10_000


def new_order(t: dict, s: Scale, txn: dict, k: int, now: int, logs):
    w, d, c = int(txn["w"][k]), int(txn["d"][k]), int(txn["c"][k])
    wh, di, cu = t["warehouse"], t["district"], t["customer"]
    dr, cr = s.district(w, d), s.customer(w, d, c)
    o_id = int(di["d_next_o_id"][dr])
    di["d_next_o_id"][dr] = o_id + 1
    st, it = t["stock"], t["item"]
    lines = _lines(txn, k)
    amounts, brand = [], []
    for n, (i, sw, q) in enumerate(lines, 1):
        sr = s.stock(sw, i)
        sq = int(st["s_quantity"][sr])
        st["s_quantity"][sr] = sq - q if sq >= q + 10 else sq - q + 91
        st["s_ytd"][sr] += q
        st["s_order_cnt"][sr] += 1
        if sw != w:
            st["s_remote_cnt"][sr] += 1
        amount = q * int(it["i_price"][i - 1])
        amounts.append(amount)
        original = (b"ORIGINAL" in _text(it["i_data"][i - 1])
                    and b"ORIGINAL" in _text(st["s_data"][sr]))
        brand.append(ord("B") if original else ord("G"))
        logs["order_line"].append({
            "ol_o_id": o_id, "ol_d_id": d, "ol_w_id": w, "ol_number": n,
            "ol_i_id": i, "ol_supply_w_id": sw, "ol_delivery_d": NULL,
            "ol_quantity": q, "ol_amount": amount,
            "ol_dist_info": bytes(np.asarray(
                st["s_dist"][sr][24 * (d - 1):24 * d], np.uint8))})
    net = _round(sum(amounts) * (10_000 - int(cu["c_discount"][cr])))
    total = _round(net * (10_000 + int(wh["w_tax"][w - 1])
                          + int(di["d_tax"][dr])))
    logs["order"].append({
        "o_id": o_id, "o_d_id": d, "o_w_id": w, "o_c_id": c,
        "o_entry_d": now, "o_carrier_id": NULL, "o_ol_cnt": len(lines),
        "o_all_local": int(all(sw == w for _, sw, _ in lines))})
    logs["new_order"].append({"no_o_id": o_id, "no_d_id": d, "no_w_id": w})
    return {"o_id": o_id, "total": total, "brand_generic": brand}


def payment(t: dict, s: Scale, txn: dict, k: int, now: int, logs):
    w, d = int(txn["w"][k]), int(txn["d"][k])
    c_w, c_d, c = (int(txn["c_w"][k]), int(txn["c_d"][k]),
                   int(txn["c"][k]))
    h = int(txn["h_amount"][k])
    wh, di, cu = t["warehouse"], t["district"], t["customer"]
    dr, cr = s.district(w, d), s.customer(c_w, c_d, c)
    _set_money(wh["w_ytd"], w - 1, _money(wh["w_ytd"], w - 1) + h)
    _set_money(di["d_ytd"], dr, _money(di["d_ytd"], dr) + h)
    balance = _money(cu["c_balance"], cr) - h
    _set_money(cu["c_balance"], cr, balance)
    _set_money(cu["c_ytd_payment"], cr, _money(cu["c_ytd_payment"], cr) + h)
    cu["c_payment_cnt"][cr] += 1
    if _text(cu["c_credit"][cr]) == b"BC":
        note = (f"{c:04d} {c_d:02d} {c_w:04d} {d:02d} {w:04d} "
                f"{h // 100:04d}.{h % 100:02d} ").encode()
        _put_text(cu["c_data"], cr, (note + _text(cu["c_data"][cr]))[:500])
    h_data = (_text(wh["w_name"][w - 1]) + b"    "
              + _text(di["d_name"][dr]))
    logs["history"].append({
        "h_c_id": c, "h_c_d_id": c_d, "h_c_w_id": c_w, "h_d_id": d,
        "h_w_id": w, "h_date": now, "h_amount": h,
        "h_data": h_data.ljust(24, b"\0")})
    return {"c_balance": balance}


def execute(tables: dict, config: dict, txn: dict, admitted, commit_rank,
            now: int):
    """Run the admitted transactions of ``txn`` serially in commit
    order on a copy of ``tables``: (tables after, appended rows per log
    in order, outputs by backlog row)."""
    s = Scale(config)
    t = {name: {c: np.array(v, copy=True) for c, v in cols.items()}
         for name, cols in tables.items()}
    logs: Dict[str, List[dict]] = {"order": [], "new_order": [],
                                   "order_line": [], "history": []}
    order = sorted((int(commit_rank[k]), k) for k in range(len(admitted))
                   if admitted[k])
    outputs = {}
    for _, k in order:
        run = new_order if txn["new_order"][k] else payment
        outputs[k] = run(t, s, txn, k, now, logs)
    return t, logs, outputs


def _row_diffs(a: dict, b: dict) -> int:
    """Rows of one table that differ in any column."""
    bad = None
    for c in b:
        x, y = np.asarray(a[c]), np.asarray(b[c])
        d = (x != y).reshape(len(y), -1).any(1)
        bad = d if bad is None else bad | d
    return int(bad.sum())


def _log_diffs(prog: dict, ref: List[dict]) -> int:
    """Appended rows that differ, the longer list's extra rows too."""
    n_prog = len(next(iter(prog.values())))
    bad = abs(n_prog - len(ref))
    for r, row in enumerate(ref[:n_prog]):
        for c, v in row.items():
            got = prog[c][r]
            want = (np.frombuffer(v, np.uint8) if isinstance(v, bytes)
                    else v)
            if not np.array_equal(np.asarray(got), want):
                bad += 1
                break
    return bad


def mismatches(prog_tables: dict, prog_logs: dict, prog_out: dict,
               ref_tables: dict, ref_logs: dict, ref_out: dict) -> int:
    """Rows of the tables, appended rows and outputs of admitted
    transactions where the program and the reference differ."""
    bad = sum(_row_diffs(prog_tables[n], ref_tables[n]) for n in ref_tables)
    bad += sum(_log_diffs(prog_logs[n], ref_logs[n]) for n in ref_logs)
    for k, want in ref_out.items():
        if "c_balance" in want:
            got = _money(prog_out["c_balance"], k)
            ok = got == want["c_balance"]
        else:
            n = len(want["brand_generic"])
            ok = (int(prog_out["o_id"][k]) == want["o_id"]
                  and int(prog_out["total"][k]) == want["total"]
                  and list(map(int, prog_out["brand_generic"][k][:n]))
                  == want["brand_generic"])
        bad += not ok
    return bad
