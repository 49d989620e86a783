"""TPC-C's NewOrder and Payment (TPC-C v5.11, §2.4 and §2.5) on the
keyed table store (``sched.txstore``), for a deterministic database
whose sequencer hands each epoch's backlog to ``scheduler.tick``.

* **Tables** (``populate``): WAREHOUSE, DISTRICT, CUSTOMER, ITEM and
  STOCK as columns at the spec's widths (§1.3) — numbers ``int32``,
  strings ``uint8[rows, len]`` (NUL after the value), money as two
  ``int32`` limbs (``money``).  The byte columns the procedures gather
  (``C_DATA``, ``S_DIST_01..10`` side by side, ``S_DATA``, ``I_DATA``)
  are stored a multiple of 128 bytes wide, NUL-padded: the TPU lays a
  narrower byte column out transposed, and a gather or scatter of its
  rows would relayout the whole column first, every tick.  ORDER,
  NEW-ORDER, ORDER-LINE and HISTORY are the store's append-only logs
  (``LOG_COLUMNS``).  The population follows §4.3.3.1 from a seed.
* **Keys**: one ``int32`` id space, a block per table in ``TABLES``
  order (``bases``); a transaction's read list is ``[W, D, C, I x 15,
  S x 15]`` (33 ids, negative ids pad) and its write list 16 ids:
  NewOrder ``[D, S x 15]``, Payment ``[W, D, C]`` then pads
  (``key_lists``).
* **Transactions** are a struct of ``int32`` columns (``TXN_FIELDS``):
  the terminal's inputs of §2.4.1 and §2.5.1, a by-name Payment's
  customer already resolved (``name_directory``).
* **Logic** (``new_order``, ``payment``), on the rows the store
  gathered from the tick's snapshot, and ``apply_tick``, which runs
  both on a backlog through ``txstore.apply_keyed``.

Fixed-point rules: tax and discount are ``numeric(4,4)`` held in units
of 1/10 000, prices and amounts in cents.  A NewOrder's total is
``round(round(sum(OL_AMOUNT) x (1 - C_DISCOUNT)) x (1 + W_TAX +
D_TAX))``, each rounding to the cent, half up, exact in ``int32``
(``scale_round``).  A bad-credit customer's ``C_DATA`` gets the text
``"CCCC DD WWWW dd wwww HHHH.hh "`` (C_ID, C_D_ID, C_W_ID, D_ID, W_ID
zero-padded, H_AMOUNT in dollars; 29 bytes) at its left, the old
content shifted right and cut at 500 bytes.  Dates (``O_ENTRY_D``,
``H_DATE``) are the tick's index, the load's rows have date 0, and a
null (``O_CARRIER_ID``, ``OL_DELIVERY_D``) is -1.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import txstore

MAX_LINES = 15              # order lines of a NewOrder, at most
READ_KEYS = 3 + 2 * MAX_LINES
WRITE_KEYS = 1 + MAX_LINES
NULL = -1
UNIT = 10 ** 9              # the low limb of a money column, in cents
C_DATA_PREFIX = 29          # bytes Payment puts before a BC customer's data
C_DATA = 500                # C_DATA's width; the column is 512 bytes
DIST = 24                   # each S_DIST_xx; ten of them in a 256-byte column
SYLLABLES = ("BAR", "OUGHT", "ABLE", "PRI", "PRES", "ESE", "ANTI",
             "CALLY", "ATION", "EING")
ALNUM = np.frombuffer(b"0123456789ABCDEFGHIJKLMNOPQRSTUVWXYZ"
                      b"abcdefghijklmnopqrstuvwxyz", np.uint8)
ORIGINAL = np.frombuffer(b"ORIGINAL", np.uint8)
TABLES = ("warehouse", "district", "customer", "item", "stock")
TXN_FIELDS = ("new_order", "w", "d", "c", "c_w", "c_d", "ol_cnt", "i_id",
              "supply_w", "qty", "h_amount")

# the append-only logs: column -> (trailing shape, dtype)
_I = ((), jnp.int32)
LOG_COLUMNS = {
    "order": {c: _I for c in ("o_id", "o_d_id", "o_w_id", "o_c_id",
                              "o_entry_d", "o_carrier_id", "o_ol_cnt",
                              "o_all_local")},
    "new_order": {c: _I for c in ("no_o_id", "no_d_id", "no_w_id")},
    "order_line": {**{c: _I for c in (
        "ol_o_id", "ol_d_id", "ol_w_id", "ol_number", "ol_i_id",
        "ol_supply_w_id", "ol_delivery_d", "ol_quantity", "ol_amount")},
        "ol_dist_info": ((24,), jnp.uint8)},
    "history": {**{c: _I for c in ("h_c_id", "h_c_d_id", "h_c_w_id",
                                   "h_d_id", "h_w_id", "h_date",
                                   "h_amount")},
                "h_data": ((24,), jnp.uint8)},
}


class Scale(NamedTuple):
    """The database's scale (§1.2, §4.3.3.1)."""
    warehouses: int
    districts: int = 10          # per warehouse
    customers: int = 3000        # per district
    items: int = 100_000

    @classmethod
    def of(cls, config: dict) -> "Scale":
        return cls(int(config["warehouses"]),
                   int(config["districts_per_warehouse"]),
                   int(config["customers_per_district"]),
                   int(config["items"]))

    @property
    def names(self) -> int:
        """Last names in use: the first 1 000 customers of a district
        take names 0..999 in order (§4.3.3.1), so every one exists."""
        return min(1000, self.customers)

    def rows(self) -> Dict[str, int]:
        w, d = self.warehouses, self.districts
        return {"warehouse": w, "district": w * d,
                "customer": w * d * self.customers, "item": self.items,
                "stock": w * self.items}


def bases(scale: Scale) -> Dict[str, int]:
    """The first key id of each table's block."""
    out, at = {}, 0
    for t in TABLES:
        out[t] = at
        at += scale.rows()[t]
    return out


# -- money: numeric(12,2) as two int32 limbs --------------------------------

def money(cents) -> np.ndarray:
    """Cents (host integers) as ``int32[..., 2]`` limbs (hi, lo):
    value = hi x 10^9 + lo, 0 <= lo < 10^9."""
    hi, lo = np.divmod(np.asarray(cents, np.int64), UNIT)
    return np.stack([hi, lo], -1).astype(np.int32)


def money_add(m: jax.Array, cents: jax.Array) -> jax.Array:
    """``m + cents`` exactly, for |cents| < 10^9."""
    lo = m[..., 1] + cents
    carry = jnp.floor_divide(lo, UNIT)
    return jnp.stack([m[..., 0] + carry, lo - carry * UNIT], -1)


def scale_round(v: jax.Array, m: jax.Array) -> jax.Array:
    """``round_half_up(v x m / 10 000)`` for 0 <= v < 2^31, 0 <= m <=
    20 000, without leaving int32: v = a x 10^4 + b."""
    a, b = jnp.divmod(v, 10_000)
    return a * m + (b * m + 5_000) // 10_000


# -- population (§4.3.3.1) ---------------------------------------------------

def nurand(rng: np.random.Generator, a: int, x: int, y: int, c: int,
           size) -> np.ndarray:
    """NURand(A, x, y) = (((random(0, A) | random(x, y)) + C) %
    (y - x + 1)) + x (§2.1.6)."""
    r = rng.integers(0, a + 1, size) | rng.integers(x, y + 1, size)
    return (r + c) % (y - x + 1) + x


def last_name(num: int) -> bytes:
    """The syllable name of ``num`` in 0..999 (§4.3.2.3)."""
    return "".join(SYLLABLES[int(ch)] for ch in f"{num:03d}").encode()


def _astring(rng, rows: int, lo: int, hi: int, width: int,
             stored: int = None) -> np.ndarray:
    """Random alphanumeric strings of length lo..hi in ``width`` bytes,
    NUL-padded to ``stored`` bytes."""
    s = ALNUM[rng.integers(0, len(ALNUM), (rows, width), dtype=np.uint8)]
    n = rng.integers(lo, hi + 1, rows)
    s[np.arange(width)[None, :] >= n[:, None]] = 0
    return np.pad(s, [(0, 0), (0, (stored or width) - width)])


def _nstring(rng, rows: int, n: int) -> np.ndarray:
    return (rng.integers(0, 10, (rows, n)) + ord("0")).astype(np.uint8)


def _zip(rng, rows: int) -> np.ndarray:
    return np.concatenate([_nstring(rng, rows, 4),
                           np.full((rows, 5), ord("1"), np.uint8)], 1)


def _letters(rng, rows: int, n: int) -> np.ndarray:
    return (rng.integers(0, 26, (rows, n)) + ord("A")).astype(np.uint8)


def _with_original(rng, data: np.ndarray) -> np.ndarray:
    """10% of rows get "ORIGINAL" at a random place of their value."""
    rows = np.nonzero(rng.random(len(data)) < 0.1)[0]
    length = (data[rows] != 0).sum(1)
    at = rng.integers(0, length - len(ORIGINAL) + 1)
    for k, ch in enumerate(ORIGINAL):
        data[rows, at + k] = ch
    return data


def _fixed(strings, width: int) -> np.ndarray:
    out = np.zeros((len(strings), width), np.uint8)
    for i, s in enumerate(strings):
        out[i, :len(s)] = np.frombuffer(s, np.uint8)
    return out


def _address(rng, rows: int, p: str) -> dict:
    return {f"{p}_street_1": _astring(rng, rows, 10, 20, 20),
            f"{p}_street_2": _astring(rng, rows, 10, 20, 20),
            f"{p}_city": _astring(rng, rows, 10, 20, 20),
            f"{p}_state": _letters(rng, rows, 2), f"{p}_zip": _zip(rng, rows)}


def populate(scale: Scale, seed: int) -> Tuple[Dict[str, dict], int]:
    """The initial database of §4.3.3.1 as host arrays, and the C_LAST
    constant ``C_LOAD`` it was drawn with.  The append tables start
    empty (see the configuration's ``assumed``)."""
    rng = np.random.default_rng([seed, 0x7C0C])
    w_n, d_n, c_n, i_n = (scale.warehouses, scale.districts,
                          scale.customers, scale.items)
    c_load = int(rng.integers(0, 256))
    ar = np.arange
    i32 = np.int32
    item = {"i_id": ar(1, i_n + 1, dtype=i32),
            "i_im_id": rng.integers(1, 10_001, i_n).astype(i32),
            "i_name": _astring(rng, i_n, 14, 24, 24),
            "i_price": rng.integers(100, 10_001, i_n).astype(i32),
            "i_data": _with_original(rng, _astring(rng, i_n, 26, 50, 50,
                                                   128))}
    warehouse = {"w_id": ar(1, w_n + 1, dtype=i32),
                 "w_name": _astring(rng, w_n, 6, 10, 10),
                 **_address(rng, w_n, "w"),
                 "w_tax": rng.integers(0, 2_001, w_n).astype(i32),
                 "w_ytd": money(np.full(w_n, 30_000_000))}
    nd = w_n * d_n
    district = {"d_id": np.tile(ar(1, d_n + 1, dtype=i32), w_n),
                "d_w_id": np.repeat(ar(1, w_n + 1, dtype=i32), d_n),
                "d_name": _astring(rng, nd, 6, 10, 10),
                **_address(rng, nd, "d"),
                "d_tax": rng.integers(0, 2_001, nd).astype(i32),
                "d_ytd": money(np.full(nd, 3_000_000)),
                "d_next_o_id": np.full(nd, 3001, i32)}
    nc = nd * c_n
    c_id = np.tile(ar(1, c_n + 1, dtype=i32), nd)
    name = np.where(c_id <= 1000, c_id - 1,
                    nurand(rng, 255, 0, 999, c_load, nc))
    names = np.stack([_fixed([last_name(k)], 16)[0] for k in range(1000)])
    credit = np.where(rng.random(nc) < 0.1, b"BC", b"GC")
    customer = {
        "c_id": c_id,
        "c_d_id": np.tile(np.repeat(ar(1, d_n + 1, dtype=i32), c_n), w_n),
        "c_w_id": np.repeat(ar(1, w_n + 1, dtype=i32), d_n * c_n),
        "c_first": _astring(rng, nc, 8, 16, 16),
        "c_middle": np.tile(np.frombuffer(b"OE", np.uint8), (nc, 1)),
        "c_last": names[name],
        **_address(rng, nc, "c"),
        "c_phone": _nstring(rng, nc, 16),
        "c_since": np.zeros(nc, i32),
        "c_credit": np.frombuffer(credit.tobytes(), np.uint8).reshape(nc, 2),
        "c_credit_lim": money(np.full(nc, 5_000_000)),
        "c_discount": rng.integers(0, 5_001, nc).astype(i32),
        "c_balance": money(np.full(nc, -1_000)),
        "c_ytd_payment": money(np.full(nc, 1_000)),
        "c_payment_cnt": np.ones(nc, i32),
        "c_delivery_cnt": np.zeros(nc, i32),
        "c_data": _astring(rng, nc, 300, C_DATA, C_DATA, 512)}
    ns = w_n * i_n
    stock = {"s_i_id": np.tile(ar(1, i_n + 1, dtype=i32), w_n),
             "s_w_id": np.repeat(ar(1, w_n + 1, dtype=i32), i_n),
             "s_quantity": rng.integers(10, 101, ns).astype(i32),
             "s_dist": np.pad(_astring(rng, ns * 10, DIST, DIST, DIST
                                       ).reshape(ns, 10 * DIST),
                              [(0, 0), (0, 16)]),
             "s_ytd": np.zeros(ns, i32), "s_order_cnt": np.zeros(ns, i32),
             "s_remote_cnt": np.zeros(ns, i32),
             "s_data": _with_original(rng, _astring(rng, ns, 26, 50, 50,
                                                   128))}
    return ({"warehouse": warehouse, "district": district,
             "customer": customer, "item": item, "stock": stock}, c_load)


def name_directory(customer: dict, scale: Scale) -> np.ndarray:
    """The customer a by-name Payment selects (§2.5.2.2), as
    ``int32[W, D, names]`` of C_ID: among the district's customers with
    that C_LAST, sorted by C_FIRST (then by C_ID), the one at position
    ceil(n / 2).  C_LAST never changes in this mix, so the directory
    built once from the loaded table stays right."""
    code = {last_name(k): k for k in range(scale.names)}
    num = np.array([code.get(bytes(r).rstrip(b"\0"), -1)
                    for r in np.asarray(customer["c_last"])])
    first = np.asarray(customer["c_first"])
    w = np.asarray(customer["c_w_id"]) - 1
    d = np.asarray(customer["c_d_id"]) - 1
    cid = np.asarray(customer["c_id"])
    keys = [cid] + [first[:, k] for k in range(first.shape[1] - 1, -1, -1)]
    order = np.lexsort(keys + [num, d, w])
    out = np.full((scale.warehouses, scale.districts, scale.names), -1,
                  np.int32)
    group = np.stack([w[order], d[order], num[order]], 1)
    starts = np.r_[0, np.nonzero((group[1:] != group[:-1]).any(1))[0] + 1]
    ends = np.r_[starts[1:], len(order)]
    for s, e in zip(starts, ends):
        gw, gd, gn = group[s]
        if gn >= 0:
            out[gw, gd, gn] = cid[order[s + (e - s + 1) // 2 - 1]]
    return out


def log_capacity(txns: int, new_order_share: float) -> Dict[str, int]:
    """Rows each log needs for the expected inserts of ``txns``
    transactions of the mix (ORDER-LINE at 10 lines an order, the mean
    of 5..15)."""
    no = int(np.ceil(txns * new_order_share))
    return {"order": no, "new_order": no, "order_line": 10 * no,
            "history": int(np.ceil(txns * (1 - new_order_share)))}


# -- keys ---------------------------------------------------------------------

def rows_read(txn: dict, scale: Scale) -> Dict[str, jax.Array]:
    """Row indices each transaction reads, table by table (negative:
    none): WAREHOUSE, DISTRICT and CUSTOMER one each; a NewOrder's items
    and stock rows one per line."""
    d_n, c_n, i_n = scale.districts, scale.customers, scale.items
    no = txn["new_order"].astype(bool)
    line = no[:, None] & (jnp.arange(MAX_LINES)[None, :]
                          < txn["ol_cnt"][:, None])
    item = jnp.where(line, txn["i_id"] - 1, -1)
    return {"warehouse": (txn["w"] - 1)[:, None],
            "district": ((txn["w"] - 1) * d_n + txn["d"] - 1)[:, None],
            "customer": (((txn["c_w"] - 1) * d_n + txn["c_d"] - 1) * c_n
                         + txn["c"] - 1)[:, None],
            "item": item,
            "stock": jnp.where(line, (txn["supply_w"] - 1) * i_n + item, -1)}


def key_lists(txn: dict, scale: Scale) -> Tuple[jax.Array, jax.Array]:
    """``scheduler.tick``'s key lists: reads ``int32[n, 33]`` ``[W, D, C,
    I x 15, S x 15]``; writes ``int32[n, 16]``, NewOrder ``[D, S x
    15]`` (it reads W_TAX and the customer, and writes neither),
    Payment ``[W, D, C]`` then pads.  Negative ids pad."""
    b = bases(scale)
    rows = rows_read(txn, scale)
    key = {t: jnp.where(r >= 0, r + b[t], -1) for t, r in rows.items()}
    read = jnp.concatenate([key[t] for t in TABLES], 1)
    n = read.shape[0]
    pad = jnp.full((n, WRITE_KEYS - 3), -1, jnp.int32)
    no = txn["new_order"].astype(bool)[:, None]
    write = jnp.where(no, jnp.concatenate([key["district"], key["stock"]], 1),
                      jnp.concatenate([key["warehouse"], key["district"],
                                       key["customer"], pad], 1))
    return read.astype(jnp.int32), write.astype(jnp.int32)


# -- the two procedures -------------------------------------------------------

def _contains(data: jax.Array, pattern: np.ndarray) -> jax.Array:
    """bool[...]: the byte rows ``data[..., L]`` contain ``pattern``."""
    span = data.shape[-1] - len(pattern) + 1
    hit = jnp.ones(data.shape[:-1] + (span,), bool)
    for j, ch in enumerate(pattern):
        hit = hit & (data[..., j:j + span] == ch)
    return hit.any(-1)


def _digits(v: jax.Array, width: int) -> jax.Array:
    """uint8[..., width]: ``v`` in zero-padded decimal."""
    p = 10 ** jnp.arange(width - 1, -1, -1, dtype=jnp.int32)
    return ((v[..., None] // p) % 10 + ord("0")).astype(jnp.uint8)


def _one(x):
    return x[:, 0]


def new_order(rows: dict, txn: dict, now: jax.Array):
    """§2.4.2: the next order id from the district, one order line per
    item with the stock row updated, and the order's total.  Returns
    (writes, inserts, outputs) for every row of the batch; the caller
    keeps NewOrder rows only.  The first holds the new values of the
    columns it sets, table by table, ``[n, k, ...]`` in the order of
    the rows read."""
    with jax.named_scope("tpcc.new_order"):
        n = txn["w"].shape[0]
        wh, di, cu = (jax.tree.map(_one, rows[t])
                      for t in ("warehouse", "district", "customer"))
        it, st = rows["item"], rows["stock"]
        line = (jnp.arange(MAX_LINES)[None, :] < txn["ol_cnt"][:, None])
        line = line & txn["new_order"].astype(bool)[:, None]
        o_id = di["d_next_o_id"]
        qty = txn["qty"]
        remote = (txn["supply_w"] != txn["w"][:, None]).astype(jnp.int32)
        q = st["s_quantity"]
        stock = dict(s_quantity=jnp.where(q >= qty + 10, q - qty,
                                          q - qty + 91),
                     s_ytd=st["s_ytd"] + qty,
                     s_order_cnt=st["s_order_cnt"] + 1,
                     s_remote_cnt=st["s_remote_cnt"] + remote)
        amount = jnp.where(line, qty * it["i_price"], 0)
        brand = jnp.where(_contains(it["i_data"], ORIGINAL)
                          & _contains(st["s_data"], ORIGINAL),
                          ord("B"), ord("G")).astype(jnp.uint8)
        at = (txn["d"] - 1)[:, None, None] * DIST + jnp.arange(DIST)
        dist_info = jnp.take_along_axis(
            st["s_dist"], jnp.broadcast_to(at, (n, MAX_LINES, DIST)), 2)
        total = scale_round(scale_round(amount.sum(1),
                                        10_000 - cu["c_discount"]),
                            10_000 + wh["w_tax"] + di["d_tax"])
        all_local = (jnp.where(line, remote, 0).sum(1) == 0)
        district = {"d_next_o_id": o_id + 1}
        col = lambda v: v[:, None]                         # noqa: E731
        order = {"o_id": o_id, "o_d_id": txn["d"], "o_w_id": txn["w"],
                 "o_c_id": txn["c"], "o_entry_d": jnp.full(n, now),
                 "o_carrier_id": jnp.full(n, NULL),
                 "o_ol_cnt": txn["ol_cnt"],
                 "o_all_local": all_local.astype(jnp.int32)}
        lines = {"ol_o_id": jnp.broadcast_to(o_id[:, None], qty.shape),
                 "ol_d_id": jnp.broadcast_to(txn["d"][:, None], qty.shape),
                 "ol_w_id": jnp.broadcast_to(txn["w"][:, None], qty.shape),
                 "ol_number": jnp.broadcast_to(
                     jnp.arange(1, MAX_LINES + 1, dtype=jnp.int32),
                     qty.shape),
                 "ol_i_id": txn["i_id"], "ol_supply_w_id": txn["supply_w"],
                 "ol_delivery_d": jnp.full(qty.shape, NULL),
                 "ol_quantity": qty, "ol_amount": amount,
                 "ol_dist_info": dist_info}
        one = txn["new_order"].astype(jnp.int32)
        new = {"district": jax.tree.map(col, district), "stock": stock}
        inserts = {"order": (one, jax.tree.map(col, order)),
                   "new_order": (one, {"no_o_id": col(o_id),
                                       "no_d_id": col(txn["d"]),
                                       "no_w_id": col(txn["w"])}),
                   "order_line": (one * txn["ol_cnt"], lines)}
        outputs = {"o_id": o_id, "total": total,
                   "brand_generic": jnp.where(line, brand, 0)}
        return new, inserts, outputs


def _h_data(w_name: jax.Array, d_name: jax.Array) -> jax.Array:
    """W_NAME, four spaces, D_NAME, in 24 bytes (NUL after)."""
    lw = (w_name != 0).sum(-1, keepdims=True)
    ld = (d_name != 0).sum(-1, keepdims=True)
    j = jnp.arange(24)[None, :]
    wpart = jnp.take_along_axis(w_name, jnp.minimum(j, 9), 1)
    dpart = jnp.take_along_axis(d_name, jnp.clip(j - lw - 4, 0, 9), 1)
    return jnp.where(j < lw, wpart,
                     jnp.where(j < lw + 4, ord(" "),
                               jnp.where(j < lw + 4 + ld, dpart, 0))
                     ).astype(jnp.uint8)


def _c_data_prefix(txn: dict) -> jax.Array:
    """uint8[n, 29]: "CCCC DD WWWW dd wwww HHHH.hh "."""
    h = txn["h_amount"]
    sp = jnp.full(h.shape + (1,), ord(" "), jnp.uint8)
    dot = jnp.full(h.shape + (1,), ord("."), jnp.uint8)
    return jnp.concatenate(
        [_digits(txn["c"], 4), sp, _digits(txn["c_d"], 2), sp,
         _digits(txn["c_w"], 4), sp, _digits(txn["d"], 2), sp,
         _digits(txn["w"], 4), sp, _digits(h // 100, 4), dot,
         _digits(h % 100, 2), sp], -1)


def payment(rows: dict, txn: dict, now: jax.Array):
    """§2.5.2: the payment added to the warehouse's, district's and
    customer's year-to-date, taken from the customer's balance, a
    bad-credit customer's data prefixed, and a HISTORY row."""
    with jax.named_scope("tpcc.payment"):
        n = txn["w"].shape[0]
        wh, di, cu = (jax.tree.map(_one, rows[t])
                      for t in ("warehouse", "district", "customer"))
        h = txn["h_amount"]
        bad = ((cu["c_credit"][:, 0] == ord("B"))
               & (cu["c_credit"][:, 1] == ord("C")))
        width = cu["c_data"].shape[1]
        shifted = jnp.concatenate(
            [_c_data_prefix(txn), cu["c_data"][:, :C_DATA - C_DATA_PREFIX],
             jnp.zeros((n, width - C_DATA), jnp.uint8)], 1)
        customer = dict(
            c_balance=money_add(cu["c_balance"], -h),
            c_ytd_payment=money_add(cu["c_ytd_payment"], h),
            c_payment_cnt=cu["c_payment_cnt"] + 1,
            c_data=jnp.where(bad[:, None], shifted, cu["c_data"]))
        col = lambda v: v[:, None]                         # noqa: E731
        pay = (1 - txn["new_order"]).astype(jnp.int32)
        new = {"warehouse": {"w_ytd": money_add(wh["w_ytd"], h)},
               "district": {"d_ytd": money_add(di["d_ytd"], h)},
               "customer": customer}
        history = {"h_c_id": txn["c"], "h_c_d_id": txn["c_d"],
                   "h_c_w_id": txn["c_w"], "h_d_id": txn["d"],
                   "h_w_id": txn["w"], "h_date": jnp.full(n, now),
                   "h_amount": h,
                   "h_data": _h_data(wh["w_name"], di["d_name"])}
        inserts = {"history": (pay, jax.tree.map(col, history))}
        return (jax.tree.map(col, new), inserts,
                {"c_balance": customer["c_balance"]})


def logic(snapshot: dict, txn: dict, rows: dict, now: jax.Array):
    """Both procedures on every row of the batch, each row keeping its
    own class's writes, inserts and outputs (``txstore.Logic``)."""
    no = txn["new_order"].astype(bool)
    nnew, ni, nout = new_order(snapshot, txn, now)
    pnew, pi, pout = payment(snapshot, txn, now)
    pay_only = lambda t: jnp.where(no[:, None], -1, rows[t])  # noqa: E731
    # both classes write the district, each its own column
    old, sel = snapshot["district"], no[:, None]
    district = {"d_next_o_id": jnp.where(sel, nnew["district"]["d_next_o_id"],
                                         old["d_next_o_id"]),
                "d_ytd": jnp.where(sel[..., None], old["d_ytd"],
                                   pnew["district"]["d_ytd"])}
    writes = {"warehouse": (pay_only("warehouse"), pnew["warehouse"]),
              "district": (rows["district"], district),
              "customer": (pay_only("customer"), pnew["customer"]),
              "stock": (rows["stock"], nnew["stock"])}
    return writes, {**ni, **pi}, {**nout, **pout}


def apply_tick(store: txstore.KeyedStore, txn: dict, admitted: jax.Array,
               commit_rank: jax.Array, now: jax.Array, *, scale: Scale):
    """Execute a tick's admitted transactions of the backlog ``txn`` in
    ``commit_rank`` order.  Returns ``(store, outputs, counts)``,
    ``counts`` int32 ``[admitted, new_orders, payments, order_lines,
    overflow]`` (one read-back a tick)."""
    rank = jnp.where(admitted, commit_rank, -1)
    rows = rows_read(txn, scale)
    store, outputs, overflow = txstore.apply_keyed(
        store, rows, rank, lambda snap: logic(snap, txn, rows, now))
    no = admitted & txn["new_order"].astype(bool)
    counts = jnp.stack([admitted.sum(dtype=jnp.int32),
                        no.sum(dtype=jnp.int32),
                        (admitted & ~no).sum(dtype=jnp.int32),
                        jnp.where(no, txn["ol_cnt"], 0).sum(dtype=jnp.int32),
                        overflow.astype(jnp.int32)])
    return store, outputs, counts
