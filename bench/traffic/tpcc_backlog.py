"""TPC-C NewOrder and Payment inputs (TPC-C v5.11, §2.4.1 and §2.5.1)
for a closed-loop admission backlog.

The stream: each transaction is a NewOrder with probability
``new_order_share``, else a Payment; its home warehouse and district
are uniform.

* NewOrder: customer ``NURand(1023, 1, customers)``, 5..15 order lines
  of distinct items ``NURand(8191, 1, items)`` (an item drawn twice is
  drawn again), each of quantity 1..10 and supplied by a remote
  warehouse (uniform among the others) with probability 1%.
* Payment: the customer's warehouse is the home one with probability
  85%, else uniform among the others, with a uniform district; 60% of
  Payments select the customer by last name ``NURand(255, 0, names -
  1)`` (``c_last`` >= 0, resolved to an id by the database before
  sequencing), the rest by id ``NURand(1023, 1, customers)``; the
  amount is uniform in 1.00..5 000.00 (cents).

The run-time constants C of NURand (§2.1.6.1): for C_ID and OL_I_ID
uniform in 0..A; for C_LAST uniform in 0..255 with |C_RUN - C_LOAD| in
65..119 and not 96 or 112, ``C_LOAD`` being the population's.  Draws
come from numpy's PCG64 seeded by ``--seed``.

The loop: one caller keeps a backlog of ``backlog`` pending
transactions, oldest first; row order is priority.  After each tick
the admitted rows leave, the rest keep their order, and the next
stream rows fill the tail (``refill``, on the device).  Running out of
stream is an error, never a wrap-around.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

MAX_LINES = 15


def nurand(rng, a: int, x: int, y: int, c: int, size) -> np.ndarray:
    """NURand(A, x, y) = (((random(0, A) | random(x, y)) + C) %
    (y - x + 1)) + x."""
    r = rng.integers(0, a + 1, size) | rng.integers(x, y + 1, size)
    return (r + c) % (y - x + 1) + x


def c_last_run(rng, c_load: int) -> int:
    """A C_RUN for C_LAST at an allowed distance from C_LOAD."""
    while True:
        c = int(rng.integers(0, 256))
        delta = abs(c - c_load)
        if 65 <= delta <= 119 and delta not in (96, 112):
            return c


def _other(rng, w: np.ndarray, warehouses: int) -> np.ndarray:
    """A warehouse other than ``w``, uniform (``w`` itself with one)."""
    if warehouses == 1:
        return w.copy()
    o = rng.integers(1, warehouses, len(w))
    return np.where(o >= w, o + 1, o)


def repeats(x: np.ndarray) -> np.ndarray:
    """bool mask of every entry equal to an earlier entry of its row."""
    order = np.argsort(x, axis=1, kind="stable")
    srt = np.take_along_axis(x, order, axis=1)
    later = np.zeros(x.shape, bool)
    later[:, 1:] = srt[:, 1:] == srt[:, :-1]
    dup = np.zeros(x.shape, bool)
    np.put_along_axis(dup, order, later, axis=1)
    return dup


def distinct_items(rng, rows: int, items: int, c: int) -> np.ndarray:
    """int[rows, 15] of ``NURand(8191, 1, items)``, an item that repeats
    in its row drawn again."""
    out = nurand(rng, 8191, 1, items, c, (rows, MAX_LINES))
    dup = repeats(out)
    while dup.any():
        out[dup] = nurand(rng, 8191, 1, items, c, int(dup.sum()))
        dup = repeats(out)
    return out


def stream(config: dict, traffic: dict, seed: int, c_load: int) -> dict:
    """The run's transactions, oldest first, as int32 columns: the
    fields of ``repro.sched.tpcc.TXN_FIELDS`` (``c`` 0 where a Payment
    selects by name) and ``c_last`` (-1 where by id)."""
    rng = np.random.default_rng([seed, 0x7C0D])
    s = int(traffic["stream_txns"])
    wn = int(config["warehouses"])
    dn = int(config["districts_per_warehouse"])
    cn = int(config["customers_per_district"])
    items = int(config["items"])
    names = min(1000, cn)
    c_id, c_item = (int(rng.integers(0, 1024)), int(rng.integers(0, 8192)))
    c_last = c_last_run(rng, c_load)
    no = rng.random(s) < float(config["new_order_share"])
    w = rng.integers(1, wn + 1, s)
    d = rng.integers(1, dn + 1, s)
    ol_cnt = rng.integers(5, MAX_LINES + 1, s)
    line = np.arange(MAX_LINES)[None, :] < ol_cnt[:, None]
    i_id = distinct_items(rng, s, items, c_item)
    remote = rng.random((s, MAX_LINES)) < 0.01
    supply = np.where(remote, _other(rng, np.repeat(w, MAX_LINES), wn
                                     ).reshape(s, MAX_LINES), w[:, None])
    qty = rng.integers(1, 11, (s, MAX_LINES))
    home = rng.random(s) < 0.85
    c_w = np.where(home, w, _other(rng, w, wn))
    c_d = np.where(home, d, rng.integers(1, dn + 1, s))
    by_name = ~no & (rng.random(s) < 0.6)
    cust = nurand(rng, 1023, 1, cn, c_id, s)
    name = nurand(rng, 255, 0, names - 1, c_last, s)
    keep = no[:, None] & line
    cols = {"new_order": no, "w": w, "d": d,
            "c": np.where(by_name, 0, cust),
            "c_w": np.where(no, w, c_w), "c_d": np.where(no, d, c_d),
            "ol_cnt": np.where(no, ol_cnt, 0),
            "i_id": np.where(keep, i_id, 0),
            "supply_w": np.where(keep, supply, 0),
            "qty": np.where(keep, qty, 0),
            "h_amount": np.where(no, 0, rng.integers(100, 500_001, s)),
            "c_last": np.where(by_name, name, -1)}
    return {k: v.astype(np.int32) for k, v in cols.items()}


@jax.jit
def _refill(backlog: dict, admitted, stream: dict, pos):
    n = admitted.shape[0]
    stay = jnp.argsort(admitted, stable=True)   # staying rows, in order
    n_stay = n - admitted.sum(dtype=jnp.int32)
    row = jnp.arange(n, dtype=jnp.int32)
    new = row >= n_stay
    src = jnp.clip(row - n_stay, 0, n - 1)

    def one(old, fresh):
        fresh = jax.lax.dynamic_slice_in_dim(fresh, pos, n)
        mask = new.reshape((n,) + (1,) * (old.ndim - 1))
        return jnp.where(mask, fresh[src], old[stay])
    return jax.tree.map(one, backlog, stream)


def refill(backlog: dict, admitted, stream: dict, pos: int) -> dict:
    """The backlog after a tick: the rows not ``admitted`` first, in
    their order, then stream rows ``pos, pos + 1, ...`` in the freed
    tail.  Refuses a ``pos`` whose backlog-long slice would pass the
    stream's end (the slice would be clamped, repeating rows)."""
    total = next(iter(stream.values())).shape[0]
    if pos + admitted.shape[0] > total:
        raise RuntimeError(f"the stream of {total} transactions ran out "
                           f"at row {pos}")
    return _refill(backlog, admitted, stream, jnp.int32(pos))
