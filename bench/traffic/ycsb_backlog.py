"""YCSB workload A transactions for a closed-loop admission backlog.

The stream: each transaction holds ``keys_per_txn`` distinct keys of a
table of ``records`` rows; each key is read, or updated (a
read-modify-write, so it is in the read and the write set) with
probability ``update_share``.  Keys follow YCSB's
``ScrambledZipfianGenerator`` (Cooper et al., SoCC 2010): a zipfian
rank over YCSB's 10^10 items with its constant 0.99 and precomputed
zeta, drawn by Gray et al.'s method ("Quickly generating billion-record
synthetic databases", SIGMOD 1994) exactly as YCSB's
``ZipfianGenerator`` draws it, in float64; the rank is scrambled with
YCSB's 64-bit FNV-1a ``fnvhash64`` modulo ``records + 1`` (YCSB's item
count for keys 0..records), and a key past the table is drawn again,
as ``CoreWorkload.nextKeynum`` does.  A key that repeats within a
transaction is drawn again (DBx1000's YCSB).  The uniform draws come
from numpy's PCG64 seeded by ``--seed``, not from Java's
``ThreadLocalRandom``.

The loop: one caller keeps a backlog of ``backlog`` pending
transactions, oldest first; row order is priority.  After each tick the
admitted rows leave, the rest keep their order, and the next stream
rows fill the tail (``refill``, jitted, on the device).  The first
``burn_in_ticks`` ticks of the loop run in set-up, so that the window
starts from the loop's steady backlog.  Running out of stream is an
error, never a wrap-around.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

PAD = -1                    # a write list's pad: the key is only read

# YCSB's ScrambledZipfianGenerator: ITEM_COUNT, USED_ZIPFIAN_CONSTANT
# and ZETAN = zeta(ITEM_COUNT, 0.99)
YCSB_ITEMS = 10_000_000_000
YCSB_THETA = 0.99
YCSB_ZETAN = 26.46902820178302

FNV_OFFSET = np.uint64(0xCBF29CE484222325)
FNV_PRIME = np.uint64(1099511628211)


def zeta(n: int, theta: float) -> float:
    return float(np.sum(1.0 / np.arange(1, n + 1, dtype=np.float64)
                        ** theta))


def zipf_ranks(rng: np.random.Generator, size, theta: float = YCSB_THETA,
               items: int = YCSB_ITEMS + 1, zetan: float = YCSB_ZETAN
               ) -> np.ndarray:
    """Zipfian ranks 0, 1, ... (0 the most frequent), as YCSB's
    ``ZipfianGenerator.nextLong`` draws them.  The scrambled generator
    builds its ``ZipfianGenerator(0, ITEM_COUNT, ...)``, so ``items``
    is ITEM_COUNT + 1."""
    alpha = 1.0 / (1.0 - theta)
    eta = ((1.0 - (2.0 / items) ** (1.0 - theta))
           / (1.0 - zeta(2, theta) / zetan))
    u = rng.random(size)
    uz = u * zetan
    rank = (items * (eta * u - eta + 1.0) ** alpha).astype(np.int64)
    rank = np.where(uz < 1.0 + 0.5 ** theta, 1, rank)
    return np.where(uz < 1.0, 0, rank)


def fnvhash64(x: np.ndarray) -> np.ndarray:
    """YCSB's ``Utils.fnvhash64``: FNV-1a over the 8 low-first octets,
    then Java's ``Math.abs`` of the signed result (int64)."""
    v = np.asarray(x, np.int64).view(np.uint64)
    h = np.full(v.shape, FNV_OFFSET, np.uint64)
    octet = np.empty_like(v)
    with np.errstate(over="ignore"):
        for i in range(8):
            np.right_shift(v, np.uint64(8 * i), out=octet)
            octet &= np.uint64(0xFF)
            h ^= octet
            h *= FNV_PRIME
    s = h.view(np.int64)
    return np.where(s < 0, -s, s)   # Java's abs: Long.MIN_VALUE stays


def scrambled_keys(rng: np.random.Generator, size, records: int
                   ) -> np.ndarray:
    """Keys of ``CoreWorkload`` with ``requestdistribution=zipfian``:
    ``fnvhash64(rank) % (records + 1)``, drawn again while past the
    table (or negative, from ``Math.abs(Long.MIN_VALUE)``)."""
    keys = np.fmod(fnvhash64(zipf_ranks(rng, size)), records + 1)
    bad = (keys < 0) | (keys >= records)
    while bad.any():
        keys[bad] = np.fmod(fnvhash64(zipf_ranks(rng, int(bad.sum()))),
                            records + 1)
        bad = (keys < 0) | (keys >= records)
    return keys


def repeats(keys: np.ndarray) -> np.ndarray:
    """bool mask of every key equal to an earlier key of its row."""
    order = np.argsort(keys, axis=1, kind="stable")
    srt = np.take_along_axis(keys, order, axis=1)
    dup = np.zeros(keys.shape, bool)
    later = np.zeros(keys.shape, bool)
    later[:, 1:] = srt[:, 1:] == srt[:, :-1]
    np.put_along_axis(dup, order, later, axis=1)
    return dup


def stream(config: dict, traffic: dict, seed: int):
    """The run's transactions, oldest first: ``keys int32[S, k]`` (all
    read) and ``update bool[S, k]`` (which keys are also written)."""
    rng = np.random.default_rng([seed, 0x7C5B])
    s, k = int(traffic["stream_txns"]), int(config["keys_per_txn"])
    records = int(config["records"])
    keys = scrambled_keys(rng, (s, k), records)
    rows = np.arange(s)
    while len(rows):
        dup = repeats(keys[rows])
        rows = rows[dup.any(axis=1)]
        dup = dup[dup.any(axis=1)]
        part = keys[rows]
        part[dup] = scrambled_keys(rng, int(dup.sum()), records)
        keys[rows] = part
    update = rng.random((s, k)) < float(config["update_share"])
    return keys.astype(np.int32), update


def sets(keys, update):
    """(read keys, write keys) of rows: every key is read; an updated
    one is written too, the others are write-list pads."""
    return keys, jnp.where(update, keys, PAD)


@jax.jit
def _refill(read_keys, write_keys, admitted, keys, update, pos):
    n = read_keys.shape[0]
    stay = jnp.argsort(admitted, stable=True)   # staying rows, in order
    n_stay = n - admitted.sum(dtype=jnp.int32)
    fresh_r, fresh_w = sets(
        jax.lax.dynamic_slice_in_dim(keys, pos, n),
        jax.lax.dynamic_slice_in_dim(update, pos, n))
    row = jnp.arange(n, dtype=jnp.int32)
    new = (row >= n_stay)[:, None]
    src = jnp.clip(row - n_stay, 0, n - 1)
    return (jnp.where(new, fresh_r[src], read_keys[stay]),
            jnp.where(new, fresh_w[src], write_keys[stay]))


def refill(read_keys, write_keys, admitted, keys, update, pos: int):
    """The backlog after a tick: the rows not ``admitted`` first, in
    their order, then stream rows ``pos, pos + 1, ...`` in the freed
    tail.  Refuses a ``pos`` whose backlog-long slice would pass the
    stream's end (the slice would be clamped, repeating rows)."""
    if pos + read_keys.shape[0] > keys.shape[0]:
        raise RuntimeError(f"the stream of {keys.shape[0]} transactions "
                           f"ran out at row {pos}")
    return _refill(read_keys, write_keys, admitted, keys, update,
                   jnp.int32(pos))
