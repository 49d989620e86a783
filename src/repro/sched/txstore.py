"""Transactional stores: where the transactions that ``scheduler.tick``
admits are executed, in its commit order.

Two forms share one rule, *snapshot reads and commit-order writes*:
every admitted transaction reads the state from before the tick, and
its writes land in ``commit_rank`` order, so that where two admitted
transactions write one place the later commit rank's write stays.
The rule is exact, not an approximation: an admitted transaction that
reads what another admitted one writes has a precedence arc to it, so
it commits first, and serial execution in commit order would have
shown it the same pre-tick value.

* **The dense page store** (``apply_tick``): ``pages`` is one
  ``[n_pages, page_size]`` array, a transaction is (read set, write
  set, payload) as page masks, and an admitted write is ``pages[w] +=
  payload[w]`` (delta) or ``pages[w] = payload[w]`` (overwrite).  It
  ticks the scheduler itself and folds over every page once per
  transaction, so it suits small page counts.
* **The keyed table store** (``KeyedStore``, ``apply_keyed``): named
  tables held on the device as columns (``{table: {column:
  array[rows, ...]}}``) and append-only logs with a fill count.  The
  caller gives the rows each transaction reads, table by table, and a
  ``logic`` function; ``apply_keyed`` gathers the rows from the
  snapshot (scope ``store.read``), runs ``logic`` on them (``store
  .logic``), then writes the columns it returns of the rows it names,
  the later commit rank winning each row, and appends its inserted
  rows to the logs in commit order (``store.write``).  Work is per key
  read or written, whatever the size of the tables.  A log that runs
  out is reported (``overflow``), never wrapped.
"""
from __future__ import annotations

from typing import Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from . import scheduler


class TxBatch(NamedTuple):
    read_sets: jax.Array     # bool[n, n_pages]
    write_sets: jax.Array    # bool[n, n_pages]
    payload: jax.Array       # f32[n, n_pages, page] (sparse-by-mask)
    additive: jax.Array      # bool[n]  (+= vs =)
    valid: jax.Array         # bool[n]


class TickStats(NamedTuple):
    admitted: jax.Array
    aborted: jax.Array
    n_admitted: jax.Array


def apply_tick(pages: jax.Array, batch: TxBatch, policy: str = "ppcc"
               ) -> Tuple[jax.Array, jax.Array, TickStats]:
    """One scheduling tick.

    Returns (new_pages, reads [n, n_pages, page] snapshot for admitted
    readers, stats).
    """
    res = scheduler.tick(batch.read_sets, batch.write_sets, batch.valid,
                         policy=policy)
    admitted = res.admitted
    # snapshot reads: all admitted transactions read the pre-tick state
    # (strict protocol: writes land at commit, after every read)
    read_mask = batch.read_sets & admitted[:, None]
    reads = jnp.where(read_mask[:, :, None], pages[None], 0.0)

    # apply writes in commit order: sort transactions by commit rank and
    # fold payloads (later rank overwrites / accumulates)
    n = batch.read_sets.shape[0]
    order = jnp.argsort(jnp.where(res.commit_rank < 0, 2 ** 30,
                                  res.commit_rank))

    def fold(pages, idx):
        w = batch.write_sets[idx] & admitted[idx]
        pay = batch.payload[idx]
        add = batch.additive[idx]
        updated = jnp.where(
            w[:, None], jnp.where(add, pages + pay, pay), pages)
        return updated, None

    pages, _ = jax.lax.scan(fold, pages, order)
    stats = TickStats(admitted=admitted, aborted=res.aborted,
                      n_admitted=admitted.sum())
    return pages, reads, stats


# -- the keyed table store ---------------------------------------------------

Columns = Dict[str, jax.Array]


class KeyedStore(NamedTuple):
    """Tables and logs on the device.  ``tables[t][c]`` is column ``c``
    of table ``t``, rows first; ``logs[t][c]`` likewise for an
    append-only log of ``capacity`` rows, of which ``fill[t]`` (int32
    scalar) are used.  A ``fill`` past the capacity means rows were
    dropped: the log ran out."""
    tables: Dict[str, Columns]
    logs: Dict[str, Columns]
    fill: Dict[str, jax.Array]


def new_store(tables: Dict[str, Columns], log_columns: Dict[str, Dict],
              capacity: Dict[str, int]) -> KeyedStore:
    """A store over ``tables`` (arrays, put on the device) with empty
    logs: ``log_columns[t]`` maps each column to ``(trailing shape,
    dtype)``, ``capacity[t]`` is the log's row count."""
    logs = {t: {c: jnp.zeros((capacity[t],) + tuple(shape), dtype)
                for c, (shape, dtype) in cols.items()}
            for t, cols in log_columns.items()}
    return KeyedStore(
        tables=jax.tree.map(jnp.asarray, tables), logs=logs,
        fill={t: jnp.zeros((), jnp.int32) for t in log_columns})


def read_rows(tables: Dict[str, Columns], rows: Dict[str, jax.Array]
              ) -> Dict[str, Columns]:
    """The rows ``rows[t]`` (int32 ``[n, k]`` row indices, negative for
    none) of every column of table ``t``, as ``[n, k, ...]``.  A
    negative index reads row 0; the caller masks it."""
    return {t: {c: col[jnp.maximum(r, 0)] for c, col in tables[t].items()}
            for t, r in rows.items()}


def write_rows(tables: Dict[str, Columns],
               writes: Dict[str, Tuple[jax.Array, Columns]],
               commit_rank: jax.Array) -> Dict[str, Columns]:
    """Write rows in commit order.  ``writes[t] = (rows, new)``: ``rows``
    int32 ``[n, k]`` (negative: no write), ``new[c]`` the rows' new
    values ``[n, k, ...]`` of the columns ``c`` the writers set; the
    table's other columns keep their values.  Transaction ``i`` writes
    only if ``commit_rank[i] >= 0``; where entries name one row, the
    highest commit rank's values are the ones that stay."""
    out = dict(tables)
    for t, (rows, new) in writes.items():
        n_rows = next(iter(tables[t].values())).shape[0]
        rank = jnp.broadcast_to(commit_rank[:, None], rows.shape)
        ok = (rows >= 0) & (rank >= 0)
        r = jnp.where(ok, rows, n_rows).reshape(-1)
        k = jnp.where(ok, rank, -1).reshape(-1)
        last = jnp.full((n_rows,), -1, jnp.int32).at[r].max(k, mode="drop")
        win = ok.reshape(-1) & (last[jnp.minimum(r, n_rows - 1)] == k)
        target = jnp.where(win, r, n_rows)
        out[t] = dict(tables[t])
        for c, v in new.items():
            col = tables[t][c]
            out[t][c] = col.at[target].set(
                v.reshape((-1,) + col.shape[1:]), mode="drop")
    return out


def append_rows(logs: Dict[str, Columns], fill: Dict[str, jax.Array],
                inserts: Dict[str, Tuple[jax.Array, Columns]],
                commit_rank: jax.Array):
    """Append inserted rows in commit order.  ``inserts[t] = (count,
    new)``: transaction ``i`` inserts rows ``new[c][i, :count[i]]`` if
    ``commit_rank[i] >= 0``.  Returns ``(logs, fill, overflow)``, the
    last true when a log ran out (its rows past the capacity are
    dropped and its ``fill`` passes the capacity)."""
    n = commit_rank.shape[0]
    order = jnp.argsort(jnp.where(commit_rank >= 0, commit_rank, n))
    logs, fill = dict(logs), dict(fill)
    overflow = jnp.zeros((), bool)
    for t, (count, new) in inserts.items():
        cap = next(iter(logs[t].values())).shape[0]
        m = next(iter(new.values())).shape[1]
        cnt = jnp.where(commit_rank >= 0, count, 0).astype(jnp.int32)
        start = jnp.zeros(n, jnp.int32).at[order].set(
            jnp.cumsum(cnt[order]) - cnt[order])
        j = jnp.arange(m, dtype=jnp.int32)
        pos = fill[t] + start[:, None] + j[None, :]
        pos = jnp.where(j[None, :] < cnt[:, None], pos, cap).reshape(-1)
        logs[t] = {c: col.at[pos].set(
                       new[c].reshape((-1,) + col.shape[1:]), mode="drop")
                   for c, col in logs[t].items()}
        fill[t] = fill[t] + cnt.sum()
        overflow = overflow | (fill[t] > cap)
    return logs, fill, overflow


Logic = Callable[[Dict[str, Columns]], Tuple[dict, dict, dict]]


def apply_keyed(store: KeyedStore, rows: Dict[str, jax.Array],
                commit_rank: jax.Array, logic: Logic):
    """Execute one tick's admitted transactions against the store.

    ``rows[t]`` (int32 ``[n, k]``, negative for none) are the rows each
    of the ``n`` transactions reads from table ``t``; ``commit_rank``
    (int32 ``[n]``, -1 when not admitted) is the tick's commit order.
    ``logic(snapshot) -> (writes, inserts, outputs)`` computes every
    transaction's result from its rows of the pre-tick snapshot:
    ``writes`` as ``write_rows`` takes them, ``inserts`` as
    ``append_rows`` does, ``outputs`` any pytree returned as it is.
    Returns ``(store, outputs, overflow)``."""
    with jax.named_scope("store.read"):
        snapshot = read_rows(store.tables, rows)
    with jax.named_scope("store.logic"):
        writes, inserts, outputs = logic(snapshot)
    with jax.named_scope("store.write"):
        tables = write_rows(store.tables, writes, commit_rank)
        logs, fill, overflow = append_rows(store.logs, store.fill,
                                           inserts, commit_rank)
    return KeyedStore(tables, logs, fill), outputs, overflow
