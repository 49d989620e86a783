"""Plain reference of one admission tick under PPCC, for the ``tick``
cells.  It imports nothing of the system under test.

A tick takes a backlog of pending transactions, row order being
priority, each given by the keys it reads and the keys it writes, and
decides which are admitted and in what order they commit:

1. Conflicts, pair by pair: ``raw[i][j]`` when some key read by ``i``
   is written by ``j`` (``i != j``): admitting both puts an arc
   ``i -> j``, ``i`` reading before ``j``'s write and so committing
   first.  Write-write overlap alone sets no order (the paper, Sec. 2.1).
2. The Prudent Precedence Rule, in priority order: a transaction with
   arcs to admitted ones (``out``) and from admitted ones (``into``) is
   admitted unless it would have both, unless one in ``out`` already
   precedes another, and unless one in ``into`` already follows
   another.  So no admitted transaction both precedes and follows, and
   every path of arcs has length one.
3. Commit order: the admitted transactions that follow no one, in
   priority order, then those that follow some, in priority order.
   ``order_violations`` counts the arcs between admitted transactions
   that this order, or any order given to it, runs backwards.
"""
from __future__ import annotations

from typing import List, Sequence, Set

import numpy as np


def key_sets(rows) -> List[Set[int]]:
    """Key lists (negative ids pad) as sets."""
    return [{int(k) for k in row if k >= 0} for row in np.asarray(rows)]


def conflicts(reads: Sequence[Set[int]], writes: Sequence[Set[int]]
              ) -> np.ndarray:
    """bool[n, n]: ``[i, j]`` when i reads a key that j writes, i != j."""
    n = len(reads)
    raw = np.zeros((n, n), bool)
    for i in range(n):
        if not reads[i]:
            continue
        for j in range(n):
            if j != i and not reads[i].isdisjoint(writes[j]):
                raw[i, j] = True
    return raw


def admit(raw: np.ndarray, valid: Sequence[bool]):
    """The Prudent Precedence Rule over the rows in priority order:
    (admitted, follows) as bool[n]."""
    n = raw.shape[0]
    admitted = np.zeros(n, bool)
    precedes = np.zeros(n, bool)
    follows = np.zeros(n, bool)
    for i in range(n):
        if not valid[i]:
            continue
        out = np.nonzero(admitted & raw[i])[0]
        into = np.nonzero(admitted & raw[:, i])[0]
        if len(out) and len(into):
            continue
        if precedes[out].any() or follows[into].any():
            continue
        admitted[i] = True
        for j in out:
            precedes[i] = follows[j] = True
        for k in into:
            precedes[k] = follows[i] = True
    return admitted, follows


def commit_rank(admitted: np.ndarray, follows: np.ndarray) -> np.ndarray:
    """int[n]: place in the commit order, -1 when not admitted."""
    rank = np.full(len(admitted), -1, np.int64)
    order = ([i for i in range(len(admitted)) if admitted[i]
              and not follows[i]]
             + [i for i in range(len(admitted)) if admitted[i]
                and follows[i]])
    for r, i in enumerate(order):
        rank[i] = r
    return rank


def order_violations(raw: np.ndarray, admitted, rank) -> int:
    """Arcs ``i -> j`` between admitted transactions with ``i`` not
    committing before ``j``."""
    admitted = np.asarray(admitted, bool)
    rank = np.asarray(rank)
    bad = 0
    for i, j in zip(*np.nonzero(raw)):
        if admitted[i] and admitted[j] and not rank[i] < rank[j]:
            bad += 1
    return bad


def tick(read_keys, write_keys, valid) -> dict:
    """One tick of the backlog given as key lists: ``admitted``,
    ``commit_rank`` and the conflicts it found (``raw``)."""
    raw = conflicts(key_sets(read_keys), key_sets(write_keys))
    admitted, follows = admit(raw, np.asarray(valid, bool))
    return {"admitted": admitted, "commit_rank": commit_rank(admitted,
                                                             follows),
            "raw": raw}
