"""PPCC conflict-matrix Pallas kernel.

The batch scheduler admits thousands of concurrent transactions whose
read/write sets are packed bitsets ``uint32[N, W]`` (W = items / 32).
The hot spot is the pairwise conflict matrix

    raw[i, j] = any(read[i] & write[j])      (i reads what j wrote)

(and its transpose for WAR).  This kernel tiles [bi, bj] transaction
pairs into VMEM and reduces over the word dimension one word at a
time against the transposed right-hand side (``uint32[W, N]``, one
word per sublane row); the bitwise AND + OR-reduce runs on the VPU.

VMEM per step: (bi + bj) x W x 4B + bi x bj x 4B accumulator; with
bi = bj = 256 and W <= 1024 (32k items) this is ~2.3 MiB.

A keyspace of millions of records has no bitset row worth forming:
``conflict_keys`` takes each transaction's sets as short key-id lists
and compares keys instead of words, emitting the same relations.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# the protocol-wide packer lives in core.bitset; re-exported here so
# kernel callers keep their historical import path
from ..core.bitset import pack as pack_bitsets  # noqa: F401


def _overlap(a, bt, words: int):
    """uint32[bi, W] x uint32[W, bj] -> bool[bi, bj]: any shared bit.
    A static loop over the words of broadcast AND — the right-hand
    side arrives transposed, so each word is one sublane row and no
    3-D intermediate is formed."""
    acc = jnp.zeros((a.shape[0], bt.shape[1]), jnp.bool_)
    for w in range(words):
        acc = acc | ((a[:, w:w + 1] & bt[w:w + 1, :]) != 0)
    return acc


def _conflict_kernel(a_ref, bt_ref, o_ref, *, words: int):
    o_ref[...] = _overlap(a_ref[...], bt_ref[...], words)


def conflict_matrix(read_bits: jax.Array, write_bits: jax.Array, *,
                    block: int = 256, interpret: bool = False
                    ) -> jax.Array:
    """read_bits/write_bits uint32[N, W] -> bool[N, N] where
    out[i, j] = read set of i intersects write set of j."""
    n, w = read_bits.shape
    assert write_bits.shape == (n, w)
    bi = min(block, n)
    assert n % bi == 0, (n, bi)
    grid = (n // bi, n // bi)
    kernel = functools.partial(_conflict_kernel, words=w)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bi, w), lambda i, j: (i, 0)),
            pl.BlockSpec((w, bi), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bi, bi), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, n), jnp.bool_),
        interpret=interpret,
    )(read_bits, write_bits.T)


def _tile_spec(b: int):
    return pl.BlockSpec((b, b), lambda i, j: (i, j))


def _row_spec(b: int):
    """Per-row vectors travel as ``int32[N, 1]`` in ``(b, 1)`` blocks:
    a 1-D ``(b,)`` block is tiled T(b) by Mosaic but T(N) by XLA, which
    clash for N > b."""
    return pl.BlockSpec((b, 1), lambda i, j: (i, 0))


def _fold_rows(deg_ref, rel, j):
    """Accumulate a tile's per-row popcounts into the ``(b, 1)`` degree
    block, which stays resident while ``j`` (the fastest grid axis)
    sweeps the row's tiles; zeroed at ``j == 0``."""
    @pl.when(j == 0)
    def _init():
        deg_ref[...] = jnp.zeros(deg_ref.shape, jnp.int32)

    deg_ref[...] += rel.astype(jnp.int32).sum(axis=1, keepdims=True)


def _conflict_fused_kernel(r_ref, wi_ref, wj_ref, raw_ref, ww_ref,
                           rdeg_ref, wdeg_ref, *, words: int):
    """One pass over the word dimension emits BOTH conflict relations —
    raw[i, j] = any(read[i] & write[j]) and ww[i, j] = any(write[i] &
    write[j]) — plus per-row popcount degrees, accumulated across the j
    grid dimension (same output block revisited; j iterates fastest)."""
    j = pl.program_id(1)
    wj = wj_ref[...]                            # uint32[W, bj] (write^T)
    raw_acc = _overlap(r_ref[...], wj, words)
    ww_acc = _overlap(wi_ref[...], wj, words)
    raw_ref[...] = raw_acc
    ww_ref[...] = ww_acc
    _fold_rows(rdeg_ref, raw_acc, j)
    _fold_rows(wdeg_ref, ww_acc, j)


def conflict_fused(read_bits: jax.Array, write_bits: jax.Array, *,
                   block: int = 256, interpret: bool = False):
    """Single-launch fusion of ``conflict_matrix(rb, wb)`` and
    ``conflict_matrix(wb, wb)``.

    Returns (raw bool[N, N], ww bool[N, N], raw_deg int32[N],
    ww_deg int32[N]); degrees are per-row popcounts INCLUDING the
    diagonal (callers mask self-conflicts as they see fit).  Bit-wise
    identical to the two separate launches; the fused pass reads each
    write-bitset tile once for both relations instead of twice.
    """
    n, w = read_bits.shape
    assert write_bits.shape == (n, w)
    bi = min(block, n)
    assert n % bi == 0, (n, bi)
    grid = (n // bi, n // bi)
    kernel = functools.partial(_conflict_fused_kernel, words=w)
    raw, ww, rdeg, wdeg = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bi, w), lambda i, j: (i, 0)),
            pl.BlockSpec((bi, w), lambda i, j: (i, 0)),
            pl.BlockSpec((w, bi), lambda i, j: (0, j)),
        ],
        out_specs=[_tile_spec(bi), _tile_spec(bi),
                   _row_spec(bi), _row_spec(bi)],
        out_shape=[
            jax.ShapeDtypeStruct((n, n), jnp.bool_),
            jax.ShapeDtypeStruct((n, n), jnp.bool_),
            jax.ShapeDtypeStruct((n, 1), jnp.int32),
            jax.ShapeDtypeStruct((n, 1), jnp.int32),
        ],
        interpret=interpret,
    )(read_bits, write_bits, write_bits.T)
    return raw, ww, rdeg[:, 0], wdeg[:, 0]


def _emit_full(raw, ww, raw_ref, ww_ref, rdeg_ref, cdeg_ref, wdeg_ref,
               dr_ref, dw_ref):
    """Write one [bi, bj] tile of ``raw`` and ``ww`` and fold it into
    the degree outputs.  Row degrees and diagonals ride the resident
    ``(b, 1)`` row blocks.  The column degree of tile (i, j) is a
    partial sum of its own (``cdeg_ref`` is the (1, bj) block of row i
    of an ``int32[N/b, 1, N]`` output, summed over i by the caller): an
    output block is written back when its index changes and never read
    again, so a column block cannot accumulate across the slower ``i``
    axis.  The diagonals are masked row reductions of the ``i == j``
    tile, as int32 0/1 (Mosaic refuses the ``jnp.diagonal`` gather)."""
    i, j = pl.program_id(0), pl.program_id(1)
    raw_ref[...] = raw
    ww_ref[...] = ww
    _fold_rows(rdeg_ref, raw, j)
    _fold_rows(wdeg_ref, ww, j)
    cdeg_ref[...] = raw.astype(jnp.int32).sum(axis=0, keepdims=True)

    @pl.when(j == 0)
    def _init_diag():
        dr_ref[...] = jnp.zeros(dr_ref.shape, jnp.int32)
        dw_ref[...] = jnp.zeros(dw_ref.shape, jnp.int32)

    @pl.when(i == j)
    def _diag():
        shape = raw.shape
        eye = jax.lax.broadcasted_iota(jnp.int32, shape, 0) == \
            jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        dr_ref[...] = jnp.where(raw & eye, 1, 0).max(axis=1, keepdims=True)
        dw_ref[...] = jnp.where(ww & eye, 1, 0).max(axis=1, keepdims=True)


def _full_call(kernel, n: int, b: int, in_specs, name: str, interpret,
               *operands):
    """The one pallas_call of both 7-output kernels: grid (N/b, N/b),
    j fastest; returns (raw, ww, raw_deg, war_deg, ww_deg, diag_raw,
    diag_ww) with the vectors as ``[N]``."""
    g = n // b
    row = _row_spec(b)
    vec = jax.ShapeDtypeStruct((n, 1), jnp.int32)
    raw, ww, rdeg, cdeg, wdeg, dr, dw = pl.pallas_call(
        kernel,
        grid=(g, g),
        in_specs=in_specs,
        out_specs=[_tile_spec(b), _tile_spec(b), row,
                   pl.BlockSpec((None, 1, b), lambda i, j: (i, 0, j)),
                   row, row, row],
        out_shape=[
            jax.ShapeDtypeStruct((n, n), jnp.bool_),
            jax.ShapeDtypeStruct((n, n), jnp.bool_),
            vec, jax.ShapeDtypeStruct((g, 1, n), jnp.int32), vec, vec, vec,
        ],
        interpret=interpret,
        name=name,
    )(*operands)
    return (raw, ww, rdeg[:, 0], cdeg.sum(axis=(0, 1)), wdeg[:, 0],
            dr[:, 0] != 0, dw[:, 0] != 0)


def _conflict_fused_full_kernel(r_ref, wi_ref, wj_ref, *out_refs,
                                words: int):
    """``conflict_fused`` plus the WAR *column* degrees and the two
    diagonals — everything degree-ordered admission consumes, one
    launch (see ``_emit_full``)."""
    wj = wj_ref[...]                            # uint32[W, bj] (write^T)
    _emit_full(_overlap(r_ref[...], wj, words),
               _overlap(wi_ref[...], wj, words), *out_refs)


def conflict_fused_full(read_bits: jax.Array, write_bits: jax.Array, *,
                        block: int = 256, interpret: bool = False):
    """Single launch → (raw, ww, raw_deg, war_deg, ww_deg, diag_raw,
    diag_ww); bit-identical to ``ref.conflict_fused_full_ref``.  The
    extra column-degree and diagonal outputs make degree-ordered
    admission (``sched.scheduler.ppcc_tick(order="degree")``) a
    one-launch tick end to end — no second pass over the materialised
    ``raw`` to form the ordering key."""
    n, w = read_bits.shape
    assert write_bits.shape == (n, w)
    bi = min(block, n)
    assert n % bi == 0, (n, bi)
    kernel = functools.partial(_conflict_fused_full_kernel, words=w)
    return _full_call(
        kernel, n, bi,
        [pl.BlockSpec((bi, w), lambda i, j: (i, 0)),
         pl.BlockSpec((bi, w), lambda i, j: (i, 0)),
         pl.BlockSpec((w, bi), lambda i, j: (0, j))],
        "conflict_fused_full", interpret,
        read_bits, write_bits, write_bits.T)


# -- key lists --------------------------------------------------------------

PAD = -1
"""A key list's pad id.  Every negative id is a pad, and a pad matches
nothing, not even another pad."""


def _key_overlap(a, bt):
    """int32[bi, ka] x int32[kb, bj] -> bool[bi, bj]: some key of row
    i's list equals some key of column j's list.  Left pads become -1
    and right pads -2, so no pad meets an equal id on the other side;
    then ka x kb broadcast compares of a key column against a key row
    (the right-hand side arrives transposed, one key per sublane row)."""
    a = jnp.where(a >= 0, a, -1)
    bt = jnp.where(bt >= 0, bt, -2)
    acc = jnp.zeros((a.shape[0], bt.shape[1]), jnp.bool_)
    for x in range(a.shape[1]):
        col = a[:, x:x + 1]
        for y in range(bt.shape[0]):
            acc = acc | (col == bt[y:y + 1, :])
    return acc


def _conflict_keys_kernel(r_ref, wi_ref, wj_ref, *out_refs):
    wj = wj_ref[...]                            # int32[kw, bj] (write^T)
    _emit_full(_key_overlap(r_ref[...], wj),
               _key_overlap(wi_ref[...], wj), *out_refs)


def conflict_keys(read_keys: jax.Array, write_keys: jax.Array, *,
                  block: int = 256, interpret: bool = False):
    """The conflict relations of transactions given as key lists:
    ``read_keys int32[N, kr]``, ``write_keys int32[N, kw]`` (negative
    ids are pads).  One launch → the same 7-tuple as
    ``conflict_fused_full``: raw[i, j] = some read key of i equals some
    write key of j, ww[i, j] = some write key of i equals some write
    key of j, then raw_deg, war_deg, ww_deg, diag_raw, diag_ww, the
    degrees counting the diagonal.  Work is N² x kr x kw compares
    (plus N² x kw² for ww), whatever the keyspace: no bitset row of the
    keyspace is ever formed.  ``N`` is a multiple of ``block`` (or at
    most it); bit-identical to ``ref.conflict_keys_ref``."""
    n, kr = read_keys.shape
    kw = write_keys.shape[1]
    assert write_keys.shape[0] == n
    bi = min(block, n)
    assert n % bi == 0, (n, bi)
    return _full_call(
        _conflict_keys_kernel, n, bi,
        [pl.BlockSpec((bi, kr), lambda i, j: (i, 0)),
         pl.BlockSpec((bi, kw), lambda i, j: (i, 0)),
         pl.BlockSpec((kw, bi), lambda i, j: (0, j))],
        "conflict_keys", interpret,
        read_keys, write_keys, write_keys.T)
