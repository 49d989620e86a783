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
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# the protocol-wide packer lives in core.bitset; re-exported here so
# kernel callers keep their historical import path
from ..core import bitset as B
from ..core.bitset import pack as pack_bitsets  # noqa: F401


def rowslab(read_bits: jax.Array, write_bits: jax.Array,
            writers_at: jax.Array, readers_at: jax.Array,
            item: jax.Array, is_write: jax.Array, active: jax.Array,
            slab: jax.Array, valid: jax.Array):
    """jnp twin of the (K, n) dirty-row slab kernel (DESIGN.md §3.2).

    Recomputes only the K relation rows named by ``slab`` against the
    full new state: fresh op-table rows come from the packed words, the
    party matrix is rebuilt from the CARRIED ``writers_at``/
    ``readers_at`` with the slab rows substituted (clean rows of the
    carried tables are exact by the dirty-row rule), and the dep join
    is a (K, nw) x (n, nw) packed overlap instead of the full
    (n, nw) self-join.  Bit-identical to ``ref.rowslab_ref``.

    Returns (dep_rows, ww_rows, wat_rows, rat_rows), each bool[K, n];
    rows with ``~valid`` are zeroed (callers scatter with OOB drop).
    """
    n = read_bits.shape[0]
    sl = jnp.clip(slab, 0, n - 1)
    s_item = item[sl]
    wat_rows = B.item_cols(write_bits, s_item)           # [K, n]
    rat_rows = B.item_cols(read_bits, s_item)
    tgt = jnp.where(valid, sl, n)                        # OOB drop pads
    wat2 = writers_at.at[tgt].set(wat_rows, mode="drop")
    rat2 = readers_at.at[tgt].set(rat_rows, mode="drop")
    eye = jnp.eye(n, dtype=bool)
    others = jnp.where(is_write[:, None], rat2, wat2)
    party = (others & active[None, :] & ~eye) | eye      # [n, n]
    pp = B.pack(party)                                   # [n, nw]
    dep_rows = B.any_overlap(pp[sl], pp)                 # [K, n]
    same_item = s_item[:, None] == item[None, :]
    either_w = is_write[sl][:, None] | is_write[None, :]
    eye_s = sl[:, None] == jnp.arange(n)[None, :]
    dep_rows = (dep_rows | (same_item & either_w)) & ~eye_s
    ww_rows = B.any_overlap(write_bits[sl], write_bits) & ~eye_s
    v = valid[:, None]
    return dep_rows & v, ww_rows & v, wat_rows & v, rat_rows & v


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

    @pl.when(j == 0)
    def _init():
        rdeg_ref[...] = jnp.zeros(rdeg_ref.shape, jnp.int32)
        wdeg_ref[...] = jnp.zeros(wdeg_ref.shape, jnp.int32)

    rdeg_ref[...] += raw_acc.sum(axis=1).astype(jnp.int32)
    wdeg_ref[...] += ww_acc.sum(axis=1).astype(jnp.int32)


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
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bi, w), lambda i, j: (i, 0)),
            pl.BlockSpec((bi, w), lambda i, j: (i, 0)),
            pl.BlockSpec((w, bi), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bi, bi), lambda i, j: (i, j)),
            pl.BlockSpec((bi, bi), lambda i, j: (i, j)),
            pl.BlockSpec((bi,), lambda i, j: (i,)),
            pl.BlockSpec((bi,), lambda i, j: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, n), jnp.bool_),
            jax.ShapeDtypeStruct((n, n), jnp.bool_),
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.int32),
        ],
        interpret=interpret,
    )(read_bits, write_bits, write_bits.T)


def _conflict_fused_full_kernel(r_ref, wi_ref, wj_ref, raw_ref, ww_ref,
                                rdeg_ref, cdeg_ref, wdeg_ref, dr_ref,
                                dw_ref, *, words: int):
    """``conflict_fused`` plus the WAR *column* degrees and the two
    diagonals — everything degree-ordered admission consumes, one
    launch.  Row accumulators (rdeg/wdeg/diagonals) are revisited along
    the fastest-varying ``j`` dimension and initialised at ``j == 0``;
    the column accumulator (cdeg) is revisited along ``i`` and
    initialised at ``i == 0``.  The diagonals are masked row reductions
    of the ``i == j`` tile, emitted as int32 0/1."""
    i, j = pl.program_id(0), pl.program_id(1)
    wj = wj_ref[...]                            # uint32[W, bj] (write^T)
    raw_acc = _overlap(r_ref[...], wj, words)
    ww_acc = _overlap(wi_ref[...], wj, words)
    raw_ref[...] = raw_acc
    ww_ref[...] = ww_acc

    @pl.when(j == 0)
    def _init_rows():
        rdeg_ref[...] = jnp.zeros(rdeg_ref.shape, jnp.int32)
        wdeg_ref[...] = jnp.zeros(wdeg_ref.shape, jnp.int32)
        dr_ref[...] = jnp.zeros(dr_ref.shape, jnp.int32)
        dw_ref[...] = jnp.zeros(dw_ref.shape, jnp.int32)

    @pl.when(i == 0)
    def _init_cols():
        cdeg_ref[...] = jnp.zeros(cdeg_ref.shape, jnp.int32)

    rdeg_ref[...] += raw_acc.sum(axis=1).astype(jnp.int32)
    cdeg_ref[...] += raw_acc.sum(axis=0).astype(jnp.int32)
    wdeg_ref[...] += ww_acc.sum(axis=1).astype(jnp.int32)

    @pl.when(i == j)
    def _diag():
        shape = raw_acc.shape
        eye = jax.lax.broadcasted_iota(jnp.int32, shape, 0) == \
            jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        dr_ref[...] = jnp.where(raw_acc & eye, 1, 0).max(axis=1)
        dw_ref[...] = jnp.where(ww_acc & eye, 1, 0).max(axis=1)


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
    grid = (n // bi, n // bi)
    kernel = functools.partial(_conflict_fused_full_kernel, words=w)
    *out, dr, dw = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bi, w), lambda i, j: (i, 0)),
            pl.BlockSpec((bi, w), lambda i, j: (i, 0)),
            pl.BlockSpec((w, bi), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bi, bi), lambda i, j: (i, j)),
            pl.BlockSpec((bi, bi), lambda i, j: (i, j)),
            pl.BlockSpec((bi,), lambda i, j: (i,)),
            pl.BlockSpec((bi,), lambda i, j: (j,)),
            pl.BlockSpec((bi,), lambda i, j: (i,)),
            pl.BlockSpec((bi,), lambda i, j: (i,)),
            pl.BlockSpec((bi,), lambda i, j: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, n), jnp.bool_),
            jax.ShapeDtypeStruct((n, n), jnp.bool_),
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.int32),
        ],
        interpret=interpret,
    )(read_bits, write_bits, write_bits.T)
    return (*out, dr != 0, dw != 0)
