"""Cohort-step megakernel: every pairwise relation of a fused PPCC
cohort step in ONE Pallas launch (DESIGN.md §3).

``ppcc.cohort_step_fused`` consumes five pairwise/rowwise relations per
quantum: the op dependence matrix (party overlap + same-item-write),
the per-op conflict degrees, the write-write join (wait-to-commit
feasibility), the current-holder hit vector, and the op membership
tables that feed the verdict phase.  Computed separately these re-read
the packed ``uint32[n, W]`` set words once per relation; this kernel
keeps the words and the per-slot op metadata resident in VMEM and
emits every relation from them in one pass.

Layout (chosen so Mosaic lowers it on the TPU):

* one program per call (one per lane under ``vmap``) holds every array
  whole — at the paper scale (n=160 slots, 16 words) the (n, n)
  intermediates are ~100 KiB each, far inside VMEM; whole-array blocks
  also sidestep the (8, 128) tiling rule for any ``n``;
* the words arrive twice, row-major ``[n, W]`` and transposed
  ``[W, n]`` (one word per sublane row), so every item membership test
  is a static loop over the ``W`` words of compare-and-select — no
  in-kernel gather, no dynamic slice;
* per-slot metadata arrives as int32 columns ``[n, c]`` and rows
  ``[r, n]`` (no 1-D or bool refs, no select between bools); the party
  join is a bf16 0/1 matmul with f32 accumulation (exact: sums are at
  most ``n``);
* pairwise outputs are int8 and the per-row vectors one int32
  ``[n, 3]`` block; the wrapper converts them back to bool/int32.

The word axis is exact by the packed zero-pad-bit invariant
(``core.bitset``).  On CPU the kernel runs in interpret mode
— the correctness twin that ``tests/test_megastep.py`` holds bit-equal
to the ``ref.megastep_ref`` oracle and to the jnp single-pass twin
inside ``ppcc.cohort_step_fused``; ``tests/test_tpu_compile.py`` lowers
it for a v5e chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_NT = (((1,), (1,)), ((), ()))          # dot_general: a @ b.T


def _op_tables(c, rt, wt, words: int):
    """Membership of each op's item in every slot's sets.

    ``c``: int32[m, >=2] op columns (item word, item bit); ``rt``/``wt``:
    uint32[W, n] transposed read/write words.  Returns bool[m, n]
    (writers_at, readers_at): [i, k] = item_i in {write,read}_set[k]."""
    m, n = c.shape[0], rt.shape[1]
    opw, opb = c[:, 0:1], c[:, 1:2].astype(jnp.uint32)
    w_acc = jnp.zeros((m, n), jnp.uint32)
    r_acc = jnp.zeros((m, n), jnp.uint32)
    for w in range(words):
        hit = opw == w
        w_acc = jnp.where(hit, wt[w:w + 1, :], w_acc)
        r_acc = jnp.where(hit, rt[w:w + 1, :], r_acc)
    return ((w_acc >> opb) & 1) != 0, ((r_acc >> opb) & 1) != 0


def _party(isw_c, wat, rat, act, self_):
    """[i, k]: slot k is a party of op i (conflicting access to item_i
    by an active other slot), or k is i itself."""
    isw = isw_c != 0
    others = (isw & rat) | (~isw & wat)              # no select on bools
    return (others & act & ~self_) | self_


def _bf16(x):
    """bool -> bf16 0/1, an exact MXU operand."""
    return jnp.where(x, 1.0, 0.0).astype(jnp.bfloat16)


def _join(a, b):
    """bool[m, n] x bool[p, n] -> bool[m, p]: rows share a True column."""
    return jax.lax.dot_general(_bf16(a), _bf16(b), _NT,
                               preferred_element_type=jnp.float32) > 0


def _ww_rows(wb, wt, words: int):
    """uint32[m, W] x uint32[W, n] -> bool[m, n] write-set overlap."""
    acc = jnp.zeros((wb.shape[0], wt.shape[1]), jnp.bool_)
    for w in range(words):
        acc = acc | ((wb[:, w:w + 1] & wt[w:w + 1, :]) != 0)
    return acc


def _i8(x):
    return x.astype(jnp.int8)


def _megastep_kernel(cols_ref, rows_ref, rt_ref, wt_ref, w_ref, r_ref,
                     d_ref, dep_ref, ww_ref, wat_ref, rat_ref, vec_ref, *,
                     words: int):
    n = dep_ref.shape[0]
    rows = rows_ref[...]                             # int32[5, n]
    item_r, isw_r = rows[0:1, :], rows[1:2, :]
    act = rows[2:3, :] != 0
    rdy, hl = rows[3:4, :] != 0, rows[4:5, :] != 0
    rt, wt = rt_ref[...], wt_ref[...]                # uint32[W, n]
    c = cols_ref[...]                                # int32[n, 4]

    eye = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0) == \
        jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    wat, rat = _op_tables(c, rt, wt, words)
    party = _party(c[:, 2:3], wat, rat, act, eye)
    same_item = c[:, 3:4] == item_r
    either_w = (c[:, 2:3] != 0) | (isw_r != 0)
    dep = (_join(party, party) | (same_item & either_w)) & ~eye
    ww = _ww_rows(w_ref[...], wt, words) & ~eye

    dep_ref[...] = _i8(dep)
    ww_ref[...] = _i8(ww)
    wat_ref[...] = _i8(wat)
    rat_ref[...] = _i8(rat)
    deg = jnp.where(dep & rdy, 1, 0).sum(axis=1, keepdims=True)
    lockhit = jnp.where(ww & hl, 1, 0).max(axis=1, keepdims=True)
    dhit = jnp.where((r_ref[...] & d_ref[...]) != 0, 1, 0).max(
        axis=1, keepdims=True)
    vec_ref[:, 0:1] = deg.astype(jnp.int32)
    vec_ref[:, 1:2] = lockhit.astype(jnp.int32)
    vec_ref[:, 2:3] = dhit.astype(jnp.int32)


def _i32(*vs):
    return [v.astype(jnp.int32) for v in vs]


def megastep(read_bits: jax.Array, write_bits: jax.Array,
             dirty_bits: jax.Array, item: jax.Array, is_write: jax.Array,
             active: jax.Array, ready: jax.Array, haslocks: jax.Array, *,
             interpret: bool = False):
    """One launch → every relation of a fused cohort step.

    Inputs: packed ``uint32[n, W]`` read/write/dirty words, per-slot op
    ``item`` (int32), and the ``is_write``/``active``/``ready``/
    ``haslocks`` flag vectors.  Returns

        dep       bool[n, n]  op dependence (party overlap | same-item
                              with a write), diagonal False
        ww        bool[n, n]  write-write overlap, diagonal False
        writers_at bool[n, n] [i, k] = item_i in write_set[k]
        readers_at bool[n, n] [i, k] = item_i in read_set[k]
        deg       int32[n]    (dep & ready).sum(axis=1)
        lockhit   bool[n]     (ww & haslocks).any(axis=1)
        dirty_hit bool[n]     read row intersects dirty row

    bit-for-bit equal to ``ref.megastep_ref``, for any ``n``.
    """
    n, w = read_bits.shape
    assert write_bits.shape == (n, w) and dirty_bits.shape == (n, w)
    item, is_write, active, ready, haslocks = _i32(
        item, is_write, active, ready, haslocks)
    cols = jnp.stack([item >> 5, item & 31, is_write, item], axis=1)
    rows = jnp.stack([item, is_write, active, ready, haslocks])
    kernel = functools.partial(_megastep_kernel, words=w)
    sq = jax.ShapeDtypeStruct((n, n), jnp.int8)
    dep, ww, wat, rat, vec = pl.pallas_call(
        kernel,
        out_shape=[sq] * 4 + [jax.ShapeDtypeStruct((n, 3), jnp.int32)],
        interpret=interpret,
    )(cols, rows, read_bits.T, write_bits.T, write_bits, read_bits,
      dirty_bits)
    dep, ww, wat, rat = (m != 0 for m in (dep, ww, wat, rat))
    return dep, ww, wat, rat, vec[:, 0], vec[:, 1] != 0, vec[:, 2] != 0
