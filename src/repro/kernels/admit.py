"""PPCC admission scan as one Pallas launch: the Prudent Precedence
Rule over a tick's backlog, every step on the core (DESIGN.md §4).

``sched.scheduler.ppcc_tick`` visits the backlog's rows once each, in
the admission order ``seq``, and tests row i against the rows admitted
before it through its RAW arcs out (row i of ``raw``) and its WAR arcs
in (column i of ``raw``).  A step's work is two row loads, a few ANDs
and a few reductions; as a ``lax.scan`` each step is a loop iteration
of about ten separate XLA ops.  Here the whole scan is one kernel:

* ``seq`` and ``valid`` arrive as scalar prefetch in SMEM;
* ``raw`` (diagonal off) and ``raw.T`` arrive as whole VMEM blocks
  ``int32[n, S, 128]``: row i of each is ``n_pad = 128 * S`` columns,
  whole ``(8, 128)`` tiles (one vreg at n <= 1 024).  The transpose is
  made once, outside, so the WAR column is a row load too;
* the three class vectors (admitted, preceding, preceded) are int32 0/1
  ``[S, 128]`` loop carries, set at bit i by an ``iota == i`` one-hot;
* the caller's rule is evaluated on bool ``[S, 128]`` vectors; a rule
  whose reductions keep their rank (``any_kept``, as
  ``scheduler._prudent``'s do) leaves the verdict a ``(1, 1)`` vector,
  broadcast back, so nothing moves to the scalar unit and nothing
  branches.

Pad columns hold no arcs and pad rows are never visited, so the result
is exact for any n.  The two tables take ``2 * n * n_pad * 4`` bytes of
VMEM (8 MiB at n = 1 024); ``fits`` says whether a backlog is within
``TABLE_BUDGET``, above which the caller keeps its ``lax.scan``
(``ref.ppcc_admit_scan_ref``, the oracle this kernel equals bit for
bit).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES, TILE = 128, 8 * 128
TABLE_BUDGET = 32 * 2 ** 20
"""Bytes of VMEM the two tables may take: n <= 2 048 rows."""
_VMEM_LIMIT = TABLE_BUDGET + 16 * 2 ** 20


def _padded(n: int) -> int:
    return -(-n // TILE) * TILE


def fits(n: int) -> bool:
    """Whether the two ``int32[n, n_pad]`` tables of a backlog of ``n``
    rows are within ``TABLE_BUDGET``."""
    return 2 * n * _padded(n) * 4 <= TABLE_BUDGET


def _admit_kernel(seq_ref, valid_ref, raw_ref, rawt_ref, out_ref, *,
                  rule: Callable):
    n = seq_ref.shape[0]
    shape = out_ref.shape[1:]                         # (S, 128)
    col = (jax.lax.broadcasted_iota(jnp.int32, shape, 0) * LANES
           + jax.lax.broadcasted_iota(jnp.int32, shape, 1))

    def step(t, carry):
        admitted, preceding, preceded = (c != 0 for c in carry)
        i = seq_ref[t]
        r = (raw_ref[i] != 0) & admitted              # i -> j arcs (RAW)
        w = (rawt_ref[i] != 0) & admitted             # k -> i arcs (WAR)
        ok = (valid_ref[i] != 0) & rule(r, w, preceding, preceded)
        ok = jnp.broadcast_to(ok, shape)
        hot = (col == i) & ok
        admitted = admitted | hot
        preceding = preceding | (hot & any_kept(r)) | (w & ok)
        preceded = preceded | (hot & any_kept(w)) | (r & ok)
        return tuple(x.astype(jnp.int32)
                     for x in (admitted, preceding, preceded))

    zero = jnp.zeros(shape, jnp.int32)
    out = jax.lax.fori_loop(0, n, step, (zero, zero, zero))
    for k, v in enumerate(out):
        out_ref[k] = v


def any_kept(x: jax.Array) -> jax.Array:
    """``x.any()`` at x's rank, reduced one axis at a time: inside a
    kernel the answer stays a vector (a reduction to rank 0 is moved to
    the scalar unit)."""
    for ax in range(x.ndim):
        x = x.any(axis=ax, keepdims=True)
    return x


def admit_scan(raw: jax.Array, valid: jax.Array, seq: jax.Array, *,
               rule: Callable, interpret: bool = False):
    """One launch -> ``(admitted, preceding, preceded)``, each
    ``bool[n]``: the rule applied to the rows in the order ``seq`` (a
    permutation of ``range(n)``), as ``ref.ppcc_admit_scan_ref``.

    ``raw`` is ``bool[n, n]`` with the diagonal off (``raw[i, j]``: i
    reads what j writes), ``valid`` ``bool[n]``; ``rule(r_i, w_i,
    preceding, preceded)`` is the admission test on row i's admitted
    RAW arcs out and WAR arcs in."""
    n = raw.shape[0]
    assert raw.shape == (n, n) and valid.shape == (n,) and seq.shape == (n,)
    n_pad = _padded(n)
    s = n_pad // LANES
    t = raw.astype(jnp.int32)
    tables = [jnp.pad(x, ((0, 0), (0, n_pad - n))).reshape(n, s, LANES)
              for x in (t, t.T)]
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(_admit_kernel, rule=rule),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(), in_specs=[vmem, vmem],
            out_specs=vmem),
        out_shape=jax.ShapeDtypeStruct((3, s, LANES), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="ppcc_admit_scan",
    )(seq.astype(jnp.int32), valid.astype(jnp.int32), *tables)
    admitted, preceding, preceded = (
        v.reshape(n_pad)[:n] != 0 for v in out)
    return admitted, preceding, preceded
