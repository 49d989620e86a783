"""PPCC batch scheduler — the paper's protocol as admission control for
concurrent actors over shared sharded state (DESIGN.md §4).

A *transaction* here is any actor with a declared read/write set over
the store's pages: an async DP replica pushing a delayed update, an
evaluator snapshotting, a serving replica reading.  Per tick the
scheduler takes the pending transactions' bitsets and decides, under a
chosen policy, which may proceed this tick and in which commit order:

* ``ppcc``  — the Prudent Precedence Rule applied in priority order
  (exact, one step per transaction in the ``ppcc_admit_scan`` Pallas
  kernel, or in its oracle, ``ref.ppcc_admit_scan_ref``'s lax.scan);
  conflicting-but-admissible transactions proceed WITH a precedence
  that the commit pass respects.
* ``2pl``   — conservative: a transaction is admitted only if it
  conflicts with no earlier-admitted transaction (blocking semantics).
* ``occ``   — admit everything, validate afterwards: a transaction
  aborts if its read set intersects the write set of any
  earlier-priority admitted transaction (restart next tick).

The pairwise conflict matrices come from the packed-bitset Pallas
kernel (``repro.kernels.conflict``); the O(n^2) pair scan is the
scheduler hot spot at thousands of concurrent actors.

Set inputs may be boolean ``bool[n, d]`` masks *or* already-packed
``uint32[n, W]`` words (``repro.core.bitset.pack``) — callers that
keep packed state hand it straight to the kernel with no re-pack per
tick.  ``W`` may exceed ``ceil(d/32)``: wider rows are simply
zero-padded words (the §1.1 invariant), so state kept at a static
word *bucket* (e.g. the 500-item fleet bucket while only 100 items
are live) flows through unchanged.  ``tick(..., words=...)`` pads
boolean inputs to such a bucket at pack time — ticks of
different-sized workloads then share one jitted executable, the same
static-axis bucketing story as ``core.sweep`` (DESIGN.md §2.4).

A keyspace of millions of records has no bitset row worth forming.
With ``keys=True`` the sets are key-id lists instead — ``int32[n, kr]``
read keys and ``int32[n, kw]`` write keys, negative ids padding short
lists — and the relations come from ``conflict_keys``, whose work is
n² x kr x kw compares whatever the keyspace.  Every policy, both
orders, the carry and ``tick_stats`` take either form.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import bitset, ppcc
from ..kernels import admit, ops as kops


def _as_bits(sets: jax.Array, words: int = None) -> jax.Array:
    """Accept bool[n, d] or pre-packed uint32[n, W] set rows.

    ``words`` pads the packed rows to a static word bucket (pad words
    are zero, so every word-wise relation below is exact) — the jit
    cache keys on the padded shape, so workloads of different ``d``
    share one compiled tick.
    """
    bits = sets if sets.dtype == jnp.uint32 else bitset.pack(sets)
    if words is not None:
        have = bits.shape[-1]
        if words < have:
            raise ValueError(
                f"words={words} below the input's {have} packed words")
        if words > have:
            bits = jnp.pad(bits, [(0, 0)] * (bits.ndim - 1)
                           + [(0, words - have)])
    return bits


class TickResult(NamedTuple):
    admitted: jax.Array       # bool[n]
    aborted: jax.Array        # bool[n]  (occ validation failures)
    commit_rank: jax.Array    # int32[n] commit order among admitted (-1)
    state: ppcc.PPCCState     # protocol state after the tick (ppcc)


class TickCarry(NamedTuple):
    """Carried pairwise state for back-to-back ticks.

    Holds the previous tick's set operands (packed words, or key lists)
    plus the full fused conflict launch output (the 7-tuple of
    ``conflict_fused_full`` / ``conflict_keys``).  When the next tick's
    sets and valid mask are unchanged — common when the pending batch
    persists across ticks (blocked actors retrying) — the O(n²) launch
    is skipped and the carried matrices are reused (a ``lax.cond``
    guards exactness)."""
    read_sets: jax.Array      # uint32[n, W] or int32[n, kr]
    write_sets: jax.Array     # uint32[n, W] or int32[n, kw]
    valid: jax.Array          # bool[n]
    rel: Tuple[jax.Array, ...]  # the full launch's 7-tuple


def _operands(read_sets: jax.Array, write_sets: jax.Array, words: int,
              keys: bool) -> Tuple[jax.Array, jax.Array]:
    """The set operands of the conflict launch: packed words (padded to
    ``words``), or the key lists as they are."""
    if keys:
        if words is not None:
            raise ValueError("words= buckets packed rows; key lists "
                             "have none")
        return read_sets.astype(jnp.int32), write_sets.astype(jnp.int32)
    return _as_bits(read_sets, words), _as_bits(write_sets, words)


def _conflict_matrices(read: jax.Array, write: jax.Array,
                       use_kernel: bool, keys: bool = False,
                       full: bool = False) -> Tuple[jax.Array, ...]:
    """The one dispatch of the pairwise launch.  Packed words go to
    ``conflict_fused`` → (raw[i,j]: i reads what j writes, ww[i,j]:
    write/write overlap, raw_deg[i], ww_deg[i]: per-row popcount
    degrees incl. diagonal), or with ``full`` to ``conflict_fused_full``
    → (raw, ww, raw_deg, war_deg, ww_deg, diag_raw, diag_ww).  Key
    lists always go to ``conflict_keys`` (the 7-tuple; without ``full``
    its four-tuple part).  ``use_kernel=False`` takes the jnp oracles.

    One fused Pallas launch emits both relations and the degrees; the
    degrees feed the degree-ordered admission heuristic below."""
    if keys:
        out = (kops.conflict_keys(read, write) if use_kernel
               else kops.ref.conflict_keys_ref(read, write))
        return out if full else (out[0], out[1], out[2], out[4])
    if full:
        return (kops.conflict_fused_full(read, write) if use_kernel
                else kops.ref.conflict_fused_full_ref(read, write))
    if use_kernel:
        return kops.conflict_fused(read, write)
    return kops.ref.conflict_fused_ref(read, write)


def _prudent(r_i: jax.Array, w_i: jax.Array, preceding: jax.Array,
             preceded: jax.Array) -> jax.Array:
    """The Prudent Precedence Rule for one transaction against the
    admitted set, given its RAW arcs out (``r_i``) and WAR arcs in
    (``w_i``): it may not become both preceding and preceded, may not
    precede a preceding transaction, and may not follow a preceded
    one (the rule's two class tests).  The verdict keeps the operands'
    rank, so in the admission kernel it stays a vector."""
    any_ = admit.any_kept
    return (~(any_(r_i) & any_(w_i)) & ~any_(r_i & preceding)
            & ~any_(w_i & preceded))


def ppcc_tick(read_sets: jax.Array, write_sets: jax.Array,
              valid: jax.Array, use_kernel: bool = True,
              order: str = "priority", words: int = None,
              carry: TickCarry = None, return_carry: bool = False,
              keys: bool = False) -> TickResult:
    """Admit a batch of single-shot transactions under PPCC.

    read_sets/write_sets: bool[n, d], packed words, or with ``keys``
    key lists; valid: bool[n].  Each transaction
    executes atomically in priority order, reads before writes.  With
    the pairwise conflict matrices precomputed (Pallas kernel), the
    Prudent Precedence Rule for transaction i against the already-
    admitted set reduces to class-bit vector tests — an O(n) step inside
    an O(n^2) scan instead of per-item protocol calls:

      R_i = {admitted j : read_i  cap write_j}   (arcs i -> j)
      W_i = {admitted k : write_i cap read_k}    (arcs k -> i)
      admit iff  (R_i empty or no j in R_i is preceding)
             and (W_i empty or no k in W_i is preceded)
             and not (R_i and W_i both nonempty)   [i would be preceding
                                                    AND preceded]
    WAW alone imposes no precedence (paper Section 2.1); commit order is
    preceding-class transactions first (any topological order of the
    path-length <= 1 DAG).

    ``order="degree"`` admits in ascending conflict-degree order (the
    fused kernel's per-row popcounts) instead of priority order:
    low-conflict transactions claim their arcs first, which admits
    larger batches under contention at the cost of strict priority.

    ``carry`` (a previous tick's ``TickCarry``) skips the fused
    conflict launch entirely when the set operands and valid mask are
    unchanged since that tick; pass ``return_carry=True`` to get
    ``(TickResult, TickCarry)`` for the next tick.

    The three parts run under the named scopes ``tick.conflict`` (the
    pairwise launch and the admission order), ``tick.scan`` (the
    rule, one step per transaction) and ``tick.commit_order``.

    The scan is one ``ppcc_admit_scan`` launch (``kernels.admit``) when
    ``use_kernel`` and its tables fit the kernel's VMEM budget
    (n <= 2 048), else its oracle's ``lax.scan``; either carries only
    the three ``bool[n]`` vectors.  ``state.prec`` is formed once after
    it, as ``raw`` (diagonal off) restricted to admitted pairs.  That is
    exact: each row is visited once, and an arc is stored only between
    admitted rows, at the later one's step.
    """
    n = read_sets.shape[0]
    with jax.named_scope("tick.conflict"):
        rs, ws = _operands(read_sets, write_sets, words, keys)
        full = None
        if order == "degree" or carry is not None or return_carry:
            # One fused launch emits the matrices, all three degrees
            # AND the diagonals.  With a carry whose inputs are
            # unchanged the launch is skipped and the carried 7-tuple
            # reused.
            def launch():
                return _conflict_matrices(rs, ws, use_kernel, keys,
                                          full=True)

            if carry is not None:
                unchanged = ((carry.read_sets == rs).all()
                             & (carry.write_sets == ws).all()
                             & (carry.valid == valid).all())
                full = jax.lax.cond(unchanged, lambda: carry.rel, launch)
            else:
                full = launch()
            raw = full[0]
        if order == "degree":
            # total involvement = RAW out-degree + WAR in-degree (the
            # kernel's column-sum output) + WW degree; kernel degrees
            # include the diagonal and self-conflicts are not conflicts
            # here, so strip it everywhere.
            _, _, raw_deg, war_deg, ww_deg, diag_raw, diag_ww = full
            self_r = diag_raw.astype(jnp.int32)
            deg = (raw_deg - self_r + war_deg - self_r
                   + ww_deg - diag_ww.astype(jnp.int32))
            seq = jnp.argsort(deg, stable=True).astype(jnp.int32)
        else:
            if full is None:
                raw = _conflict_matrices(rs, ws, use_kernel, keys)[0]
            seq = jnp.arange(n, dtype=jnp.int32)
        raw = raw & ~jnp.eye(n, dtype=bool)      # self-RAW is not a conflict

    with jax.named_scope("tick.scan"):
        scan = (kops.ppcc_admit_scan
                if use_kernel and admit.fits(n)
                else kops.ref.ppcc_admit_scan_ref)
        admitted, preceding, preceded = scan(raw, valid, seq, rule=_prudent)
        # exact: ``seq`` visits each row once, and a step stores an arc
        # only between its row, if admitted, and rows admitted before it
        prec = raw & admitted[:, None] & admitted[None, :]
    with jax.named_scope("tick.commit_order"):
        # preceding-class (readers) first
        rank_key = jnp.where(admitted, preceded.astype(jnp.int32), 2 ** 30)
        commit_order = jnp.argsort(rank_key, stable=True)
        commit_rank = jnp.full((n,), -1, jnp.int32)
        commit_rank = commit_rank.at[commit_order].set(
            jnp.arange(n, dtype=jnp.int32))
        commit_rank = jnp.where(admitted, commit_rank, -1)
    s = ppcc.init_state(n, 1)
    s = s._replace(prec=prec, preceding=preceding, preceded=preceded,
                   active=admitted)
    res = TickResult(admitted=admitted,
                     aborted=jnp.zeros_like(admitted),
                     commit_rank=commit_rank, state=s)
    if return_carry:
        return res, TickCarry(read_sets=rs, write_sets=ws, valid=valid,
                              rel=full)
    return res


def twopl_tick(read_sets: jax.Array, write_sets: jax.Array,
               valid: jax.Array, use_kernel: bool = True,
               words: int = None, keys: bool = False) -> TickResult:
    """Conservative baseline: admit a prefix-greedy conflict-free set."""
    n = read_sets.shape[0]
    raw, ww, *_ = _conflict_matrices(
        *_operands(read_sets, write_sets, words, keys), use_kernel, keys)
    conflict = raw | raw.T | ww            # any lock conflict
    conflict = conflict & ~jnp.eye(n, dtype=bool)

    def step(admitted, i):
        ok = valid[i] & ~(conflict[i] & admitted).any()
        return admitted.at[i].set(ok), ok

    admitted, _ = jax.lax.scan(step, jnp.zeros(n, bool),
                               jnp.arange(n, dtype=jnp.int32))
    rank = jnp.where(admitted, jnp.cumsum(admitted) - 1, -1)
    return TickResult(admitted=admitted, aborted=jnp.zeros(n, bool),
                      commit_rank=rank.astype(jnp.int32),
                      state=ppcc.init_state(1, 1))


def occ_tick(read_sets: jax.Array, write_sets: jax.Array,
             valid: jax.Array, use_kernel: bool = True,
             words: int = None, keys: bool = False) -> TickResult:
    """Optimistic baseline: all run; backward validation in priority
    order — abort if an earlier-priority survivor wrote what you read
    (or wrote)."""
    n = read_sets.shape[0]
    raw, ww, *_ = _conflict_matrices(
        *_operands(read_sets, write_sets, words, keys), use_kernel, keys)
    bad = raw | ww                          # i conflicts with j's writes

    def step(survivors, i):
        earlier = jnp.arange(n) < i
        fail = (bad[i] & survivors & earlier).any()
        ok = valid[i] & ~fail
        return survivors.at[i].set(ok), ok

    survivors, _ = jax.lax.scan(step, jnp.zeros(n, bool),
                                jnp.arange(n, dtype=jnp.int32))
    rank = jnp.where(survivors, jnp.cumsum(survivors) - 1, -1)
    return TickResult(admitted=survivors,
                      aborted=valid & ~survivors,
                      commit_rank=rank.astype(jnp.int32),
                      state=ppcc.init_state(1, 1))


POLICIES = {"ppcc": ppcc_tick, "2pl": twopl_tick, "occ": occ_tick}


def tick_stats(read_sets: jax.Array, write_sets: jax.Array,
               valid: jax.Array, result: TickResult,
               use_kernel: bool = True, words: int = None,
               keys: bool = False) -> dict:
    """Host-side per-tick telemetry: admitted/aborted/pending counts
    plus conflict-degree stats over the valid batch (max / mean rows of
    the symmetric conflict relation ``raw | raw^T | ww``).  Pure
    observation — reads the tick inputs and result, mutates nothing."""
    raw, ww, *_ = _conflict_matrices(
        *_operands(read_sets, write_sets, words, keys), use_kernel, keys)
    n = raw.shape[0]
    conflict = (raw | raw.T | ww) & ~jnp.eye(n, dtype=bool)
    conflict = conflict & valid[None, :] & valid[:, None]
    deg = np.asarray(conflict.sum(axis=1))[np.asarray(valid)]
    admitted = int(np.asarray(result.admitted).sum())
    aborted = int(np.asarray(result.aborted).sum())
    n_valid = int(np.asarray(valid).sum())
    return {
        "valid": n_valid,
        "admitted": admitted,
        "aborted": aborted,
        "pending": n_valid - admitted - aborted,
        "degree_max": int(deg.max()) if deg.size else 0,
        "degree_mean": float(deg.mean()) if deg.size else 0.0,
    }


@functools.partial(jax.jit, static_argnames=("policy", "order", "words",
                                             "return_carry", "keys"))
def tick(read_sets: jax.Array, write_sets: jax.Array, valid: jax.Array,
         policy: str = "ppcc", order: str = "priority",
         words: int = None, carry: TickCarry = None,
         return_carry: bool = False, keys: bool = False) -> TickResult:
    """One admission tick.  ``keys=True`` takes the sets as key-id
    lists (``int32[n, kr]`` reads, ``int32[n, kw]`` writes, negative
    ids are pads) instead of bool masks or packed words.  For ppcc,
    ``carry``/``return_carry`` thread the pairwise conflict state
    across ticks: the fused O(n²) launch is skipped whenever the set
    operands and valid mask match the carried tick's (see
    ``TickCarry``)."""
    if policy == "ppcc":
        return ppcc_tick(read_sets, write_sets, valid, order=order,
                         words=words, carry=carry,
                         return_carry=return_carry, keys=keys)
    if order != "priority":
        raise ValueError(
            f"order={order!r} is only supported for policy='ppcc'")
    if carry is not None or return_carry:
        raise ValueError("carried conflict state is ppcc-only")
    return POLICIES[policy](read_sets, write_sets, valid, words=words,
                            keys=keys)
