"""Tensorised Prudent-Precedence protocol state — the paper's contribution
as a composable JAX module.

The protocol state for ``n`` concurrent transactions over ``d`` items is a
fixed-shape pytree (`PPCCState`), and every protocol transition (paper
Section 2.2-2.3) is a pure, jit-able function:

    try_read / try_write     read-phase admission under the Prudent
                             Precedence Rule (returns verdict + new state)
    wc_acquire_locks         wait-to-commit exclusive locking (Fig. 4)
    can_commit               all predecessors have left (Fig. 4)
    commit / abort           leave the precedence graph, release locks

The read/write sets are *packed bitsets* — ``uint32[n, ceil(d/32)]``
words from ``repro.core.bitset`` (DESIGN.md §1.1): membership tests,
overlap joins and popcounts run word-wise, which cuts the sets' memory
traffic ~8x versus ``bool[n, d]`` rows and keeps the whole conflict
pipeline (primitives, engine, Pallas kernels, scheduler) on one shared
representation.

The invariant that makes the paper's protocol cheap — every precedence
path has length <= 1, hence acyclicity without cycle detection (Thm. 1) —
is a one-line tensor predicate here (`assert_invariant`).

These primitives are consumed by

* ``repro.core.jaxsim``  — the tensorised discrete-event simulator,
* ``repro.sched.scheduler`` — PPCC batch admission for the transactional
  store (conflict matrices from the Pallas kernel in
  ``repro.kernels.conflict``).
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from . import bitset as B

# verdicts
PROCEED, BLOCK, ABORT = 0, 1, 2

# block-reason codes attached to BLOCK verdicts (telemetry taxonomy):
# the op hit a wait-to-commit lock (R_LOCK) vs the Prudent Precedence
# Rule refused the precedence (R_RULE).  R_NONE on non-BLOCK lanes.
R_NONE, R_LOCK, R_RULE = 0, 1, 2


class PPCCState(NamedTuple):
    """Protocol state for n transaction slots over d items.

    Wait-to-commit lock ownership is *derived*, not stored per item: a
    slot with ``haslocks[k]`` holds exclusive locks on exactly its
    ``write_set[k]`` items (acquisition is all-or-nothing and the
    acquirer's write set is frozen while it holds locks, so the owner
    of item ``x`` is the unique ``k`` with ``haslocks[k]`` and bit
    ``x`` set — uniqueness because winners' write words are disjoint
    from every other holder's).  That keeps the whole lock machinery on
    the packed ``uint32[n, W]`` words: no ``int32[d]`` owner array is
    ever materialised or scattered into (DESIGN.md §1.1).
    """

    read_set: jax.Array      # uint32[n, W] packed bitset (W = ceil(d/32))
    write_set: jax.Array     # uint32[n, W] (private-workspace writes)
    prec: jax.Array          # bool[n, n]  prec[a, b] == True iff a -> b
    preceding: jax.Array     # bool[n]     class bit: has preceded someone
    preceded: jax.Array      # bool[n]     class bit: has been preceded
    active: jax.Array        # bool[n]     slot holds a live transaction
    haslocks: jax.Array      # bool[n]     holds wait-to-commit locks on
                             #             its whole write_set row

    @property
    def n(self) -> int:
        return self.read_set.shape[0]

    @property
    def words(self) -> int:
        return self.read_set.shape[1]


def init_state(n: int, d: int) -> PPCCState:
    return PPCCState(
        read_set=B.zeros(n, d),
        write_set=B.zeros(n, d),
        prec=jnp.zeros((n, n), jnp.bool_),
        preceding=jnp.zeros((n,), jnp.bool_),
        preceded=jnp.zeros((n,), jnp.bool_),
        active=jnp.zeros((n,), jnp.bool_),
        haslocks=jnp.zeros((n,), jnp.bool_),
    )


def begin(s: PPCCState, i: jax.Array) -> PPCCState:
    """Activate slot i as a fresh independent transaction."""
    return s._replace(
        read_set=s.read_set.at[i].set(jnp.uint32(0)),
        write_set=s.write_set.at[i].set(jnp.uint32(0)),
        prec=s.prec.at[i, :].set(False).at[:, i].set(False),
        preceding=s.preceding.at[i].set(False),
        preceded=s.preceded.at[i].set(False),
        active=s.active.at[i].set(True),
        haslocks=s.haslocks.at[i].set(False),
    )


def _lock_verdict(s: PPCCState, i: jax.Array, x: jax.Array) -> jax.Array:
    """Paper Fig. 3: accessing an item locked by a wait-to-commit txn.

    Returns PROCEED when unlocked / self-locked, ABORT when the accessor
    already precedes the lock owner (circular-wait prevention), BLOCK
    otherwise.  The owner of x is the unique holder whose write set
    covers it (see ``PPCCState``); ``prec[i, i]`` is invariantly False,
    so the precedes-owner test needs no explicit self-exclusion.
    """
    owner_bits = B.get_col(s.write_set, x) & s.haslocks        # bool[n]
    me = jnp.arange(s.n) == i
    locked_by_other = (owner_bits & ~me).any()
    i_precedes_owner = (owner_bits & s.prec[i, :]).any()
    return jnp.where(
        locked_by_other,
        jnp.where(i_precedes_owner, ABORT, BLOCK),
        PROCEED,
    )


def try_read(s: PPCCState, i: jax.Array, x: jax.Array
             ) -> Tuple[PPCCState, jax.Array]:
    """Transaction i reads item x (RAW handling, paper Example 1).

    Under the strict protocol the reader gets the *old* value, so the
    reader precedes every uncommitted writer of x.  The Prudent Precedence
    Rule admits the read iff (i) the reader has never been preceded and
    (ii) no such writer has ever preceded anyone.
    """
    lock_v = _lock_verdict(s, i, x)
    me = jax.nn.one_hot(i, s.n, dtype=jnp.bool_)
    # writers of x we do not already precede
    new_writers = B.get_col(s.write_set, x) & s.active & ~me & ~s.prec[i, :]
    any_new = new_writers.any()
    rule_ok = (~s.preceded[i]) & ~(new_writers & s.preceding).any()
    allowed = (lock_v == PROCEED) & (~any_new | rule_ok)
    verdict = jnp.where(lock_v != PROCEED, lock_v,
                        jnp.where(allowed, PROCEED, BLOCK))

    def apply(s: PPCCState) -> PPCCState:
        add = new_writers & allowed
        return s._replace(
            read_set=B.set_bit(s.read_set, i, x, allowed),
            prec=s.prec.at[i, :].set(s.prec[i, :] | add),
            preceding=s.preceding.at[i].set(
                s.preceding[i] | (allowed & any_new)),
            preceded=s.preceded | add,
        )

    return apply(s), verdict


def try_write(s: PPCCState, i: jax.Array, x: jax.Array
              ) -> Tuple[PPCCState, jax.Array]:
    """Transaction i writes item x in its workspace (WAR, paper Example 2).

    Every current reader of x precedes the writer.  Admitted iff
    (i) the writer has never preceded anyone and (ii) no such reader has
    ever been preceded.
    """
    lock_v = _lock_verdict(s, i, x)
    me = jax.nn.one_hot(i, s.n, dtype=jnp.bool_)
    new_readers = B.get_col(s.read_set, x) & s.active & ~me & ~s.prec[:, i]
    any_new = new_readers.any()
    rule_ok = (~s.preceding[i]) & ~(new_readers & s.preceded).any()
    allowed = (lock_v == PROCEED) & (~any_new | rule_ok)
    verdict = jnp.where(lock_v != PROCEED, lock_v,
                        jnp.where(allowed, PROCEED, BLOCK))

    def apply(s: PPCCState) -> PPCCState:
        add = new_readers & allowed
        return s._replace(
            write_set=B.set_bit(s.write_set, i, x, allowed),
            prec=s.prec.at[:, i].set(s.prec[:, i] | add),
            preceded=s.preceded.at[i].set(
                s.preceded[i] | (allowed & any_new)),
            preceding=s.preceding | add,
        )

    return apply(s), verdict


def try_op(s: PPCCState, i: jax.Array, x: jax.Array, is_write: jax.Array
           ) -> Tuple[PPCCState, jax.Array]:
    """Dispatch on op kind without python control flow."""
    sr, vr = try_read(s, i, x)
    sw, vw = try_write(s, i, x)
    pick = lambda a, b: jnp.where(is_write, b, a)
    return jax.tree.map(pick, sr, sw), pick(vr, vw)


def wc_acquire_locks(s: PPCCState, i: jax.Array
                     ) -> Tuple[PPCCState, jax.Array]:
    """Wait-to-commit: atomically lock the write set (all-or-nothing,
    which prevents deadlock between wait-to-commit transactions).
    Succeeds iff no *other* holder's write words intersect i's — one
    word-wise AND over the packed rows (self-held locks pass, so the
    call is idempotent).  Returns (state, acquired: bool)."""
    me = jnp.arange(s.n) == i
    hit = B.overlap_rows(s.write_set, s.write_set[i][None, :])   # bool[n]
    ok = ~(hit & s.haslocks & ~me).any()
    return s._replace(haslocks=s.haslocks.at[i].set(
        s.haslocks[i] | ok)), ok


def can_commit(s: PPCCState, i: jax.Array) -> jax.Array:
    """Fig. 4: proceed to commit iff no active transaction precedes i."""
    return ~(s.prec[:, i] & s.active).any()


def _leave(s: PPCCState, i: jax.Array) -> PPCCState:
    """Shared cleanup for commit and abort: transaction i leaves the
    system — drop its arcs, sets and locks."""
    return s._replace(
        read_set=s.read_set.at[i].set(jnp.uint32(0)),
        write_set=s.write_set.at[i].set(jnp.uint32(0)),
        prec=s.prec.at[i, :].set(False).at[:, i].set(False),
        active=s.active.at[i].set(False),
        haslocks=s.haslocks.at[i].set(False),
    )


def commit(s: PPCCState, i: jax.Array) -> PPCCState:
    return _leave(s, i)


def abort(s: PPCCState, i: jax.Array) -> PPCCState:
    return _leave(s, i)


# --------------------------------------------------------------------------
# invariants (paper Theorem 1)
# --------------------------------------------------------------------------

def path_length_leq_one(s: PPCCState) -> jax.Array:
    """True iff no precedence path of length 2 exists: prec @ prec == 0."""
    p = s.prec.astype(jnp.int32)
    return (p @ p).sum() == 0


def acyclic(s: PPCCState) -> jax.Array:
    """With paths of length <= 1, a cycle could only be a 2-cycle or a
    self-loop; check both directly."""
    two_cycle = (s.prec & s.prec.T).any()
    self_loop = jnp.diagonal(s.prec).any()
    return ~(two_cycle | self_loop) & path_length_leq_one(s)


def classes_consistent(s: PPCCState) -> jax.Array:
    """Arcs only run preceding -> preceded; class bits cover the arcs."""
    rows_ok = (~s.prec.any(axis=1) | s.preceding).all()
    cols_ok = (~s.prec.any(axis=0) | s.preceded).all()
    return rows_ok & cols_ok


# --------------------------------------------------------------------------
# batch admission (used by repro.sched.scheduler)
# --------------------------------------------------------------------------

class BatchVerdict(NamedTuple):
    admitted: jax.Array      # bool[n] ops admitted this round
    blocked: jax.Array       # bool[n]
    aborted: jax.Array       # bool[n]
    state: PPCCState


def admit_ops(s: PPCCState, txn: jax.Array, item: jax.Array,
              is_write: jax.Array, valid: jax.Array) -> BatchVerdict:
    """Admit a batch of operations in priority (index) order.

    The Prudent Precedence Rule is order-dependent, so exactness requires
    a sequential pass: a ``lax.scan`` over the op list.  Each element is
    (txn slot, item, is_write, valid).  Invalid lanes are no-ops.
    """
    def step(s: PPCCState, op):
        t, x, w, v = op
        s2, verdict = try_op(s, t, x, w)
        s2 = jax.tree.map(lambda a, b: jnp.where(v, a, b), s2, s)
        verdict = jnp.where(v, verdict, BLOCK)
        return s2, verdict

    s, verdicts = jax.lax.scan(step, s, (txn, item, is_write, valid))
    return BatchVerdict(
        admitted=(verdicts == PROCEED) & valid,
        blocked=(verdicts == BLOCK) & valid,
        aborted=(verdicts == ABORT) & valid,
        state=s,
    )


def _any_overlap(a: jax.Array, b: jax.Array) -> jax.Array:
    """bool[N, M] x bool[K, M] -> bool[N, K] row-pair intersection via
    packed bitsets.  For *party matrices* (boolean over slots); the
    protocol's item sets are already packed words and go straight to
    ``bitset.any_overlap``.  Self-joins pack the operand once."""
    ap = B.pack(a)
    bp = ap if b is a else B.pack(b)
    return B.any_overlap(ap, bp)


# --------------------------------------------------------------------------
# batched cohort primitives (DESIGN.md §2.3)
#
# The cohort-stepped engine advances many slots per ``while_loop``
# iteration.  A vectorized protocol step applied to a *cohort* of pending
# ops is exactly equivalent to applying them sequentially (in any order)
# iff the ops are pairwise independent.  Op i's transition reads and
# writes only the protocol state of its *party*:
#
#     party(i) = {i} ∪ {active writers of item_i}   (read op)
#                {i} ∪ {active readers of item_i}   (write op)
#
# (verdict inputs: class bits and arcs of party members, the item's lock
# word; updates: read/write-set bit of i, arcs between i and party
# members, class bits of party members).  Read-phase ops never touch
# lock words, so two ops commute iff their parties are disjoint and they
# do not target the same item with a write involved (the same-item guard
# covers the party membership the ops are *about to create*).
# --------------------------------------------------------------------------


def begin_many(s: PPCCState, mask: jax.Array) -> PPCCState:
    """Activate every masked slot as a fresh independent transaction.

    ``begin`` touches only slot-local rows/columns, so any set of begins
    commutes; this is the exact batched form of ``begin`` over ``mask``.
    """
    m = mask
    return s._replace(
        read_set=B.clear_rows(s.read_set, m),
        write_set=B.clear_rows(s.write_set, m),
        prec=s.prec & ~m[:, None] & ~m[None, :],
        preceding=s.preceding & ~m,
        preceded=s.preceded & ~m,
        active=s.active | m,
        haslocks=s.haslocks & ~m,
    )


def _op_tables(s: PPCCState, item: jax.Array):
    """Shared gathers: (writers_at, readers_at), each [i, k] =
    {write,read}_set[k, item[i]] — one packed-word gather per pair."""
    return B.item_cols(s.write_set, item), B.item_cols(s.read_set, item)


def op_parties(s: PPCCState, item: jax.Array, is_write: jax.Array
               ) -> jax.Array:
    """party[i, k]: slot i's pending op touches slot k's protocol state."""
    writers_at, readers_at = _op_tables(s, item)
    return _parties(s, is_write, writers_at, readers_at)


def _parties(s, is_write, writers_at, readers_at):
    eye = jnp.eye(s.n, dtype=bool)
    others = jnp.where(is_write[:, None], readers_at, writers_at)
    return (others & s.active[None, :] & ~eye) | eye


def _dep_matrix(s, item, is_write, writers_at, readers_at):
    """dep[i, j]: ops of slots i and j do not commute — their parties
    intersect, or they target the same item with a write involved (the
    write is about to *make* the other op's slot a party member)."""
    party = _parties(s, is_write, writers_at, readers_at)
    dep = _any_overlap(party, party)
    same_item = item[:, None] == item[None, :]
    either_write = is_write[:, None] | is_write[None, :]
    return (dep | (same_item & either_write)) & ~jnp.eye(s.n, dtype=bool)


def _select(s, item, is_write, ready, writers_at, readers_at):
    """Selected: ready slots no lower-indexed *ready* slot depends on."""
    n = s.n
    dep = _dep_matrix(s, item, is_write, writers_at, readers_at)
    lower = jnp.arange(n)[None, :] < jnp.arange(n)[:, None]
    return ready & ~(dep & ready[None, :] & lower).any(axis=1)


def cohort_select(s: PPCCState, item: jax.Array, is_write: jax.Array,
                  ready: jax.Array) -> jax.Array:
    """Pairwise-independent subset of ``ready``, in one vectorized step:
    slot i is selected iff no lower-indexed *ready* slot's op depends on
    it.  (A conservative relaxation of the sequential greedy set — a
    ready slot excluded by an also-excluded lower slot just retries next
    quantum.)  The lowest ready slot is always selected, so a
    cohort-stepped engine makes progress every iteration.
    """
    writers_at, readers_at = _op_tables(s, item)
    return _select(s, item, is_write, ready, writers_at, readers_at)


def _try_ops(s, item, is_write, mask, writers_at, readers_at):
    n = s.n
    eye = jnp.eye(n, dtype=bool)

    # Lock verdicts ride the op tables: the owner of item_i is the unique
    # holder k whose write set covers it, i.e. writers_at[i, k] &
    # haslocks[k].  prec[i, i] is invariantly False, so the
    # precedes-owner test needs no self-exclusion.
    owner_at = writers_at & s.haslocks[None, :]          # bool[n, n]
    locked_by_other = (owner_at & ~eye).any(axis=1)
    i_prec_owner = (owner_at & s.prec).any(axis=1)
    lock_v = jnp.where(locked_by_other,
                       jnp.where(i_prec_owner, ABORT, BLOCK), PROCEED)

    act = s.active[None, :]
    new_writers = writers_at & act & ~eye & ~s.prec      # read: ~prec[i, k]
    new_readers = readers_at & act & ~eye & ~s.prec.T    # write: ~prec[k, i]

    any_new_r = new_writers.any(axis=1)
    rule_r = (~s.preceded) & ~(new_writers & s.preceding[None, :]).any(1)
    any_new_w = new_readers.any(axis=1)
    rule_w = (~s.preceding) & ~(new_readers & s.preceded[None, :]).any(1)

    any_new = jnp.where(is_write, any_new_w, any_new_r)
    rule_ok = jnp.where(is_write, rule_w, rule_r)
    allowed = (lock_v == PROCEED) & (~any_new | rule_ok) & mask
    verdict = jnp.where(lock_v != PROCEED, lock_v,
                        jnp.where(allowed, PROCEED, BLOCK))
    verdict = jnp.where(mask, verdict, BLOCK).astype(jnp.int32)
    reason = jnp.where(mask & (verdict == BLOCK),
                       jnp.where(locked_by_other, R_LOCK, R_RULE),
                       R_NONE).astype(jnp.int32)

    ok_r = allowed & ~is_write
    ok_w = allowed & is_write
    add_r = new_writers & ok_r[:, None]                  # arcs i -> k
    add_w = new_readers & ok_w[:, None]                  # arcs k -> i
    return s._replace(
        read_set=B.or_rowwise(s.read_set, item, ok_r),
        write_set=B.or_rowwise(s.write_set, item, ok_w),
        prec=s.prec | add_r | add_w.T,
        preceding=s.preceding | (ok_r & any_new_r) | add_w.any(axis=0),
        preceded=s.preceded | (ok_w & any_new_w) | add_r.any(axis=0),
    ), verdict, reason


def try_ops_batched(s: PPCCState, item: jax.Array, is_write: jax.Array,
                    mask: jax.Array) -> Tuple[PPCCState, jax.Array]:
    """One protocol op per slot, resolved in a single vectorized step.

    Slot i (where ``mask[i]``) performs (item[i], is_write[i]) against the
    pre-state.  Sequential equivalence requires the masked ops to be
    pairwise independent (use ``cohort_select``).  Unmasked lanes are
    inert and report BLOCK.  Returns (state, verdict int32[n]).
    """
    writers_at, readers_at = _op_tables(s, item)
    s2, verdict, _ = _try_ops(s, item, is_write, mask, writers_at,
                              readers_at)
    return s2, verdict


def cohort_step(s: PPCCState, item: jax.Array, is_write: jax.Array,
                ready: jax.Array
                ) -> Tuple[PPCCState, jax.Array, jax.Array, jax.Array]:
    """``cohort_select`` + ``try_ops_batched`` sharing one set of
    gathers (the engine hot path).  Returns (state, verdict, selected,
    block-reason codes — ``R_LOCK``/``R_RULE`` on BLOCK lanes).
    """
    writers_at, readers_at = _op_tables(s, item)
    sel = _select(s, item, is_write, ready, writers_at, readers_at)
    s2, verdict, reason = _try_ops(s, item, is_write, sel, writers_at,
                                   readers_at)
    return s2, verdict, sel, reason


class FusedStep(NamedTuple):
    """Result of one fused cohort step (``cohort_step_fused``)."""

    state: PPCCState
    verdict: jax.Array       # int32[n] read-phase verdicts (BLOCK unmasked)
    selected: jax.Array      # bool[n]  pairwise-independent admitted set
    degree: jax.Array        # int32[n] conflict degree among ready ops
    won: jax.Array           # bool[n]  wait-to-commit lock winners
    can_commit: jax.Array    # bool[n]  Fig. 4 test on the post-ops state
    reason: jax.Array        # int32[n] block-reason codes (R_LOCK/R_RULE)


def cohort_step_fused(s: PPCCState, item: jax.Array, is_write: jax.Array,
                      ready: jax.Array, wc_mask: jax.Array, *,
                      order: str = "index", exact_wc: bool = False,
                      relations=None) -> FusedStep:
    """One cohort step, fused end to end (DESIGN.md §3): conflict/party
    matrix → degree → ordered independence selection → op verdicts +
    apply → wait-to-commit feasibility/winners → commit test — a single
    pass over the packed words, replacing the engine's former
    ``cohort_step`` + ``wc_acquire_many`` + ``can_commit_many`` chain
    (which re-gathered the op tables and re-joined the write words).

    ``ready`` marks read-phase ops, ``wc_mask`` the slots attempting
    wait-to-commit lock acquisition this quantum; the engine guarantees
    they are disjoint (each slot is in exactly one phase).  That
    disjointness is what makes computing the write-write join ``ww`` on
    the PRE-state exact for the lock phase: rows consulted are wc slots
    and columns are current/candidate holders, and neither's write row
    can be changed by this quantum's read-phase ops (a slot's row is
    only ever mutated by its own op).

    ``order`` picks the selection priority: ``"index"`` is bit-identical
    to ``cohort_select`` (slot order); ``"degree"`` admits in ascending
    conflict-degree order (ties by index) — low-degree ops go first, so
    a hub op stops shutting out its whole neighbourhood.  Either order
    selects its minimum-key ready slot, so the engine makes progress
    every iteration.  ``exact_wc`` switches the lock phase from the
    one-step relaxation to the sequential-greedy scan
    (``wc_acquire_many(exact=True)`` semantics).

    ``relations`` optionally supplies the pairwise relations from ONE
    launch of the cohort-step megakernel — the tuple
    ``kernels.ops.megastep_relations(...)`` returns (its trailing
    ``dirty_hit`` is ignored here) — in place of the inline jnp joins;
    both are bit-identical (``tests/test_megastep.py``).  The compiled
    megakernel path is for the TPU; elsewhere the inline twin is the
    fast path.
    """
    n = s.n
    idx = jnp.arange(n, dtype=jnp.int32)
    if relations is None:
        rel = compute_relations(s, item, is_write)
        dep, ww, writers_at, readers_at, deg, lockhit = \
            relations_inputs(rel, ready, s.haslocks)
    else:
        dep, ww, writers_at, readers_at, deg, lockhit = relations[:6]
    if order == "index":
        key = idx
    elif order == "degree":
        key = deg * n + idx          # unique keys: ties broken by slot
    else:
        raise ValueError(f"unknown selection order: {order!r}")
    before = key[None, :] < key[:, None]
    sel = ready & ~(dep & ready[None, :] & before).any(axis=1)
    s2, verdict, reason = _try_ops(s, item, is_write, sel, writers_at,
                                   readers_at)

    feasible = wc_mask & ~lockhit
    if exact_wc:
        def step(won, i):
            ok = feasible[i] & ~(ww[i] & won).any()
            return won.at[i].set(ok), ok

        won, _ = jax.lax.scan(step, jnp.zeros(n, bool), idx)
    else:
        lower = idx[None, :] < idx[:, None]
        won = feasible & ~(ww & feasible[None, :] & lower).any(axis=1)
    s3 = s2._replace(haslocks=s2.haslocks | won)
    return FusedStep(s3, verdict, sel, deg, won, can_commit_many(s3),
                     reason)


# --------------------------------------------------------------------------
# delta-maintained relations (DESIGN.md §3.2)
#
# The four pairwise relations a fused cohort step consumes (``dep``,
# ``ww``, ``writers_at``, ``readers_at``) are functions of (packed set
# words, per-slot op cursor, active flags) only — the per-quantum
# ``deg``/``lockhit`` vectors derive from them with the live
# ``ready``/``haslocks`` masks.  A single step mutates few slots, so the
# engine carries the matrices across iterations and recomputes only the
# *dirty rows* — then mirrors them into the columns (``dep``/``ww`` are
# symmetric; clean rows of the op tables are provably unchanged, see
# ``dirty_slots``).
# --------------------------------------------------------------------------


class Relations(NamedTuple):
    """Loop-carried pairwise relations of the fused cohort step.

    Invariant (when the engine's delta path is on): equal to
    ``compute_relations(state, item, is_write)`` for the state and op
    cursor the NEXT ``cohort_step_fused`` call will see.
    """

    dep: jax.Array           # bool[n, n] op dependence, diagonal False
    ww: jax.Array            # bool[n, n] write-write overlap, diag False
    writers_at: jax.Array    # bool[n, n] [i, k] = item_i in write_set[k]
    readers_at: jax.Array    # bool[n, n] [i, k] = item_i in read_set[k]


def empty_relations(n: int = 0) -> Relations:
    """A shape-(n, n) Relations pytree; n=0 when the delta path is off
    (keeps the engine-state tree structure constant)."""
    z = jnp.zeros((n, n), jnp.bool_)
    return Relations(z, z, z, z)


def compute_relations(s: PPCCState, item: jax.Array, is_write: jax.Array
                      ) -> Relations:
    """Full O(n²·w) recompute — the inline twin of the megakernel's
    first four outputs, and the delta path's overflow fallback."""
    writers_at, readers_at = _op_tables(s, item)
    dep = _dep_matrix(s, item, is_write, writers_at, readers_at)
    ww = B.any_overlap(s.write_set, s.write_set) & \
        ~jnp.eye(s.n, dtype=bool)
    return Relations(dep, ww, writers_at, readers_at)


def relations_inputs(rel: Relations, ready: jax.Array,
                     haslocks: jax.Array):
    """Attach the per-quantum ``deg``/``lockhit`` vectors to carried
    relations: the 6-tuple ``cohort_step_fused(relations=...)`` takes."""
    deg = (rel.dep & ready[None, :]).sum(axis=1, dtype=jnp.int32)
    lockhit = (rel.ww & haslocks[None, :]).any(axis=1)
    return (rel.dep, rel.ww, rel.writers_at, rel.readers_at, deg, lockhit)


def dirty_slots(old: PPCCState, new: PPCCState, old_item: jax.Array,
                new_item: jax.Array, old_isw: jax.Array,
                new_isw: jax.Array) -> jax.Array:
    """bool[n]: slots whose relation ROWS may differ between the old and
    new (state, op cursor) pairs.

    Three triggers:
      * ``rowchange`` — any bit of the slot's own read/write words
        changed (covers its ``ww`` row/column and its own membership in
        other parties);
      * ``cursor`` — the slot's pending (item, kind) changed (all four
        of its rows are keyed on the cursor);
      * ``member`` — the bit of the slot's item is in the UNION of all
        slots' word deltas: some third slot joined or left this row's
        party / op tables.
    Active-flag flips need no trigger of their own: a flip co-occurs
    with the flipping slot's words being cleared (commit/abort/begin),
    so any row it participated in is caught by ``member``, and a slot
    activating with empty words is in no party either way.
    """
    delta = (old.read_set ^ new.read_set) | (old.write_set ^ new.write_set)
    rowchange = B.any_bit(delta)
    cursor = (old_item != new_item) | (old_isw != new_isw)
    union = B.or_reduce(delta, axis=0)                   # uint32[W]
    w, b = B.word_bit(new_item)
    member = ((union[w] >> b) & jnp.uint32(1)).astype(bool)
    return rowchange | cursor | member


def dirty_slab(dirty: jax.Array, k: int):
    """Gather the dirty-row ids into a static K-slot slab.

    Returns (slab int32[k] — ids ascending, padded with n; valid
    bool[k]; count int32 — the TRUE dirty count, > k on overflow)."""
    n = dirty.shape[0]
    slab = jnp.nonzero(dirty, size=k, fill_value=n)[0].astype(jnp.int32)
    return slab, slab < n, dirty.sum(dtype=jnp.int32)


def scatter_relations(rel: Relations, dep_rows: jax.Array,
                      ww_rows: jax.Array, wat_rows: jax.Array,
                      rat_rows: jax.Array, slab: jax.Array,
                      valid: jax.Array) -> Relations:
    """Write a row-slab kernel's (K, n) row blocks back into the carried
    matrices: rows for all four relations, PLUS mirrored columns for the
    symmetric ``dep``/``ww`` (a dirty slot's column equals its row; the
    op tables' clean rows are unchanged by the dirty-row rule, so they
    need no column fix-up).  Invalid slab entries route to row n and
    drop."""
    n = rel.dep.shape[0]
    tgt = jnp.where(valid, slab, n)
    dep = rel.dep.at[tgt, :].set(dep_rows, mode="drop")
    dep = dep.at[:, tgt].set(dep_rows.T, mode="drop")
    ww = rel.ww.at[tgt, :].set(ww_rows, mode="drop")
    ww = ww.at[:, tgt].set(ww_rows.T, mode="drop")
    return Relations(
        dep=dep, ww=ww,
        writers_at=rel.writers_at.at[tgt, :].set(wat_rows, mode="drop"),
        readers_at=rel.readers_at.at[tgt, :].set(rat_rows, mode="drop"),
    )


def wc_acquire_many(s: PPCCState, mask: jax.Array, exact: bool = True
                    ) -> Tuple[PPCCState, jax.Array]:
    """Batched all-or-nothing wait-to-commit lock acquisition.

    With ``exact=True`` (default) this matches the event engine's
    sequential greedy semantics exactly: slot i wins iff its whole write
    set is unlocked (or self-locked) and no lower-indexed *winner*'s
    write set overlaps it (disjoint lock words).  ``exact=False`` uses
    the vectorized one-step relaxation (no lower-indexed *feasible*
    overlap) — a subset of the greedy winners; shut-out slots simply
    wait as a sequential loser would.  Losers keep the state they had
    (no partial locks).  Returns (state, got bool[n]).
    """
    n = s.n
    idx = jnp.arange(n, dtype=jnp.int32)
    overlap = B.any_overlap(s.write_set, s.write_set) & \
        ~jnp.eye(n, dtype=bool)
    # feasible[i] <=> no *other* current holder's write words intersect
    # i's (self-held locks pass — re-acquisition is idempotent).  One
    # word-wise self-join; no per-item owner array exists to reconcile.
    feasible = mask & ~(overlap & s.haslocks[None, :]).any(axis=1)

    if exact:
        def step(won, i):
            ok = feasible[i] & ~(overlap[i] & won).any()
            return won.at[i].set(ok), ok

        won, _ = jax.lax.scan(step, jnp.zeros(n, bool), idx)
    else:
        lower = idx[None, :] < idx[:, None]
        won = feasible & ~(overlap & feasible[None, :] & lower).any(axis=1)
    return s._replace(haslocks=s.haslocks | won), won


def can_commit_many(s: PPCCState) -> jax.Array:
    """Vectorized Fig. 4 test: slot i may commit iff no active
    transaction precedes it."""
    return ~((s.prec & s.active[:, None]).any(axis=0))


def _leave_many(s: PPCCState, mask: jax.Array) -> PPCCState:
    return s._replace(
        read_set=B.clear_rows(s.read_set, mask),
        write_set=B.clear_rows(s.write_set, mask),
        prec=s.prec & ~mask[:, None] & ~mask[None, :],
        active=s.active & ~mask,
        haslocks=s.haslocks & ~mask,
    )


def commit_many(s: PPCCState, mask: jax.Array) -> PPCCState:
    """Batched ``commit``: exact — leaves of distinct slots commute."""
    return _leave_many(s, mask)


def abort_many(s: PPCCState, mask: jax.Array) -> PPCCState:
    """Batched ``abort``: exact — leaves of distinct slots commute."""
    return _leave_many(s, mask)


def default_admit_block(n: int) -> int:
    """Block size for ``admit_ops_blocked``: the fast path only fires
    when a block has no same-slot pair, and over ``n`` slots a random
    block of B ops collides with probability ~ B²/2n (birthday), so B
    must track sqrt(n).  B ~ sqrt(n) (~40% collision rate) is the
    measured optimum on the ``sched_admit`` shape at this commit
    (DESIGN.md §4): the derived-lock/packed-word protocol state made
    the sequential fallback cheap enough that fewer, larger blocks
    beat the old sqrt(n)/2 low-collision point; the original fixed
    B=32 at n=256 (~90% collisions) still ran *slower* than the plain
    scan."""
    b = 1
    while (2 * b) ** 2 <= n:        # largest power of two <= sqrt(n)
        b *= 2
    return max(8, b)


def admit_order_degree(s: PPCCState, txn: jax.Array, item: jax.Array,
                       is_write: jax.Array, valid: jax.Array) -> jax.Array:
    """Degree-ordered admission permutation (DESIGN.md §4).

    Primary key: each op's occurrence rank within its own transaction —
    rank-0 ops of every txn first, then rank-1, … — so consecutive ops
    almost never share a slot and the blocked fast path stops falling
    back on same-slot collisions.  Secondary key: the issuing txn's
    conflict degree over the batch's would-be read/write sets (RAW out
    + WAR in + WW, self-conflicts stripped — the same total-involvement
    key as ``sched.scheduler.ppcc_tick(order="degree")``, and on the
    scheduler path the degrees are free from the fused conflict
    kernel).  Ties break by original index, keeping the permutation
    deterministic.  Returns int32[m] — op positions in admission order.
    """
    m = txn.shape[0]
    d_pad = s.words * B.WORD
    # scatter each op's bit; invalid/other-kind lanes route to an OOB
    # row and drop, so every stored value is True (duplicate-safe)
    t_r = jnp.where(valid & ~is_write, txn, s.n)
    t_w = jnp.where(valid & is_write, txn, s.n)
    read_b = B.pack(jnp.zeros((s.n, d_pad), bool)
                    .at[t_r, item].set(True, mode="drop"))
    write_b = B.pack(jnp.zeros((s.n, d_pad), bool)
                     .at[t_w, item].set(True, mode="drop"))
    raw = B.any_overlap(read_b, write_b)
    ww = B.any_overlap(write_b, write_b)
    self_r = jnp.diagonal(raw).astype(jnp.int32)
    deg = (raw.sum(axis=1, dtype=jnp.int32) - self_r
           + raw.sum(axis=0, dtype=jnp.int32) - self_r
           + ww.sum(axis=1, dtype=jnp.int32)
           - jnp.diagonal(ww).astype(jnp.int32))
    idx = jnp.arange(m, dtype=jnp.int32)
    same_txn = txn[:, None] == txn[None, :]
    rank = (same_txn & (idx[None, :] < idx[:, None])).sum(
        axis=1, dtype=jnp.int32)
    return jnp.lexsort((idx, deg[txn], rank)).astype(jnp.int32)


def admit_ops_blocked(s: PPCCState, txn: jax.Array, item: jax.Array,
                      is_write: jax.Array, valid: jax.Array,
                      block: int = None,
                      order: str = "index") -> BatchVerdict:
    """Exactly ``admit_ops``, but blocked: the op list is cut into blocks
    of ``block`` consecutive ops; a block whose (valid) ops are pairwise
    independent — disjoint parties, distinct txn slots, no same-item
    write pair — resolves in ONE vectorized ``try_ops_batched`` step,
    otherwise it falls back to the sequential inner scan.  Either branch
    is order-exact, so the result is bit-identical to ``admit_ops``.

    ``block=None`` picks ``default_admit_block(n)`` — block size must
    scale with sqrt(n) or same-slot birthday collisions push every
    block onto the sequential fallback (DESIGN.md §4); under
    ``order="degree"`` the default is 2x that, because the rank-primary
    permutation keeps same-slot pairs out of blocks.

    ``order="degree"`` forms blocks in the ``admit_order_degree``
    permutation instead of list order: same-slot pairs leave the blocks
    (rank interleaving) and low-conflict-degree transactions admit
    first.  Admission under the Prudent Precedence Rule is
    order-dependent, so this is a *different* (still rule-exact)
    admission schedule: the result is bit-identical to ``admit_ops``
    applied to the permuted op list, with verdicts reported in the
    original op positions.
    """
    n = s.n
    if order == "degree":
        # rank-primary ordering removes same-slot pairs from blocks, so
        # the birthday bound no longer caps B: measured optimum is 2x
        # the index-order default (DESIGN.md §4)
        if block is None:
            block = 2 * default_admit_block(n)
        perm = admit_order_degree(s, txn, item, is_write, valid)
        res = admit_ops_blocked(s, txn[perm], item[perm], is_write[perm],
                                valid[perm], block=block)
        m = txn.shape[0]
        inv = jnp.zeros(m, jnp.int32).at[perm].set(
            jnp.arange(m, dtype=jnp.int32))
        return BatchVerdict(admitted=res.admitted[inv],
                            blocked=res.blocked[inv],
                            aborted=res.aborted[inv], state=res.state)
    if order != "index":
        raise ValueError(f"unknown admission order: {order!r}")
    if block is None:
        block = default_admit_block(n)
    m = txn.shape[0]
    pad = (-m) % block
    if pad:
        txn = jnp.concatenate([txn, jnp.zeros(pad, txn.dtype)])
        item = jnp.concatenate([item, jnp.zeros(pad, item.dtype)])
        is_write = jnp.concatenate([is_write, jnp.zeros(pad, bool)])
        valid = jnp.concatenate([valid, jnp.zeros(pad, bool)])
    nb = txn.shape[0] // block
    ops = jax.tree.map(lambda a: a.reshape(nb, block),
                       (txn, item, is_write, valid))

    def blk(s: PPCCState, op):
        t, x, w, v = op
        me = jnp.arange(n)[None, :] == t[:, None]        # [B, n]
        others = jnp.where(w[:, None], B.item_cols(s.read_set, x),
                           B.item_cols(s.write_set, x))
        party = (others & s.active[None, :] & ~me) | me
        dep = _any_overlap(party, party)
        dep = dep | ((x[:, None] == x[None, :]) & (w[:, None] | w[None, :]))
        dep = dep | (t[:, None] == t[None, :])
        dep = dep & ~jnp.eye(block, dtype=bool)
        dep = dep & v[:, None] & v[None, :]
        indep = ~dep.any()

        def fast(s: PPCCState):
            # scatter one op per slot; invalid lanes dropped via OOB index
            tgt = jnp.where(v, t, n)
            mask_full = jnp.zeros(n, bool).at[tgt].set(v, mode="drop")
            item_full = jnp.zeros(n, x.dtype).at[tgt].set(x, mode="drop")
            w_full = jnp.zeros(n, bool).at[tgt].set(w, mode="drop")
            s2, verd_full = try_ops_batched(s, item_full, w_full, mask_full)
            return s2, verd_full[jnp.minimum(t, n - 1)]

        def slow(s: PPCCState):
            def step(s, op1):
                t1, x1, w1, v1 = op1
                s2, verdict = try_op(s, t1, x1, w1)
                s2 = jax.tree.map(lambda a, b: jnp.where(v1, a, b), s2, s)
                return s2, jnp.where(v1, verdict, BLOCK)
            return jax.lax.scan(step, s, (t, x, w, v))

        return jax.lax.cond(indep, fast, slow, s)

    s, verds = jax.lax.scan(blk, s, ops)
    verdicts = verds.reshape(-1)[:m]
    valid = valid.reshape(-1)[:m] if pad else valid[:m]
    return BatchVerdict(
        admitted=(verdicts == PROCEED) & valid,
        blocked=(verdicts == BLOCK) & valid,
        aborted=(verdicts == ABORT) & valid,
        state=s,
    )
