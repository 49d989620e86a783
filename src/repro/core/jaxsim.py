"""Tensorised, event-synchronous discrete-event simulator in JAX.

The event-heap oracle (``pysim``) is a pointer-chasing CPU artifact; this
module is the TPU-native reformulation (DESIGN.md §2): the entire
simulator state is a fixed-shape pytree and a ``lax.while_loop``
advances it via masked tensor updates.  Two step modes share that state:

* ``cohort`` (default, DESIGN.md §2.3) — each iteration processes the
  full *cohort* of ready slots: every slot whose ``next_time`` falls
  inside the current time quantum ``[t_min, t_min + cohort_dt]``.  The
  cohort is split by event kind and resolved with the batched protocol
  primitives in ``repro.core.ppcc`` (``try_ops_batched`` over a
  ``cohort_select``-ed independent subset, ``wc_acquire_many``,
  ``commit_many`` / ``abort_many`` / ``begin_many``); non-independent
  ops are deferred one iteration, so progress is guaranteed.
* ``event`` — the seed engine: one iteration processes exactly one
  event (``argmin`` over next-event times) via a ``lax.switch``.  Kept
  as the before/after baseline and the parity target for tests.

FCFS multi-server resource pools become ``free_at`` vectors: a request
reserves ``argmin(free_at)`` at request time, which reproduces FCFS
because events are processed in (quantised) time order; cohort mode
reserves for all requesters in one slot-ordered ``lax.scan``.

All three protocols run on the same tensor state:

* ``ppcc`` — the paper's protocol via ``repro.core.ppcc`` primitives,
* ``2pl`` — strict 2PL (read/write sets double as S/X lock tables),
* ``occ``  — backward validation via a per-transaction ``dirty`` bitmap
  (write sets of transactions that committed during the reader's
  lifetime), re-checked at flush end to close the K-R overlap window.

All set state — the protocol read/write sets and the OCC ``dirty``
map — is packed ``uint32[n, ceil(d/32)]`` bitset words
(``repro.core.bitset``, DESIGN.md §1.1); set algebra in the engine body
is word-wise AND/OR/popcount.

``vmap`` over (seed, write_prob, mpl, block_timeout) turns a parameter
sweep into one SPMD computation; ``examples/ppcc_sweep.py`` shards such
a sweep over the production mesh's data axis.

MPL can additionally be a *runtime* parameter (DESIGN.md §2.4): the
slot axis pads to a static bucket and ``make_padded_engine`` returns
``run(seed, mpl, rt)`` where only the first ``mpl`` slots ever
activate — one compiled executable serves every MPL point.  The
remaining workload axes are runtime values too (``RtParams``: live
item count below the ``d`` bit bucket, write_prob, txn-length bounds
below the ``max_ops`` bucket, live resource counts below the pool
buckets), and the samplers draw at the bucket-invariant ``ops_draw``
width — so a run inside a wider bucket is bit-identical to its
exact-shape twin.  ``repro.core.sweep`` builds on this to run a whole
(protocol × MPL × seed) figure grid — or ALL paper figures at once
(``run_grid``) — as a single jitted fleet call, optionally
shard_map-ed over the host (or multi-host pod) mesh.
Fleet engines (``fleet=True``) drop the quiet-iteration ``lax.cond``
gates (under vmap they decay to select-both-branches) and draw fresh
transactions from a pre-sampled pool (``pool > 0``) instead of calling
``sample_txns`` in-loop.

Semantics are validated statistically against the oracle in
``tests/test_jaxsim_vs_pysim.py`` (same model, different tie-breaking).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from . import bitset as B
from . import ppcc as P
from ..obs import metrics as M
from .types import SimParams, SimResult

INF = jnp.float32(1e30)

# Op-axis draw quantum (DESIGN.md §2.4): samplers ALWAYS draw at
# ``bucket(max_ops, OP_QUANTUM)`` and slice to the engine's op capacity,
# so engines whose op buckets differ (a mean-8 figure inside the
# max_ops=20 grid bucket vs its native max_ops=12 trace) consume the
# SAME PRNG stream — the bucketing bit-identity bar depends on it.  20
# is the paper grid's largest op list (txn_size 16 + spread 4).
OP_QUANTUM = 20

# event kinds
EV_ATTEMPT, EV_DISK_DONE, EV_FLUSH_DONE, EV_TIMEOUT, EV_RESTART = range(5)
# phases
PH_READ, PH_BLOCKED, PH_WC_LOCK, PH_WC_PREC, PH_FLUSH, PH_RESTART, PH_OFF \
    = range(7)


class RtParams(NamedTuple):
    """Workload axes that are RUNTIME values, not trace shapes.

    Every field is a traced scalar (int32 / float32) riding the engine
    state as loop-invariant data, so one compiled executable serves any
    paper figure whose *shapes* fit the engine's static buckets
    (``EngCfg.d`` item bits, ``EngCfg.max_ops`` op slots,
    ``EngCfg.cpus`` / ``EngCfg.disks`` pool entries).  Values must not
    exceed their buckets: items are sampled below ``d``, ops beyond
    ``len_hi`` stay ``-1`` pads, and resource entries past
    ``cpus`` / ``disks`` hold ``free_at = INF`` so FCFS ``argmin`` never
    picks them.
    """
    d: jax.Array            # live item count (<= cfg.d)
    write_prob: jax.Array   # f32
    len_lo: jax.Array       # txn length bounds (len_hi <= cfg.max_ops)
    len_hi: jax.Array
    cpus: jax.Array         # live pool sizes (<= cfg.cpus / cfg.disks)
    disks: jax.Array
    zipf_theta: jax.Array   # f32 hot-spot skew (0 = uniform, bit-exact
                            # legacy streams; see _zipf_map)


def rt_of(p: SimParams) -> RtParams:
    """The runtime-axis values of a parameter setting."""
    return RtParams(
        d=jnp.int32(p.db_size), write_prob=jnp.float32(p.write_prob),
        len_lo=jnp.int32(max(2, p.txn_size_mean - p.txn_size_spread)),
        len_hi=jnp.int32(p.txn_size_mean + p.txn_size_spread),
        cpus=jnp.int32(p.num_cpus), disks=jnp.int32(p.num_disks),
        zipf_theta=jnp.float32(getattr(p, "zipf_theta", 0.0)))


class EngState(NamedTuple):
    now: jax.Array               # f32 scalar
    key: jax.Array               # PRNG
    pstate: P.PPCCState          # protocol tensor state
    dirty: jax.Array             # uint32[N, W] (OCC validation bitmap)
    kinds: jax.Array             # int8[N, L]  op kinds (-1 pad)
    items: jax.Array             # int32[N, L]
    op_idx: jax.Array            # int32[N]
    phase: jax.Array             # int8[N]
    next_time: jax.Array         # f32[N]
    next_kind: jax.Array         # int8[N]
    deadline: jax.Array          # f32[N] block timeout deadline
    flush_left: jax.Array        # int32[N]
    cpu_free: jax.Array          # f32[C]
    disk_free: jax.Array         # f32[K]
    commits: jax.Array           # int32
    aborts: jax.Array
    blocks: jax.Array
    ops_done: jax.Array
    iters: jax.Array
    pool_kinds: jax.Array        # int8[P, L] pre-sampled txn pool (P=0: off)
    pool_items: jax.Array        # int32[P, L]
    pool_next: jax.Array         # int32 next pool row to hand out
    rt: RtParams                 # runtime workload axes (loop-invariant)
    rel: P.Relations             # carried (n,n) relation tables when
                                 # EngCfg.delta (else (0,0) placeholders);
                                 # invariant: equals compute_relations of
                                 # pstate + this iteration's op cursor
    tm: M.Telemetry              # telemetry accumulators when
                                 # EngCfg.telemetry (else 0-size
                                 # placeholders, same pytree structure)


@dataclasses.dataclass(frozen=True)
class EngCfg:
    protocol: str
    n: int                       # MPL slots (static bucket)
    d: int                       # db size (static item-bit bucket; the
                                 # live item count is rt.d <= d)
    max_ops: int                 # op-list capacity (static bucket)
    ops_draw: int                # sampler draw width: bucket(max_ops,
                                 # OP_QUANTUM) — see OP_QUANTUM
    cpus: int                    # resource-pool capacities (static
    disks: int                   # buckets; live sizes are rt.cpus/disks)
    cpu_mean: float
    cpu_spread: float
    io_mean: float
    io_spread: float
    write_prob: float
    len_lo: int
    len_hi: int
    block_timeout: float
    restart_mean: float
    horizon: float
    max_iters: int
    cohort_dt: float = 0.0       # time-quantum width for cohort stepping
    fleet: bool = False          # body will run under vmap lanes: drop the
                                 # quiet-iteration lax.cond gates (they decay
                                 # to full-state selects under batching)
    pool: int = 0                # >0: pre-sample this many transactions at
                                 # init and pop on commit instead of calling
                                 # sample_txns per iteration (fleet hot-path:
                                 # in-loop sampling was ~2/3 of body cost)
    fused: bool = True           # ppcc: one fused cohort step (conflict →
                                 # select → verdicts → wc) per iteration
                                 # instead of the multipass chain; both
                                 # paths are bit-identical (DESIGN.md §3)
    order: str = "index"         # fused selection priority: "index" (the
                                 # multipass-identical default) | "degree"
    megakernel: bool = False     # fused relations from the Pallas
                                 # cohort-step megakernel (one launch per
                                 # quantum); compiled path — real
                                 # accelerators only, CPU keeps the
                                 # bit-identical jnp twin
    delta: bool = False          # ppcc+fused: carry the (n,n) relation
                                 # tables in the loop state and update
                                 # only the dirty rows per iteration via
                                 # the row-slab kernel (DESIGN.md §3.2);
                                 # bit-identical to full recompute
    delta_k: int = 0             # dirty-row slab capacity (static); a
                                 # non-fleet step falls back to full
                                 # recompute past it, a fleet step loops
                                 # K-sized chunks until the dirty set is
                                 # drained
    telemetry: bool = False      # carry obs.metrics accumulators in the
                                 # loop state (DESIGN.md §8); off keeps
                                 # 0-size placeholder leaves so results
                                 # and compiled code are bit-identical
    trace_every: int = 0         # >0: sample the time-series ring
                                 # buffer every this many iterations
    trace_len: int = 256         # ring-buffer rows (static shape)


def _cfg(p: SimParams, max_iters: int) -> EngCfg:
    max_ops = p.txn_size_mean + p.txn_size_spread
    return EngCfg(
        protocol="", n=p.mpl, d=p.db_size, max_ops=max_ops,
        ops_draw=B.bucket(max_ops, OP_QUANTUM),
        cpus=p.num_cpus, disks=p.num_disks,
        cpu_mean=p.cpu_burst_mean, cpu_spread=p.cpu_burst_spread,
        io_mean=p.io_time_mean, io_spread=p.io_time_spread,
        write_prob=p.write_prob,
        len_lo=max(2, p.txn_size_mean - p.txn_size_spread),
        len_hi=p.txn_size_mean + p.txn_size_spread,
        block_timeout=p.block_timeout, restart_mean=p.restart_delay_mean,
        horizon=p.horizon, max_iters=max_iters)


# --------------------------------------------------------------------------
# workload sampling (in-kernel)
# --------------------------------------------------------------------------

def _zipf_cdf(cfg: EngCfg, rt: RtParams) -> jax.Array:
    """CDF over item ranks for Zipf(``rt.zipf_theta``) hot-spot skew.

    Static ``cfg.d`` width with ranks past the live ``rt.d`` masked to
    zero weight, so the shape stays bucket-invariant.  Loop-invariant —
    hoist it out of per-op scans."""
    ranks = jnp.arange(cfg.d, dtype=jnp.float32) + 1.0
    w = jnp.where(jnp.arange(cfg.d) < rt.d,
                  ranks ** (-rt.zipf_theta), 0.0)
    return jnp.cumsum(w) / jnp.maximum(w.sum(), jnp.float32(1e-30))


def _zipf_map(cdf: jax.Array, raw: jax.Array, rt: RtParams) -> jax.Array:
    """Remap uniform draws ``raw`` in [0, rt.d) through the Zipf CDF.

    Sampler-only inverse-CDF transform: the PRNG draw itself is kept, so
    at ``zipf_theta == 0`` the returned items are bit-identical to the
    legacy uniform stream (the ``where`` selects ``raw`` untouched)."""
    u = raw.astype(jnp.float32) / rt.d.astype(jnp.float32)
    z = jnp.searchsorted(cdf, u, side="right").astype(raw.dtype)
    z = jnp.minimum(z, rt.d - 1)
    return jnp.where(rt.zipf_theta > 0, z, raw)


def sample_txn(key: jax.Array, cfg: EngCfg, rt: RtParams
               ) -> Tuple[jax.Array, jax.Array]:
    """One transaction: (kinds int8[L], items int32[L]); -1 pads.

    Workload bounds (``rt.len_lo/len_hi``, ``rt.write_prob``, ``rt.d``)
    are runtime scalars, and all draws use the ``cfg.ops_draw`` width
    (never ``cfg.max_ops``) so the PRNG stream is invariant to the op
    bucket — a figure run inside a wider bucket samples the exact same
    transactions (see OP_QUANTUM).
    """
    D = cfg.ops_draw
    kl, kw, ki = jax.random.split(key, 3)
    length = jax.random.randint(kl, (), rt.len_lo, rt.len_hi + 1)
    want_w = jax.random.uniform(kw, (D,)) < rt.write_prob
    keys = jax.random.split(ki, D)
    zcdf = _zipf_cdf(cfg, rt)      # loop-invariant: hoisted off the scan

    def slot(carry, inp):
        read_items, n_read, written = carry
        j, kk, ww = inp
        k1, k2 = jax.random.split(kk)
        avail = (jnp.arange(D) < n_read) & ~written
        n_avail = avail.sum()
        do_write = ww & (n_avail > 0)
        # pick a random available read slot (guard all-masked case)
        logits = jnp.where(avail | (n_avail == 0), 0.0, -jnp.inf)
        wpick = jax.random.categorical(k1, logits)
        item_w = read_items[wpick]
        item_r = _zipf_map(zcdf, jax.random.randint(k2, (), 0, rt.d), rt)
        item = jnp.where(do_write, item_w, item_r)
        kind = jnp.where(do_write, 1, 0).astype(jnp.int8)
        kind = jnp.where(j < length, kind, jnp.int8(-1))
        new_read = jnp.where(do_write | (j >= length), read_items,
                             read_items.at[n_read].set(item_r))
        new_n = jnp.where(do_write | (j >= length), n_read, n_read + 1)
        new_written = jnp.where(do_write,
                                written.at[wpick].set(True), written)
        return (new_read, new_n, new_written), (kind, item)

    init = (jnp.zeros(D, jnp.int32), jnp.int32(0), jnp.zeros(D, bool))
    _, (kinds, items) = jax.lax.scan(
        slot, init, (jnp.arange(D), keys, want_w))
    # ops beyond max_ops are always pads (length <= len_hi <= max_ops)
    return kinds[:cfg.max_ops], items[:cfg.max_ops].astype(jnp.int32)


def sample_txns(key: jax.Array, cfg: EngCfg, rt: RtParams, n: int
                ) -> Tuple[jax.Array, jax.Array]:
    """n transactions at once: (kinds int8[n, L], items int32[n, L]).

    Same model as ``sample_txn`` — writes target a uniformly-random
    previously-read, not-yet-written item — but all PRNG draws are
    hoisted out of the per-op scan (threefry per scan step is the cost
    that made per-commit resampling dominate the cohort engine).  Draws
    run at the bucket-invariant ``cfg.ops_draw`` width and slice to the
    engine's op capacity, like ``sample_txn``.
    """
    L = cfg.ops_draw
    kl, kw, kp, kr = jax.random.split(key, 4)
    length = jax.random.randint(kl, (n,), rt.len_lo, rt.len_hi + 1)
    want_w = jax.random.uniform(kw, (n, L)) < rt.write_prob
    read_cand = _zipf_map(_zipf_cdf(cfg, rt),
                          jax.random.randint(kr, (n, L), 0, rt.d), rt)
    pick_u = jax.random.uniform(kp, (n, L))

    rows = jnp.arange(n)

    def slot(carry, inp):
        read_items, n_read, written = carry      # [n, L], int32[n], [n, L]
        j, ww, item_r, u = inp
        avail = (jnp.arange(L)[None, :] < n_read[:, None]) & ~written
        n_avail = avail.sum(axis=1)
        do_write = ww & (n_avail > 0) & (j < length)
        # u selects uniformly among available read slots (cumsum rank)
        target = jnp.floor(u * n_avail).astype(jnp.int32) + 1
        wpick = jnp.argmax(jnp.cumsum(avail, axis=1) ==
                           target[:, None], axis=1)
        item_w = jnp.take_along_axis(read_items, wpick[:, None],
                                     axis=1)[:, 0]
        item = jnp.where(do_write, item_w, item_r)
        kind = jnp.where(do_write, 1, 0).astype(jnp.int8)
        kind = jnp.where(j < length, kind, jnp.int8(-1))
        is_read = ~do_write & (j < length)
        # append this read's item to the compacted read list
        pos = jnp.minimum(n_read, L - 1)
        cur = jnp.take_along_axis(read_items, pos[:, None], axis=1)[:, 0]
        read_items = read_items.at[rows, pos].set(
            jnp.where(is_read, item_r, cur))
        n_read = n_read + is_read
        written = written | (do_write[:, None] &
                             (jnp.arange(L)[None, :] == wpick[:, None]))
        return (read_items, n_read, written), (kind, item)

    init = (jnp.zeros((n, L), jnp.int32), jnp.zeros(n, jnp.int32),
            jnp.zeros((n, L), bool))
    _, (kinds, items) = jax.lax.scan(
        slot, init, (jnp.arange(L), want_w.T, read_cand.T, pick_u.T))
    return (jnp.moveaxis(kinds, 0, 1)[:, :cfg.max_ops],
            jnp.moveaxis(items, 0, 1)[:, :cfg.max_ops])


def _uniform(key, mean, spread):
    return jax.random.uniform(key, (), minval=mean - spread,
                              maxval=mean + spread)


# --------------------------------------------------------------------------
# resource pools: reserve argmin(free_at)
# --------------------------------------------------------------------------

def _reserve(free: jax.Array, now: jax.Array, dur: jax.Array
             ) -> Tuple[jax.Array, jax.Array]:
    idx = jnp.argmin(free)
    start = jnp.maximum(now, free[idx])
    done = start + dur
    return free.at[idx].set(done), done


# --------------------------------------------------------------------------
# protocol adapters
# --------------------------------------------------------------------------

def _try_op(cfg: EngCfg, s: EngState, i, x, is_write
            ) -> Tuple[EngState, jax.Array]:
    ps = s.pstate
    if cfg.protocol == "ppcc":
        ps2, verdict = P.try_op(ps, i, x, is_write)
        return s._replace(pstate=ps2), verdict
    if cfg.protocol == "2pl":
        others = ps.active & (jnp.arange(cfg.n) != i)
        x_held = (B.get_col(ps.write_set, x) & others).any()
        s_held = (B.get_col(ps.read_set, x) & others).any()
        ok = jnp.where(is_write, ~x_held & ~s_held, ~x_held)
        rs = B.set_bit(ps.read_set, i, x, ok & ~is_write)
        ws = B.set_bit(ps.write_set, i, x, ok & is_write)
        verdict = jnp.where(ok, P.PROCEED, P.BLOCK)
        return s._replace(pstate=ps._replace(read_set=rs, write_set=ws)), \
            verdict
    # occ: never blocks
    rs = B.set_bit(ps.read_set, i, x, ~is_write)
    ws = B.set_bit(ps.write_set, i, x, is_write)
    return s._replace(pstate=ps._replace(read_set=rs, write_set=ws)), \
        jnp.int32(P.PROCEED)


def _read_done(cfg: EngCfg, s: EngState, i) -> Tuple[EngState, jax.Array]:
    """Returns code 0=flush, 1=wait(lock), 2=wait(prec), 3=abort."""
    ps = s.pstate
    if cfg.protocol == "ppcc":
        ps2, got = P.wc_acquire_locks(ps, i)
        can = P.can_commit(ps2, i)
        code = jnp.where(~got, 1, jnp.where(can, 0, 2))
        ps3 = jax.tree.map(lambda a, b: jnp.where(got, a, b), ps2, ps)
        return s._replace(pstate=ps3), code
    if cfg.protocol == "2pl":
        return s, jnp.int32(0)
    fail = B.overlap_rows(ps.read_set[i], s.dirty[i])
    return s, jnp.where(fail, 3, 0)


def _on_commit(cfg: EngCfg, s: EngState, i) -> EngState:
    ps = s.pstate
    if cfg.protocol == "occ":
        # broadcast write set into every active transaction's dirty map
        others = ps.active & (jnp.arange(cfg.n) != i)
        dirty = jnp.where(others[:, None],
                          s.dirty | ps.write_set[i][None, :], s.dirty)
        dirty = dirty.at[i].set(jnp.uint32(0))
        s = s._replace(dirty=dirty)
    return s._replace(pstate=P.commit(ps, i))


def _on_abort(cfg: EngCfg, s: EngState, i) -> EngState:
    s = s._replace(dirty=s.dirty.at[i].set(jnp.uint32(0)))
    return s._replace(pstate=P.abort(s.pstate, i))


# --------------------------------------------------------------------------
# engine
# --------------------------------------------------------------------------

def _wake_waiters(s: EngState) -> EngState:
    waiting = (s.phase == PH_BLOCKED) | (s.phase == PH_WC_LOCK) | \
        (s.phase == PH_WC_PREC)
    return s._replace(next_time=jnp.where(waiting, s.now, s.next_time))


def _begin_txn(cfg: EngCfg, s: EngState, i, fresh: jax.Array) -> EngState:
    """(Re)start slot i: fresh -> sample new ops; else reuse (restart)."""
    key, k1, k2 = jax.random.split(s.key, 3)
    kinds_i, items_i = sample_txn(k1, cfg, s.rt)
    new_kinds = jnp.where(fresh, kinds_i, s.kinds[i])
    new_items = jnp.where(fresh, items_i, s.items[i])
    s = s._replace(
        key=key,
        kinds=s.kinds.at[i].set(new_kinds),
        items=s.items.at[i].set(new_items),
        op_idx=s.op_idx.at[i].set(0),
        pstate=P.begin(s.pstate, i),
        phase=s.phase.at[i].set(PH_READ),
        flush_left=s.flush_left.at[i].set(0),
    )
    cpu_free, done = _reserve(s.cpu_free, s.now,
                              _uniform(k2, cfg.cpu_mean, cfg.cpu_spread))
    return s._replace(
        cpu_free=cpu_free,
        next_time=s.next_time.at[i].set(done),
        next_kind=s.next_kind.at[i].set(EV_ATTEMPT))


def _ev_attempt(cfg: EngCfg, s: EngState, i) -> EngState:
    """CPU burst done (or waiter woken): run the protocol on current op."""
    done_reading = s.op_idx[i] >= (s.kinds[i] >= 0).sum()
    in_wc = (s.phase[i] == PH_WC_LOCK) | (s.phase[i] == PH_WC_PREC)

    def read_phase(s: EngState) -> EngState:
        x = s.items[i, s.op_idx[i]]
        is_write = s.kinds[i, s.op_idx[i]] == 1
        s2, verdict = _try_op(cfg, s, i, x, is_write)
        proceed = verdict == P.PROCEED
        block = verdict == P.BLOCK
        key, k1, k2 = jax.random.split(s2.key, 3)
        s2 = s2._replace(key=key)
        # --- proceed ---
        op2 = jnp.where(proceed, s.op_idx[i] + 1, s.op_idx[i])
        was_last = op2 >= (s.kinds[i] >= 0).sum()
        s2 = s2._replace(op_idx=s2.op_idx.at[i].set(op2),
                         ops_done=s2.ops_done + proceed)
        # reads pay a disk access; writes go straight to the next CPU burst
        dur_io = _uniform(k1, cfg.io_mean, cfg.io_spread)
        dur_cpu = _uniform(k2, cfg.cpu_mean, cfg.cpu_spread)

        def do_proceed(s2: EngState) -> EngState:
            def do_read(s3):
                disk_free, done = _reserve(s3.disk_free, s3.now, dur_io)
                return s3._replace(
                    disk_free=disk_free,
                    next_time=s3.next_time.at[i].set(done),
                    next_kind=s3.next_kind.at[i].set(EV_DISK_DONE),
                    phase=s3.phase.at[i].set(PH_READ))

            def do_write(s3):
                # last op: enter wait-to-commit immediately (no extra CPU
                # burst), matching the oracle's transition
                def sched_cpu(s4):
                    cpu_free, done = _reserve(s4.cpu_free, s4.now, dur_cpu)
                    return s4._replace(
                        cpu_free=cpu_free,
                        next_time=s4.next_time.at[i].set(done),
                        next_kind=s4.next_kind.at[i].set(EV_ATTEMPT),
                        phase=s4.phase.at[i].set(PH_READ))

                def to_wc(s4):
                    return s4._replace(
                        next_time=s4.next_time.at[i].set(s4.now),
                        next_kind=s4.next_kind.at[i].set(EV_ATTEMPT),
                        phase=s4.phase.at[i].set(PH_READ))
                return jax.lax.cond(was_last, to_wc, sched_cpu, s3)
            return jax.lax.cond(is_write, do_write, do_read, s2)

        def do_block(s2: EngState) -> EngState:
            was_blocked = s.phase[i] == PH_BLOCKED
            new_deadline = jnp.where(was_blocked, s.deadline[i],
                                     s.now + cfg.block_timeout)
            return s2._replace(
                phase=s2.phase.at[i].set(PH_BLOCKED),
                deadline=s2.deadline.at[i].set(new_deadline),
                next_time=s2.next_time.at[i].set(new_deadline),
                next_kind=s2.next_kind.at[i].set(EV_TIMEOUT),
                blocks=s2.blocks + jnp.where(was_blocked, 0, 1))

        def do_abort(s2: EngState) -> EngState:
            return _abort(cfg, s2, i)

        return jax.lax.cond(
            proceed, do_proceed,
            lambda s_: jax.lax.cond(block, do_block, do_abort, s_), s2)

    def wc_phase(s: EngState) -> EngState:
        s2, code = _read_done(cfg, s, i)

        def flush(s3: EngState) -> EngState:
            n_w = B.popcount(s3.pstate.write_set[i])
            s3 = s3._replace(flush_left=s3.flush_left.at[i].set(n_w),
                             phase=s3.phase.at[i].set(PH_FLUSH))
            return jax.lax.cond(n_w > 0, _flush_one,
                                lambda s4: _commit(cfg, s4, i), s3)

        def wait_lock(s3: EngState) -> EngState:
            first = s.phase[i] != PH_WC_LOCK
            new_deadline = jnp.where(first, s3.now + cfg.block_timeout,
                                     s3.deadline[i])
            return s3._replace(
                phase=s3.phase.at[i].set(PH_WC_LOCK),
                deadline=s3.deadline.at[i].set(new_deadline),
                next_time=s3.next_time.at[i].set(new_deadline),
                next_kind=s3.next_kind.at[i].set(EV_TIMEOUT))

        def wait_prec(s3: EngState) -> EngState:
            return s3._replace(
                phase=s3.phase.at[i].set(PH_WC_PREC),
                next_time=s3.next_time.at[i].set(INF),
                next_kind=s3.next_kind.at[i].set(EV_ATTEMPT))

        def _flush_one(s3: EngState) -> EngState:
            key, k1 = jax.random.split(s3.key)
            disk_free, done = _reserve(
                s3.disk_free, s3.now, _uniform(k1, cfg.io_mean,
                                               cfg.io_spread))
            return s3._replace(
                key=key, disk_free=disk_free,
                next_time=s3.next_time.at[i].set(done),
                next_kind=s3.next_kind.at[i].set(EV_FLUSH_DONE))

        return jax.lax.switch(
            code, [flush, wait_lock, wait_prec,
                   lambda s3: _abort(cfg, s3, i)], s2)

    return jax.lax.cond(done_reading | in_wc, wc_phase, read_phase, s)


def _ev_disk_done(cfg: EngCfg, s: EngState, i) -> EngState:
    key, k1 = jax.random.split(s.key)
    s = s._replace(key=key)
    done_reading = s.op_idx[i] >= (s.kinds[i] >= 0).sum()

    def to_wc(s2):                      # last read done -> wait-to-commit
        return s2._replace(
            next_time=s2.next_time.at[i].set(s2.now),
            next_kind=s2.next_kind.at[i].set(EV_ATTEMPT))

    def sched_cpu(s2):
        cpu_free, done = _reserve(
            s2.cpu_free, s2.now, _uniform(k1, cfg.cpu_mean,
                                          cfg.cpu_spread))
        return s2._replace(
            cpu_free=cpu_free,
            next_time=s2.next_time.at[i].set(done),
            next_kind=s2.next_kind.at[i].set(EV_ATTEMPT))
    return jax.lax.cond(done_reading, to_wc, sched_cpu, s)


def _ev_flush_done(cfg: EngCfg, s: EngState, i) -> EngState:
    left = s.flush_left[i] - 1
    s = s._replace(flush_left=s.flush_left.at[i].set(left))

    def more(s2):
        key, k1 = jax.random.split(s2.key)
        disk_free, done = _reserve(
            s2.disk_free, s2.now, _uniform(k1, cfg.io_mean, cfg.io_spread))
        return s2._replace(key=key, disk_free=disk_free,
                           next_time=s2.next_time.at[i].set(done),
                           next_kind=s2.next_kind.at[i].set(EV_FLUSH_DONE))
    return jax.lax.cond(left > 0, more,
                        lambda s2: _commit(cfg, s2, i), s)


def _commit(cfg: EngCfg, s: EngState, i) -> EngState:
    if cfg.protocol == "occ":
        # close the Kung-Robinson overlap window: re-validate at commit
        fail = B.overlap_rows(s.pstate.read_set[i], s.dirty[i])

        def ok(s2):
            return _commit_body(cfg, s2, i)
        return jax.lax.cond(fail, lambda s2: _abort(cfg, s2, i), ok, s)
    return _commit_body(cfg, s, i)


def _commit_body(cfg: EngCfg, s: EngState, i) -> EngState:
    s = _on_commit(cfg, s, i)
    s = s._replace(commits=s.commits + 1)
    s = _wake_waiters(s)
    return _begin_txn(cfg, s, i, fresh=jnp.bool_(True))


def _abort(cfg: EngCfg, s: EngState, i) -> EngState:
    s = _on_abort(cfg, s, i)
    key, k1 = jax.random.split(s.key)
    delay = jax.random.uniform(k1, (), minval=0.5 * cfg.restart_mean,
                               maxval=1.5 * cfg.restart_mean)
    s = _wake_waiters(s._replace(key=key, aborts=s.aborts + 1))
    return s._replace(
        phase=s.phase.at[i].set(PH_RESTART),
        next_time=s.next_time.at[i].set(s.now + delay),
        next_kind=s.next_kind.at[i].set(EV_RESTART))


def _ev_timeout(cfg: EngCfg, s: EngState, i) -> EngState:
    still = (s.phase[i] == PH_BLOCKED) | (s.phase[i] == PH_WC_LOCK)
    expired = s.now >= s.deadline[i]
    return jax.lax.cond(still & expired,
                        lambda s2: _abort(cfg, s2, i),
                        lambda s2: _ev_attempt(cfg, s2, i), s)


def _ev_restart(cfg: EngCfg, s: EngState, i) -> EngState:
    return _begin_txn(cfg, s, i, fresh=jnp.bool_(False))


# --------------------------------------------------------------------------
# cohort-stepped engine (DESIGN.md §2.3)
# --------------------------------------------------------------------------

def _reserve_cohort(cpu_free: jax.Array, disk_free: jax.Array,
                    t_req: jax.Array, cpu_dur: jax.Array,
                    io_dur: jax.Array, cpu_m: jax.Array, disk_m: jax.Array
                    ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """FCFS multi-reservation for the whole cohort in ONE scan:
    sequential ``argmin(free_at)`` reservation per masked slot, in
    slot-index order (the cohort's tie-break).  A slot requests at most
    one of {cpu, disk}, so both pools ride the same scan.  Returns
    (cpu_free', disk_free', cpu_done[n], disk_done[n])."""
    def step(carry, inp):
        cpu, disk, = carry
        t, cd, dd, cm, dm = inp
        ci = jnp.argmin(cpu)
        cdone = jnp.maximum(t, cpu[ci]) + cd
        cpu2 = jnp.where(cm, cpu.at[ci].set(cdone), cpu)
        di = jnp.argmin(disk)
        ddone = jnp.maximum(t, disk[di]) + dd
        disk2 = jnp.where(dm, disk.at[di].set(ddone), disk)
        return (cpu2, disk2), (jnp.where(cm, cdone, INF),
                               jnp.where(dm, ddone, INF))

    (cpu_free, disk_free), (cpu_done, disk_done) = jax.lax.scan(
        step, (cpu_free, disk_free), (t_req, cpu_dur, io_dur, cpu_m,
                                      disk_m))
    return cpu_free, disk_free, cpu_done, disk_done


def _try_ops_cohort(cfg: EngCfg, ps: P.PPCCState, item: jax.Array,
                    is_write: jax.Array, ready: jax.Array
                    ) -> Tuple[P.PPCCState, jax.Array, jax.Array,
                               jax.Array]:
    """Batched read-phase protocol step over a cohort of pending ops.

    Selects a pairwise-independent subset of ``ready`` (protocol
    dependent), resolves it in one vectorized step, and returns
    (state, verdict[n], selected[n], block-reason[n]).  Deferred
    (ready & ~selected) slots are retried next iteration.  Reason codes
    are ``ppcc.R_LOCK`` / ``ppcc.R_RULE`` on BLOCK lanes (every 2PL
    block is a lock wait; OCC never blocks).
    """
    n = ps.n
    idx = jnp.arange(n, dtype=jnp.int32)
    eye = jnp.eye(n, dtype=bool)
    if cfg.protocol == "ppcc":
        return P.cohort_step(ps, item, is_write, ready)
    if cfg.protocol == "2pl":
        # lock-table ops only interact when they target the same item
        # with a write involved; keep the lowest ready claimant per item.
        same = (item[:, None] == item[None, :]) & \
            (is_write[:, None] | is_write[None, :]) & ~eye
        lower = idx[None, :] < idx[:, None]
        sel = ready & ~(same & ready[None, :] & lower).any(axis=1)
        others = ps.active[None, :] & ~eye
        x_held = (B.item_cols(ps.write_set, item) & others).any(axis=1)
        s_held = (B.item_cols(ps.read_set, item) & others).any(axis=1)
        ok = jnp.where(is_write, ~x_held & ~s_held, ~x_held) & sel
        ps2 = ps._replace(
            read_set=B.or_rowwise(ps.read_set, item, ok & ~is_write),
            write_set=B.or_rowwise(ps.write_set, item, ok & is_write))
        verdict = jnp.where(ok, P.PROCEED, P.BLOCK).astype(jnp.int32)
        reason = jnp.where(sel & ~ok, P.R_LOCK, P.R_NONE).astype(jnp.int32)
        return ps2, verdict, sel, reason
    # occ: ops never read other slots' protocol state — all independent
    sel = ready
    ps2 = ps._replace(
        read_set=B.or_rowwise(ps.read_set, item, sel & ~is_write),
        write_set=B.or_rowwise(ps.write_set, item, sel & is_write))
    verdict = jnp.full(n, P.PROCEED, jnp.int32)
    return ps2, verdict, sel, jnp.zeros(n, jnp.int32)


def _wc_cohort(cfg: EngCfg, ps: P.PPCCState, dirty: jax.Array,
               wc_m: jax.Array):
    """Batched wait-to-commit step.  Returns
    (state, flush_m, wait_lock_m, wait_prec_m, abort_m)."""
    n = ps.n
    zeros = jnp.zeros(n, bool)
    if cfg.protocol == "ppcc":
        ps2, won = P.wc_acquire_many(ps, wc_m, exact=False)
        can = P.can_commit_many(ps2)
        flush_m = wc_m & won & can
        wait_prec_m = wc_m & won & ~can
        wait_lock_m = wc_m & ~won
        return ps2, flush_m, wait_lock_m, wait_prec_m, zeros
    if cfg.protocol == "2pl":
        return ps, wc_m, zeros, zeros, zeros
    fail = B.overlap_rows(ps.read_set, dirty)
    return ps, wc_m & ~fail, zeros, zeros, wc_m & fail


def _rowslab_rows(cfg: EngCfg, ps, rel, item, is_write, slab, valid):
    """Dispatch the (K, n) row-slab kernel: Pallas launch on the
    megakernel path, bit-identical jnp twin otherwise."""
    if cfg.megakernel:
        from ..kernels import ops as kops
        return kops.rowslab_relations(
            ps.read_set, ps.write_set, rel.writers_at, rel.readers_at,
            item, is_write, ps.active, slab, valid)
    from ..kernels import conflict as kconf
    return kconf.rowslab(
        ps.read_set, ps.write_set, rel.writers_at, rel.readers_at,
        item, is_write, ps.active, slab, valid)


def _delta_update(cfg: EngCfg, s: EngState, ps5, cur_item, cur_w,
                  new_kinds, new_items, op_new) -> "P.Relations":
    """Delta-maintain the carried relation tables for the next
    iteration's cursor (DESIGN.md §3.2): find the slots whose packed
    words or op cursor changed, recompute only those (K, n) rows via
    the row-slab kernel, and scatter rows + mirrored columns back.

    Non-fleet bodies guard exactness with a ``lax.cond`` full-recompute
    fallback on slab overflow.  Fleet bodies run under vmap, where a
    cond decays into both branches + select — they instead drain the
    dirty set K ids at a time in a ``while_loop``; later chunks'
    mirrored column writes repair the stale dirty×dirty cross entries,
    so the loop converges to the full recompute exactly."""
    n = cfg.n
    idx = jnp.arange(n, dtype=jnp.int32)
    nxt_i = jnp.minimum(op_new, cfg.max_ops - 1)
    nxt_item = new_items[idx, nxt_i]
    nxt_w = new_kinds[idx, nxt_i] == jnp.int8(1)
    dirty_m = P.dirty_slots(s.pstate, ps5, cur_item, nxt_item,
                            cur_w, nxt_w)
    k = cfg.delta_k

    def slab_rows(rel, slab, valid):
        rows = _rowslab_rows(cfg, ps5, rel, nxt_item, nxt_w, slab, valid)
        return P.scatter_relations(rel, *rows, slab, valid)

    if cfg.fleet:
        ids = jnp.nonzero(dirty_m, size=n, fill_value=n)[0] \
            .astype(jnp.int32)
        m = dirty_m.sum(dtype=jnp.int32)

        def body(carry):
            rel, c = carry
            slab = jax.lax.dynamic_slice_in_dim(ids, c * k, k)
            return slab_rows(rel, slab, slab < n), c + 1

        rel, _ = jax.lax.while_loop(
            lambda carry: carry[1] * k < m, body, (s.rel, jnp.int32(0)))
        return rel

    slab, valid, cnt = P.dirty_slab(dirty_m, k)
    return jax.lax.cond(
        cnt > k,
        lambda rel: P.compute_relations(ps5, nxt_item, nxt_w),
        lambda rel: slab_rows(rel, slab, valid),
        s.rel)


def _cohort_body(cfg: EngCfg, s: EngState) -> EngState:
    """One cohort step.  Each phase runs under a ``jax.named_scope``
    (``cohort.classify``, ``.relations``, ``.step``, ``.leave_begin``,
    ``.refill``, ``.reserve``, ``.transitions``); the names reach only
    the compiled program's ``op_name`` metadata, through which a device
    trace is attributed to them (``bench/scopes.py``)."""
    with jax.named_scope("cohort.classify"):
        n = cfg.n
        idx = jnp.arange(n, dtype=jnp.int32)
        t0 = s.next_time.min()
        ready = (s.next_time <= t0 + cfg.cohort_dt) & (s.next_time < 0.5 * INF)
        te = jnp.where(ready, s.next_time, t0)   # per-slot event time
        s = s._replace(now=t0, iters=s.iters + 1)

        # per-iteration randomness (vector draws; streams differ from the
        # one-event engine — parity is statistical, as with the oracle)
        key, kc, kd, kr, kt = jax.random.split(s.key, 5)
        dur_cpu = jax.random.uniform(kc, (n,), minval=cfg.cpu_mean
                                     - cfg.cpu_spread,
                                     maxval=cfg.cpu_mean + cfg.cpu_spread)
        dur_io = jax.random.uniform(kd, (n,), minval=cfg.io_mean
                                    - cfg.io_spread,
                                    maxval=cfg.io_mean + cfg.io_spread)
        delay = jax.random.uniform(kr, (n,), minval=0.5 * cfg.restart_mean,
                                   maxval=1.5 * cfg.restart_mean)
        s = s._replace(key=key)

        # ---------------- classification ----------------
        kind = s.next_kind
        phase = s.phase
        n_ops = (s.kinds >= 0).sum(axis=1)
        done_reading = s.op_idx >= n_ops
        in_wc = (phase == PH_WC_LOCK) | (phase == PH_WC_PREC)
        still_wait = (phase == PH_BLOCKED) | (phase == PH_WC_LOCK)

        is_att = ready & (kind == EV_ATTEMPT)
        is_disk = ready & (kind == EV_DISK_DONE)
        is_fl = ready & (kind == EV_FLUSH_DONE)
        is_to = ready & (kind == EV_TIMEOUT)
        is_rs = ready & (kind == EV_RESTART)

        to_expired = is_to & still_wait & (s.deadline <= te)
        att = is_att | (is_to & ~(still_wait & (s.deadline <= te)))
        wc_m = att & (done_reading | in_wc)
        read_m = att & ~(done_reading | in_wc)

        # ---------------- read-phase + wait-to-commit cohorts --------------
        op_i = jnp.minimum(s.op_idx, cfg.max_ops - 1)
        cur_item = s.items[idx, op_i]
        cur_w = s.kinds[idx, op_i] == jnp.int8(1)
    rel = None
    if cfg.protocol == "ppcc" and cfg.fused:
        with jax.named_scope("cohort.relations"):
            if cfg.delta:
                # the carried tables already equal this iteration's full
                # recompute (the end-of-body delta pass maintains them for
                # the NEXT cursor) — only the cheap O(n·w) reductions run
                rel = P.relations_inputs(s.rel, read_m, s.pstate.haslocks)
            elif cfg.megakernel:
                from ..kernels import ops as kops
                rel = kops.megastep_relations(
                    s.pstate.read_set, s.pstate.write_set, s.dirty, cur_item,
                    cur_w, s.pstate.active, read_m, s.pstate.haslocks)
    with jax.named_scope("cohort.step"):
        if cfg.protocol == "ppcc" and cfg.fused:
            # one fused pass over the packed words: conflict/party matrix →
            # ordered selection → op verdicts + apply → lock winners →
            # commit test.  read_m and wc_m are disjoint (a slot is in one
            # phase), which is what licenses the fused step's pre-state
            # write-write join (see cohort_step_fused).  Bit-identical to
            # the multipass chain below under order="index".
            fs = P.cohort_step_fused(s.pstate, cur_item, cur_w, read_m, wc_m,
                                     order=cfg.order, relations=rel)
            ps1 = ps2 = fs.state
            verdict, sel, reason = fs.verdict, fs.selected, fs.reason
            degree = fs.degree
            flush_m = wc_m & fs.won & fs.can_commit
            wait_prec_m = wc_m & fs.won & ~fs.can_commit
            wait_lock_m = wc_m & ~fs.won
            wc_abort = jnp.zeros(n, bool)
        else:
            ps1, verdict, sel, reason = _try_ops_cohort(cfg, s.pstate,
                                                        cur_item, cur_w,
                                                        read_m)
            degree = jnp.zeros(n, jnp.int32)
            # The lax.cond gates in this body are pure perf guards: each
            # branch is exact under an all-False mask.  Under vmap (fleet
            # lanes) a cond decays into computing BOTH branches plus a
            # full-state select, so fleet bodies run the masked computation
            # directly instead.
            if cfg.fleet:
                ps2, flush_m, wait_lock_m, wait_prec_m, wc_abort = \
                    _wc_cohort(cfg, ps1, s.dirty, wc_m)
            else:
                ps2, flush_m, wait_lock_m, wait_prec_m, wc_abort = \
                    jax.lax.cond(
                        wc_m.any(),
                        lambda ps: _wc_cohort(cfg, ps, s.dirty, wc_m),
                        lambda ps: (ps, jnp.zeros(n, bool),
                                    jnp.zeros(n, bool), jnp.zeros(n, bool),
                                    jnp.zeros(n, bool)),
                        ps1)
        deferred = read_m & ~sel
        proceed = sel & (verdict == P.PROCEED)
        v_block = sel & (verdict == P.BLOCK)
        v_abort = sel & (verdict == P.ABORT)
        op2 = s.op_idx + proceed
        was_last = proceed & (op2 >= n_ops)
        rd_disk = proceed & ~cur_w
        wr_cpu = proceed & cur_w & ~was_last
        wr_wc = proceed & cur_w & was_last
        n_w = B.popcount(ps2.write_set)
        flush_io = flush_m & (n_w > 0)
        flush_zero = flush_m & (n_w == 0)

        # ---------------- flush completions ----------------
        left = s.flush_left - is_fl.astype(jnp.int32)
        flush_more = is_fl & (left > 0)
        flush_done = is_fl & (left <= 0)

        # ---------------- commits / aborts ----------------
        commit_pre = flush_zero | flush_done
        if cfg.protocol == "occ":
            # close the Kung-Robinson overlap window: re-validate at commit.
            # Same-iteration committers must also validate against each
            # other (the event engine broadcasts each commit's writes before
            # the next commit validates) — a slot-ordered pass over the
            # accumulated writes of lower surviving committers, taken only
            # on multi-commit iterations.
            def occ_validate_multi(_):
                def vstep(acc, i):
                    fail_i = commit_pre[i] & \
                        B.overlap_rows(ps2.read_set[i], s.dirty[i] | acc)
                    acc = acc | jnp.where(commit_pre[i] & ~fail_i,
                                          ps2.write_set[i], jnp.uint32(0))
                    return acc, fail_i
                _, fails = jax.lax.scan(
                    vstep, jnp.zeros(ps2.words, jnp.uint32), idx)
                return fails

            if cfg.fleet:
                occ_fail = occ_validate_multi(None)
            else:
                occ_fail = jax.lax.cond(
                    commit_pre.sum() > 1, occ_validate_multi,
                    lambda _: commit_pre & B.overlap_rows(ps2.read_set,
                                                          s.dirty),
                    None)
        else:
            occ_fail = jnp.zeros(n, bool)
        commit_now = commit_pre & ~occ_fail
        abort_now = to_expired | v_abort | wc_abort | occ_fail

    # ---------------- leave + re-begin (skipped on quiet iterations) ---
    with jax.named_scope("cohort.leave_begin"):
        begin_m = commit_now | is_rs

        def leave_and_begin(ps):
            dirty = s.dirty
            if cfg.protocol == "occ":
                union = B.or_reduce(
                    jnp.where(commit_now[:, None], ps.write_set,
                              jnp.uint32(0)), axis=0)
                receivers = ps.active & ~commit_now & ~abort_now
                dirty = jnp.where(receivers[:, None],
                                  dirty | union[None, :], dirty)
                dirty = B.clear_rows(dirty, commit_now | abort_now)
            if cfg.protocol == "ppcc":
                ps = P.commit_many(ps, commit_now)
                ps = P.abort_many(ps, abort_now)
                return P.begin_many(ps, begin_m), dirty
            # 2pl / occ never write prec, class bits or locks — leave/begin
            # reduce to the read/write-set and active-bit updates
            gone = commit_now | abort_now
            return ps._replace(
                read_set=B.clear_rows(ps.read_set, gone | begin_m),
                write_set=B.clear_rows(ps.write_set, gone | begin_m),
                active=(ps.active & ~gone) | begin_m,
            ), dirty

        if cfg.fleet:
            ps5, dirty = leave_and_begin(ps2)
        else:
            ps5, dirty = jax.lax.cond(
                (commit_now | abort_now | begin_m).any(),
                leave_and_begin, lambda ps: (ps, s.dirty), ps2)

    with jax.named_scope("cohort.refill"):
        # fresh workloads are only needed on commit iterations — gate the
        # (vmapped) sampling behind a cond so quiet iterations skip it
        def do_sample(k):
            return sample_txns(k, cfg, s.rt, n)

        def no_sample(k):
            return (jnp.full((n, cfg.max_ops), -1, jnp.int8),
                    jnp.zeros((n, cfg.max_ops), jnp.int32))

        pool_next = s.pool_next
        if cfg.pool:
            # pop pool rows instead of sampling in-loop: the c-th committing
            # slot (slot order) takes pool[(pool_next + c) mod P].  Same
            # workload distribution, drawn once at init; the pool rides the
            # carry untouched, so XLA hoists it as loop-invariant.
            rank = jnp.cumsum(commit_now) - 1
            take = (pool_next + jnp.where(commit_now, rank, 0)) % cfg.pool
            fresh_kinds = s.pool_kinds[take]
            fresh_items = s.pool_items[take]
            pool_next = (pool_next + commit_now.sum()) % cfg.pool
        elif cfg.fleet:
            fresh_kinds, fresh_items = do_sample(kt)
        else:
            fresh_kinds, fresh_items = jax.lax.cond(
                commit_now.any(), do_sample, no_sample, kt)
        new_kinds = jnp.where(commit_now[:, None], fresh_kinds, s.kinds)
        new_items = jnp.where(commit_now[:, None], fresh_items, s.items)

    # ---------------- resource reservations (one fused scan) -----------
    with jax.named_scope("cohort.reserve"):
        cpu_req = wr_cpu | (is_disk & ~done_reading) | begin_m
        disk_req = rd_disk | flush_more | flush_io
        cpu_free, disk_free, cpu_done, disk_done = _reserve_cohort(
            s.cpu_free, s.disk_free, te, dur_cpu, dur_io, cpu_req, disk_req)

    # ---------------- transitions (masks are pairwise disjoint) --------
    with jax.named_scope("cohort.transitions"):
        nt, nk = s.next_time, s.next_kind
        ph, dl, fl = s.phase, s.deadline, left

        def put(m, arr, val):
            return jnp.where(m, val, arr)

        # deferred read ops: retry next iteration at their own event time
        nt = put(deferred, nt, te)
        nk = put(deferred, nk, jnp.int8(EV_ATTEMPT))
        # read proceeded -> disk read
        nt = put(rd_disk, nt, disk_done)
        nk = put(rd_disk, nk, jnp.int8(EV_DISK_DONE))
        ph = put(rd_disk, ph, jnp.int8(PH_READ))
        # write proceeded, not last -> next CPU burst
        nt = put(wr_cpu, nt, cpu_done)
        nk = put(wr_cpu, nk, jnp.int8(EV_ATTEMPT))
        ph = put(wr_cpu, ph, jnp.int8(PH_READ))
        # last write proceeded -> enter wait-to-commit immediately
        nt = put(wr_wc, nt, te)
        nk = put(wr_wc, nk, jnp.int8(EV_ATTEMPT))
        ph = put(wr_wc, ph, jnp.int8(PH_READ))
        # read-phase block
        was_blocked = phase == PH_BLOCKED
        new_dl = jnp.where(was_blocked, s.deadline, te + cfg.block_timeout)
        dl = put(v_block, dl, new_dl)
        ph = put(v_block, ph, jnp.int8(PH_BLOCKED))
        nt = put(v_block, nt, new_dl)
        nk = put(v_block, nk, jnp.int8(EV_TIMEOUT))
        # wait-to-commit routing
        ph = put(flush_m, ph, jnp.int8(PH_FLUSH))
        fl = jnp.where(flush_m, n_w, fl)
        nt = put(flush_io, nt, disk_done)
        nk = put(flush_io, nk, jnp.int8(EV_FLUSH_DONE))
        first_lock = phase != PH_WC_LOCK
        lock_dl = jnp.where(first_lock, te + cfg.block_timeout, s.deadline)
        dl = put(wait_lock_m, dl, lock_dl)
        ph = put(wait_lock_m, ph, jnp.int8(PH_WC_LOCK))
        nt = put(wait_lock_m, nt, lock_dl)
        nk = put(wait_lock_m, nk, jnp.int8(EV_TIMEOUT))
        ph = put(wait_prec_m, ph, jnp.int8(PH_WC_PREC))
        nt = put(wait_prec_m, nt, INF)
        nk = put(wait_prec_m, nk, jnp.int8(EV_ATTEMPT))
        # disk completions
        disk_cpu = is_disk & ~done_reading
        nt = put(disk_cpu, nt, cpu_done)
        nk = put(disk_cpu, nk, jnp.int8(EV_ATTEMPT))
        disk_wc = is_disk & done_reading
        nt = put(disk_wc, nt, te)
        nk = put(disk_wc, nk, jnp.int8(EV_ATTEMPT))
        # flush continues
        nt = put(flush_more, nt, disk_done)
        nk = put(flush_more, nk, jnp.int8(EV_FLUSH_DONE))
        # aborts -> restart later
        ph = put(abort_now, ph, jnp.int8(PH_RESTART))
        nt = put(abort_now, nt, te + delay)
        nk = put(abort_now, nk, jnp.int8(EV_RESTART))
        # begins (fresh after commit / reuse after restart delay)
        ph = put(begin_m, ph, jnp.int8(PH_READ))
        fl = jnp.where(begin_m, 0, fl)
        nt = put(begin_m, nt, cpu_done)
        nk = put(begin_m, nk, jnp.int8(EV_ATTEMPT))
        op_new = jnp.where(begin_m, 0, op2)

        # wake waiters on any commit/abort
        any_leave = (commit_now | abort_now).any()
        waiting = (ph == PH_BLOCKED) | (ph == PH_WC_LOCK) | (ph == PH_WC_PREC)
        nt = jnp.where(any_leave & waiting, jnp.minimum(nt, t0), nt)

    # ---------------- delta relation maintenance ----------------------
    with jax.named_scope("cohort.relations"):
        if cfg.delta and cfg.protocol == "ppcc" and cfg.fused:
            rel_c = _delta_update(cfg, s, ps5, cur_item, cur_w,
                                  new_kinds, new_items, op_new)
        else:
            rel_c = s.rel

    with jax.named_scope("cohort.transitions"):
        new_block = v_block & ~was_blocked

    # ---------------- telemetry (compiled out when cfg.telemetry off) --
    if cfg.telemetry:
        tm = s.tm
        edges = jnp.asarray(M.EDGES, jnp.float32)
        # Wait-episode state machine: open on block / wc-lock-wait /
        # wc-prec-wait entry (wait_from INF = no open episode), close —
        # folding the span into wait_acc — the quantum the slot is
        # processed while its post-phase is no longer a waiting state.
        # PH_WC_LOCK -> PH_WC_PREC keeps the episode open (one wait).
        entering = (v_block | wait_lock_m | wait_prec_m) & \
            (tm.wait_from > 0.5 * INF)
        wfrom = jnp.where(entering, te, tm.wait_from)
        exiting = ready & (wfrom < 0.5 * INF) & ~waiting
        wacc = jnp.where(exiting, tm.wait_acc + (te - wfrom), tm.wait_acc)
        wfrom = jnp.where(exiting, INF, wfrom)

        # commit folds: non-commit lanes scatter to the one-past-the-end
        # bin and are dropped, so the hists only ever count commits
        lat_idx = jnp.where(
            commit_now,
            jnp.searchsorted(edges, te - tm.first_start, side="right"),
            M.NBINS).astype(jnp.int32)
        wait_idx = jnp.where(
            commit_now, jnp.searchsorted(edges, wacc, side="right"),
            M.NBINS).astype(jnp.int32)
        r_idx = jnp.where(commit_now,
                          jnp.minimum(tm.restarts, M.RBINS - 1),
                          M.RBINS).astype(jnp.int32)
        lat_hist = tm.lat_hist.at[lat_idx].add(1, mode="drop")
        wait_hist = tm.wait_hist.at[wait_idx].add(1, mode="drop")
        restart_hist = tm.restart_hist.at[r_idx].add(1, mode="drop")
        first_start = jnp.where(commit_now, te, tm.first_start)
        wacc = jnp.where(commit_now, jnp.float32(0), wacc)
        restarts = jnp.where(commit_now, 0,
                             tm.restarts + abort_now.astype(jnp.int32))

        # abort causes: priority-masked partition — each aborting slot
        # is charged to exactly one cause, so causes sum to aborts even
        # if the underlying masks ever overlapped
        rest = abort_now
        cause_counts = []
        for cm in (to_expired & was_blocked, to_expired & ~was_blocked,
                   v_abort, wc_abort, occ_fail):
            take = rest & cm
            cause_counts.append(take.sum())
            rest = rest & ~cm
        abort_causes = tm.abort_causes + jnp.stack(cause_counts)
        # lock + rule partition the engine's `blocks` counter; wc-lock
        # wait entries are a separate episode class
        block_causes = tm.block_causes + jnp.stack([
            (new_block & (reason == P.R_LOCK)).sum(),
            (new_block & (reason == P.R_RULE)).sum(),
            (wait_lock_m & first_lock).sum()])

        trace = tm.trace
        if cfg.trace_every > 0:
            # ring-buffer sample every trace_every iterations: a
            # read-modify-write dynamic slice (vmap-safe, no cond)
            it1 = s.iters - 1
            do = (it1 % cfg.trace_every) == 0
            pos = (it1 // cfg.trace_every) % cfg.trace_len
            row = jnp.stack([
                t0,
                ready.sum().astype(jnp.float32),
                (ph == PH_BLOCKED).sum().astype(jnp.float32),
                waiting.sum().astype(jnp.float32),
                (s.commits + commit_now.sum()).astype(jnp.float32),
                (s.aborts + abort_now.sum()).astype(jnp.float32),
                sel.sum().astype(jnp.float32),
                jnp.where(read_m, degree, 0).sum().astype(jnp.float32)])
            old = jax.lax.dynamic_slice(trace, (pos, 0),
                                        (1, row.shape[0]))
            new = jnp.where(do, row[None, :], old)
            trace = jax.lax.dynamic_update_slice(trace, new, (pos, 0))
        tm = M.Telemetry(first_start, wfrom, wacc, restarts, lat_hist,
                         wait_hist, restart_hist, abort_causes,
                         block_causes, trace)
    else:
        tm = s.tm

    with jax.named_scope("cohort.transitions"):
        return s._replace(
            pstate=ps5, dirty=dirty, kinds=new_kinds, items=new_items,
            rel=rel_c, op_idx=op_new, phase=ph, next_time=nt, next_kind=nk,
            deadline=dl, flush_left=fl, cpu_free=cpu_free,
            disk_free=disk_free,
            commits=s.commits + commit_now.sum(),
            aborts=s.aborts + abort_now.sum(),
            blocks=s.blocks + new_block.sum(),
            ops_done=s.ops_done + proceed.sum(),
            pool_next=pool_next, tm=tm)


def default_cohort_dt(p: SimParams) -> float:
    """Half a mean read cycle (CPU burst + disk access): wide enough to
    batch many completions per quantum, narrow enough that protocol
    decisions stay fresh — commit counts track the one-event engine
    within a few percent across the paper grid (DESIGN.md §2.3
    discusses the trade-off)."""
    return 0.5 * (p.cpu_burst_mean + p.io_time_mean)


def make_engine(p: SimParams, protocol: str, max_iters: int = 400_000,
                step_mode: str = "cohort", cohort_dt: float = None):
    init, cond, step = engine_parts(p, protocol, max_iters=max_iters,
                                    step_mode=step_mode,
                                    cohort_dt=cohort_dt)

    @jax.jit
    def run(seed: jax.Array) -> EngState:
        return jax.lax.while_loop(cond, step, init(seed))

    return run


def make_padded_engine(p: SimParams, protocol: str, n_slots: int,
                       max_iters: int = 400_000, step_mode: str = "cohort",
                       cohort_dt: float = None, fleet: bool = False,
                       pool: int = 0, fused: bool = True,
                       order: str = "index", delta: bool = False,
                       delta_k: int = 0, telemetry: bool = False,
                       trace_every: int = 0, trace_len: int = 256):
    """An engine whose MPL is a RUNTIME parameter (DESIGN.md §2.4).

    The slot axis is padded to the static bucket ``n_slots``; the
    returned ``run(seed, mpl, rt=None)`` activates only the first
    ``mpl`` lanes (``mpl`` is a traced int32, so one compiled
    executable serves every MPL point up to the bucket).  Padded slots
    start inactive with ``next_time = INF`` and are never begun, so
    every masked primitive leaves them inert.  ``rt`` overrides the
    runtime workload axes (item count, write_prob, txn-length bounds,
    resource-pool sizes) — the remaining static axes of ``p`` are then
    just buckets those values must fit inside (``check_rt``).
    """
    init, cond, step = engine_parts(p, protocol, max_iters=max_iters,
                                    step_mode=step_mode,
                                    cohort_dt=cohort_dt, n_slots=n_slots,
                                    fleet=fleet, pool=pool, fused=fused,
                                    order=order, delta=delta,
                                    delta_k=delta_k, telemetry=telemetry,
                                    trace_every=trace_every,
                                    trace_len=trace_len)

    @jax.jit
    def _run(seed: jax.Array, mpl: jax.Array, rt: RtParams) -> EngState:
        return jax.lax.while_loop(cond, step, init(seed, mpl, rt))

    def run(seed, mpl, rt: RtParams = None) -> EngState:
        # only the first n_slots lanes exist — a larger mpl would be
        # silently clamped by init's fori_loop, mislabeling the result
        if not isinstance(mpl, jax.core.Tracer) and int(mpl) > n_slots:
            raise ValueError(f"mpl={int(mpl)} > n_slots={n_slots}")
        if rt is None:
            rt = rt_of(p)
        else:
            check_rt(p, rt)
        return _run(seed, mpl, rt)

    run._cache_size = _run._cache_size
    return run


def check_rt(p: SimParams, rt: RtParams) -> None:
    """Reject runtime values that overflow their static buckets.

    Only applied to concrete (non-traced) values — inside a trace the
    caller owns the invariant.  Overflow would be *silent* otherwise:
    items >= d would scatter into pad bits (breaking the zero-pad-bit
    invariant), ops past ``max_ops`` would be dropped by the sampler
    slice, and resource entries past the bucket do not exist.
    """
    bounds = (("d", rt.d, p.db_size),
              ("len_hi", rt.len_hi,
               p.txn_size_mean + p.txn_size_spread),
              ("cpus", rt.cpus, p.num_cpus),
              ("disks", rt.disks, p.num_disks))
    for name, val, cap in bounds:
        if isinstance(val, jax.core.Tracer):
            continue
        hi = int(jnp.max(jnp.asarray(val)))
        if hi > cap:
            raise ValueError(
                f"rt.{name}={hi} exceeds its static bucket {cap}")


def engine_parts(p: SimParams, protocol: str, max_iters: int = 400_000,
                 step_mode: str = "cohort", cohort_dt: float = None,
                 n_slots: int = None, fleet: bool = False, pool: int = 0,
                 fused: bool = True, order: str = "index",
                 megakernel: bool = None, delta: bool = False,
                 delta_k: int = 0, telemetry: bool = False,
                 trace_every: int = 0, trace_len: int = 256):
    """(init, cond, step) for single-stepping an engine from tests —
    e.g. checking protocol invariants after every cohort step.

    ``n_slots`` pads the slot axis beyond ``p.mpl`` (the padded-lane
    engine); ``init(seed, mpl=None)`` then takes the number of active
    slots as a runtime value (default ``p.mpl``).  ``megakernel=None``
    auto-gates the Pallas cohort-step megakernel to the TPU, the one
    backend it is compiled for (elsewhere the kernel would run in
    interpret mode inside the loop, so the jnp twin inside
    ``ppcc.cohort_step_fused`` is both the fast and the correct path)."""
    if step_mode not in ("cohort", "event"):
        raise ValueError(f"unknown step_mode: {step_mode!r}")
    if telemetry and step_mode != "cohort":
        raise ValueError("telemetry requires step_mode='cohort'")
    if megakernel is None:
        megakernel = jax.default_backend() == "tpu"
    if cohort_dt is None:
        cohort_dt = default_cohort_dt(p)
    if n_slots is None:
        n_slots = p.mpl
    if n_slots < p.mpl:
        raise ValueError(f"n_slots={n_slots} < mpl={p.mpl}")
    if delta and delta_k <= 0:
        # measured dirty-row occupancy sits well under n/4 per quantum
        # (BENCH_sweep.json["delta_vs_full"]["occupancy"]); bucket to a
        # lane multiple so the slab tiles cleanly
        delta_k = B.bucket(max(1, n_slots // 4), 8)
    carry_rel = delta and protocol == "ppcc" and fused and \
        step_mode == "cohort"
    cfg = dataclasses.replace(_cfg(p, max_iters), protocol=protocol,
                              cohort_dt=float(cohort_dt), n=n_slots,
                              fleet=fleet, pool=pool, fused=fused,
                              order=order, megakernel=megakernel,
                              delta=carry_rel, delta_k=delta_k,
                              telemetry=telemetry,
                              trace_every=trace_every,
                              trace_len=trace_len)

    def init(seed, mpl=None, rt: RtParams = None) -> EngState:
        if mpl is None:
            mpl = p.mpl
        if rt is None:
            rt = rt_of(p)
        mpl = jnp.asarray(mpl, jnp.int32)
        key = jax.random.PRNGKey(seed)
        if cfg.pool:
            key, kp = jax.random.split(key)
            pool_kinds, pool_items = sample_txns(kp, cfg, rt, cfg.pool)
        else:
            pool_kinds = jnp.zeros((0, cfg.max_ops), jnp.int8)
            pool_items = jnp.zeros((0, cfg.max_ops), jnp.int32)
        # resource-pool entries past the live size hold free_at = INF:
        # FCFS argmin never picks them while a live server exists, so a
        # bucketed pool is bit-identical to its exact-size twin
        live = jnp.where(jnp.arange(cfg.cpus) < rt.cpus, 0.0, INF)
        live_d = jnp.where(jnp.arange(cfg.disks) < rt.disks, 0.0, INF)
        s = EngState(
            now=jnp.float32(0.0), key=key,
            pstate=P.init_state(cfg.n, cfg.d),
            dirty=B.zeros(cfg.n, cfg.d),
            kinds=jnp.full((cfg.n, cfg.max_ops), -1, jnp.int8),
            items=jnp.zeros((cfg.n, cfg.max_ops), jnp.int32),
            op_idx=jnp.zeros(cfg.n, jnp.int32),
            phase=jnp.full(cfg.n, PH_OFF, jnp.int8),
            next_time=jnp.full(cfg.n, INF),
            next_kind=jnp.zeros(cfg.n, jnp.int8),
            deadline=jnp.zeros(cfg.n, jnp.float32),
            flush_left=jnp.zeros(cfg.n, jnp.int32),
            cpu_free=live.astype(jnp.float32),
            disk_free=live_d.astype(jnp.float32),
            commits=jnp.int32(0), aborts=jnp.int32(0),
            blocks=jnp.int32(0), ops_done=jnp.int32(0),
            iters=jnp.int32(0),
            pool_kinds=pool_kinds, pool_items=pool_items,
            pool_next=jnp.int32(0), rt=rt,
            rel=P.empty_relations(cfg.n if cfg.delta else 0),
            tm=M.init_telemetry(
                cfg.n if cfg.telemetry else 0,
                cfg.trace_len if (cfg.telemetry and cfg.trace_every > 0)
                else 0))
        # begin only the first `mpl` slots; the rest stay PH_OFF/INF so
        # every cohort mask derived from `ready` leaves them inert
        s = jax.lax.fori_loop(
            0, cfg.n,
            lambda i, s_: jax.lax.cond(
                i < mpl,
                lambda s2: _begin_txn(cfg, s2, i, jnp.bool_(True)),
                lambda s2: s2, s_), s)
        if cfg.delta:
            # seed the carried-tables invariant: rel equals the full
            # recompute at the first body's op cursor
            idx0 = jnp.arange(cfg.n, dtype=jnp.int32)
            op_i = jnp.minimum(s.op_idx, cfg.max_ops - 1)
            s = s._replace(rel=P.compute_relations(
                s.pstate, s.items[idx0, op_i],
                s.kinds[idx0, op_i] == jnp.int8(1)))
        return s

    def cond(s: EngState):
        return (s.now <= cfg.horizon) & (s.iters < cfg.max_iters) & \
            (s.next_time.min() < 0.5 * INF)

    if step_mode == "cohort":
        step = functools.partial(_cohort_body, cfg)
    else:
        def step(s: EngState) -> EngState:
            i = jnp.argmin(s.next_time)
            s = s._replace(now=s.next_time[i], iters=s.iters + 1,
                           next_time=s.next_time.at[i].set(INF))
            return jax.lax.switch(
                s.next_kind[i].astype(jnp.int32),
                [functools.partial(_ev_attempt, cfg),
                 functools.partial(_ev_disk_done, cfg),
                 functools.partial(_ev_flush_done, cfg),
                 functools.partial(_ev_timeout, cfg),
                 functools.partial(_ev_restart, cfg)],
                s, i)

    return init, jax.jit(cond), jax.jit(step)


def simulate(p: SimParams, protocol: str,
             step_mode: str = "cohort") -> SimResult:
    run = make_engine(p, protocol, step_mode=step_mode)
    s = run(jnp.int32(p.seed))
    res = SimResult(protocol=protocol, params=p)
    res.commits = int(s.commits)
    res.aborts = int(s.aborts)
    res.blocks = int(s.blocks)
    res.ops_executed = int(s.ops_done)
    res.sim_time = float(min(float(s.now), p.horizon))
    return res


def simulate_sweep(p: SimParams, protocol: str, seeds,
                   step_mode: str = "cohort") -> Any:
    """vmap over seeds — one SPMD computation, shardable over `data`."""
    run = make_engine(p, protocol, step_mode=step_mode)
    final = jax.vmap(run)(jnp.asarray(seeds, jnp.int32))
    return {"commits": final.commits, "aborts": final.aborts,
            "blocks": final.blocks}
