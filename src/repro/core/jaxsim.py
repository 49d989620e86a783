"""Tensorised, event-synchronous discrete-event simulator in JAX.

The event-heap oracle (``pysim``) is a pointer-chasing CPU artifact; this
module is the TPU-native reformulation (DESIGN.md §2): the entire
simulator state is a fixed-shape pytree and a ``lax.while_loop``
advances it via masked tensor updates (DESIGN.md §2.3).  Each iteration
processes the full *cohort* of ready slots: every slot whose
``next_time`` falls inside the current time quantum
``[t_min, t_min + cohort_dt]``.  The cohort is split by event kind and
resolved with the batched protocol primitives in ``repro.core.ppcc``
(PPCC: one ``cohort_step_fused`` over the relations of
``_relations``; then ``commit_many`` / ``abort_many`` /
``begin_many``); non-independent ops are deferred one iteration, so
progress is guaranteed.

FCFS multi-server resource pools become ``free_at`` vectors: a request
reserves ``argmin(free_at)`` at request time, which reproduces FCFS
because events are processed in (quantised) time order; the cohort
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
The body has no ``lax.cond`` gates (under the fleet's vmap a cond
decays to computing both branches plus a select), and fleet engines
draw fresh transactions from a pre-sampled pool (``pool > 0``) instead
of calling ``sample_txns`` in-loop.

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
    pool: int = 0                # >0: pre-sample this many transactions at
                                 # init and pop on commit instead of calling
                                 # sample_txns per iteration (fleet hot-path:
                                 # in-loop sampling was ~2/3 of body cost)
    megakernel: bool = False     # ppcc relations from the Pallas
                                 # cohort-step megakernel (one launch per
                                 # quantum); compiled path — TPU only,
                                 # elsewhere the bit-identical jnp twin
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


def _begin_txn(cfg: EngCfg, s: EngState, i) -> EngState:
    """Start slot i on a freshly sampled transaction (``init`` only; the
    cohort body begins slots in bulk)."""
    key, k1, k2 = jax.random.split(s.key, 3)
    kinds_i, items_i = sample_txn(k1, cfg, s.rt)
    s = s._replace(
        key=key,
        kinds=s.kinds.at[i].set(kinds_i),
        items=s.items.at[i].set(items_i),
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
    (cpu_free', disk_free', cpu_done[n], disk_done[n]).

    A step never indexes a pool with a traced index: ``min`` reads the
    first free server's time (``pool[argmin(pool)]``, bit for bit) and
    a one-hot select writes it back.  Under the fleet's ``vmap`` an
    indexed read or write becomes a per-lane gather or scatter, which
    the TPU applies one row at a time (DESIGN.md §2.2)."""
    def reserve(pool, t, dur, m):
        done = jnp.maximum(t, jnp.min(pool)) + dur
        hit = m & (jnp.arange(pool.shape[0]) == jnp.argmin(pool))
        return jnp.where(hit, done, pool), jnp.where(m, done, INF)

    def step(carry, inp):
        cpu, disk = carry
        t, cd, dd, cm, dm = inp
        cpu2, cdone = reserve(cpu, t, cd, cm)
        disk2, ddone = reserve(disk, t, dd, dm)
        return (cpu2, disk2), (cdone, ddone)

    (cpu_free, disk_free), (cpu_done, disk_done) = jax.lax.scan(
        step, (cpu_free, disk_free), (t_req, cpu_dur, io_dur, cpu_m,
                                      disk_m))
    return cpu_free, disk_free, cpu_done, disk_done


def _try_ops_cohort(cfg: EngCfg, ps: P.PPCCState, item: jax.Array,
                    is_write: jax.Array, ready: jax.Array
                    ) -> Tuple[P.PPCCState, jax.Array, jax.Array,
                               jax.Array]:
    """Batched read-phase step of 2PL or OCC over a cohort of pending ops
    (PPCC's is ``ppcc.cohort_step_fused``).

    Selects a pairwise-independent subset of ``ready``, resolves it in
    one vectorized step, and returns (state, verdict[n], selected[n],
    block-reason[n]).  Deferred (ready & ~selected) slots are retried
    next iteration.  Every 2PL block is a lock wait (``ppcc.R_LOCK``);
    OCC never blocks.
    """
    n = ps.n
    idx = jnp.arange(n, dtype=jnp.int32)
    eye = jnp.eye(n, dtype=bool)
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
    """Batched wait-to-commit step of 2PL or OCC.  Returns
    (state, flush_m, wait_lock_m, wait_prec_m, abort_m)."""
    n = ps.n
    zeros = jnp.zeros(n, bool)
    if cfg.protocol == "2pl":
        return ps, wc_m, zeros, zeros, zeros
    fail = B.overlap_rows(ps.read_set, dirty)
    return ps, wc_m & ~fail, zeros, zeros, wc_m & fail


def _relations(cfg: EngCfg, ps: P.PPCCState, dirty: jax.Array,
               item: jax.Array, is_write: jax.Array, ready: jax.Array):
    """The pairwise relations ``ppcc.cohort_step_fused`` consumes: one
    launch of the Pallas megakernel on the TPU, the bit-identical jnp
    twin elsewhere (``tests/test_megastep.py``)."""
    if cfg.megakernel:
        from ..kernels import ops as kops
        return kops.megastep_relations(
            ps.read_set, ps.write_set, dirty, item, is_write, ps.active,
            ready, ps.haslocks)
    return P.relations_inputs(P.compute_relations(ps, item, is_write),
                              ready, ps.haslocks)


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
        # oracle's — parity is statistical)
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
    if cfg.protocol == "ppcc":
        with jax.named_scope("cohort.relations"):
            rel = _relations(cfg, s.pstate, s.dirty, cur_item, cur_w, read_m)
    with jax.named_scope("cohort.step"):
        if cfg.protocol == "ppcc":
            # one fused pass: ordered selection → op verdicts + apply →
            # lock winners → commit test.  read_m and wc_m are disjoint
            # (a slot is in one phase), which is what licenses the fused
            # step's pre-state write-write join (see cohort_step_fused).
            fs = P.cohort_step_fused(s.pstate, cur_item, cur_w, read_m, wc_m,
                                     relations=rel)
            ps2 = fs.state
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
            ps2, flush_m, wait_lock_m, wait_prec_m, wc_abort = \
                _wc_cohort(cfg, ps1, s.dirty, wc_m)
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
            # other (the oracle broadcasts each commit's writes before the
            # next commit validates) — a slot-ordered pass over the
            # accumulated writes of lower surviving committers.
            def vstep(acc, i):
                fail_i = commit_pre[i] & \
                    B.overlap_rows(ps2.read_set[i], s.dirty[i] | acc)
                acc = acc | jnp.where(commit_pre[i] & ~fail_i,
                                      ps2.write_set[i], jnp.uint32(0))
                return acc, fail_i
            _, occ_fail = jax.lax.scan(
                vstep, jnp.zeros(ps2.words, jnp.uint32), idx)
        else:
            occ_fail = jnp.zeros(n, bool)
        commit_now = commit_pre & ~occ_fail
        abort_now = to_expired | v_abort | wc_abort | occ_fail

    # ---------------- leave + re-begin ----------------
    with jax.named_scope("cohort.leave_begin"):
        begin_m = commit_now | is_rs
        dirty = s.dirty
        if cfg.protocol == "occ":
            union = B.or_reduce(
                jnp.where(commit_now[:, None], ps2.write_set,
                          jnp.uint32(0)), axis=0)
            receivers = ps2.active & ~commit_now & ~abort_now
            dirty = jnp.where(receivers[:, None],
                              dirty | union[None, :], dirty)
            dirty = B.clear_rows(dirty, commit_now | abort_now)
        if cfg.protocol == "ppcc":
            ps5 = P.commit_many(ps2, commit_now)
            ps5 = P.abort_many(ps5, abort_now)
            ps5 = P.begin_many(ps5, begin_m)
        else:
            # 2pl / occ never write prec, class bits or locks — leave/begin
            # reduce to the read/write-set and active-bit updates
            gone = commit_now | abort_now
            ps5 = ps2._replace(
                read_set=B.clear_rows(ps2.read_set, gone | begin_m),
                write_set=B.clear_rows(ps2.write_set, gone | begin_m),
                active=(ps2.active & ~gone) | begin_m)

    with jax.named_scope("cohort.refill"):
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
        else:
            fresh_kinds, fresh_items = sample_txns(kt, cfg, s.rt, n)
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
            op_idx=op_new, phase=ph, next_time=nt, next_kind=nk,
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
    decisions stay fresh — commit counts track the ``pysim`` oracle
    within a few percent across the paper grid (DESIGN.md §2.3
    discusses the trade-off)."""
    return 0.5 * (p.cpu_burst_mean + p.io_time_mean)


def make_engine(p: SimParams, protocol: str, max_iters: int = 400_000,
                cohort_dt: float = None):
    init, cond, step = engine_parts(p, protocol, max_iters=max_iters,
                                    cohort_dt=cohort_dt)

    @jax.jit
    def run(seed: jax.Array) -> EngState:
        return jax.lax.while_loop(cond, step, init(seed))

    return run


def make_padded_engine(p: SimParams, protocol: str, n_slots: int,
                       max_iters: int = 400_000, cohort_dt: float = None,
                       pool: int = 0, telemetry: bool = False,
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
                                    cohort_dt=cohort_dt, n_slots=n_slots,
                                    pool=pool, telemetry=telemetry,
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
                 cohort_dt: float = None, n_slots: int = None, pool: int = 0,
                 megakernel: bool = None, telemetry: bool = False,
                 trace_every: int = 0, trace_len: int = 256):
    """(init, cond, step) of the cohort engine: ``make_engine``,
    ``make_padded_engine`` and ``sweep.Fleet`` loop them, and tests
    single-step them (e.g. to check the protocol invariants after every
    cohort step).

    ``n_slots`` pads the slot axis beyond ``p.mpl`` (the padded-lane
    engine); ``init(seed, mpl=None)`` then takes the number of active
    slots as a runtime value (default ``p.mpl``).  ``megakernel=None``
    selects the Pallas cohort-step megakernel on the TPU, the one
    backend it is compiled for, and its jnp twin elsewhere (where the
    kernel would run in interpret mode inside the loop)."""
    if megakernel is None:
        megakernel = jax.default_backend() == "tpu"
    if cohort_dt is None:
        cohort_dt = default_cohort_dt(p)
    if n_slots is None:
        n_slots = p.mpl
    if n_slots < p.mpl:
        raise ValueError(f"n_slots={n_slots} < mpl={p.mpl}")
    cfg = dataclasses.replace(_cfg(p, max_iters), protocol=protocol,
                              cohort_dt=float(cohort_dt), n=n_slots,
                              pool=pool, megakernel=megakernel,
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
            tm=M.init_telemetry(
                cfg.n if cfg.telemetry else 0,
                cfg.trace_len if (cfg.telemetry and cfg.trace_every > 0)
                else 0))
        # begin only the first `mpl` slots; the rest stay PH_OFF/INF so
        # every cohort mask derived from `ready` leaves them inert
        return jax.lax.fori_loop(
            0, cfg.n,
            lambda i, s_: jax.lax.cond(
                i < mpl, lambda s2: _begin_txn(cfg, s2, i),
                lambda s2: s2, s_), s)

    def cond(s: EngState):
        return (s.now <= cfg.horizon) & (s.iters < cfg.max_iters) & \
            (s.next_time.min() < 0.5 * INF)

    return init, jax.jit(cond), jax.jit(functools.partial(_cohort_body, cfg))


def simulate(p: SimParams, protocol: str) -> SimResult:
    run = make_engine(p, protocol)
    s = run(jnp.int32(p.seed))
    res = SimResult(protocol=protocol, params=p)
    res.commits = int(s.commits)
    res.aborts = int(s.aborts)
    res.blocks = int(s.blocks)
    res.ops_executed = int(s.ops_done)
    res.sim_time = float(min(float(s.now), p.horizon))
    return res


def simulate_sweep(p: SimParams, protocol: str, seeds) -> Any:
    """vmap over seeds — one SPMD computation, shardable over `data`."""
    run = make_engine(p, protocol)
    final = jax.vmap(run)(jnp.asarray(seeds, jnp.int32))
    return {"commits": final.commits, "aborts": final.aborts,
            "blocks": final.blocks}
