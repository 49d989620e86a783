"""Driver of the execution surface: ``scheduler.tick`` on key lists,
then the keyed table store executing what it admitted, a closed loop
over a backlog of pending transactions.

Set-up loads the database from the seed (``repro.sched.tpcc.populate``,
span ``bench.load``): the tables, put on the device whole, and the
store's append-only logs, sized for the stream's expected inserts
(``tpcc.log_capacity``).  It draws the cell's stream
(``bench/traffic/<kind>``), resolves its by-name Payments with the
loaded customers (``tpcc.name_directory``), puts it on the device,
fills the backlog with its first rows, warms the tick, the apply (with
nothing admitted, which leaves the store as it is), the refill and the
copies that keep compared ticks, and runs the loop for the traffic's
``burn_in_ticks`` (span ``bench.burn_in``).  A step of the loop: the
tick, timed from the call to ``block_until_ready`` of ``admitted``
(span ``bench.tick_call``); the apply, which executes the admitted
transactions in commit order and returns the step's counts (admitted,
NewOrders, Payments, order lines, whether a log ran out), timed to
their read-back (span ``bench.apply_call``); then the refill, in which
the admitted rows leave and stream rows fill the tail (span
``bench.refill``).  ``sim_commits_per_s`` is the window's committed
transactions over the loop's whole wall time.  A traced run profiles
a part that starts after ``TRACE_AFTER_TICKS`` untraced steps.
``correct`` compares the loop's first tick and three ticks of the
window, drawn from the seed before it, all kept on the device, with
the plain reference: ``admitted`` and ``commit_rank`` exactly, no arc
against the commit order, and the store after the tick — every row of
every table, the appended rows and the transactions' outputs.
"""
from __future__ import annotations

import sys
import time
from typing import NamedTuple

import numpy as np

from reference import tpcc_tick, ycsb_tick

KERNELS = {"conflict_keys": "conflict_keys"}

# the traced part: after a few untraced steps, at least three ticks
TRACE_AFTER_TICKS = 10
TRACE_SECONDS = 1.0
TRACE_MIN_TICKS = 3

ADMITTED, NEW_ORDERS, PAYMENTS, LINES, OVERFLOW = range(5)


class Step(NamedTuple):
    tick_s: float           # call of the tick to its ``admitted``
    store_s: float          # call of the apply to its counts
    step_s: float           # the whole step, refill included
    commits: int
    new_orders: int
    payments: int
    traced: bool


class Ctx:
    pass


def setup(cell, gen, seed: int, span) -> Ctx:
    # a program without the keyed store's TPC-C stops here, before any work
    from repro.sched import tpcc
    import functools

    import jax
    import jax.numpy as jnp
    from repro.sched import scheduler, txstore
    cfg, tr = cell.config, cell.traffic
    ctx = Ctx()
    ctx.jax, ctx.gen, ctx.span, ctx.limits = jax, gen, span, cell.limits
    ctx.config, ctx.seed = cfg, seed
    ctx.n = n = int(tr["backlog"])
    ctx.check_ticks = int(tr["check_ticks"])
    burn_in = int(tr["burn_in_ticks"])
    scale = tpcc.Scale.of(cfg)
    with span("bench.load"):
        tables, c_load = tpcc.populate(scale, seed)
        directory = tpcc.name_directory(tables["customer"], scale)
        stream = gen.stream(cfg, tr, seed, c_load)
        name = stream.pop("c_last")
        by = name >= 0
        stream["c"][by] = directory[stream["c_w"][by] - 1,
                                    stream["c_d"][by] - 1, name[by]]
        ctx.capacity = tpcc.log_capacity(len(name),
                                         float(cfg["new_order_share"]))
        ctx.store = txstore.new_store(tables, tpcc.LOG_COLUMNS,
                                      ctx.capacity)
        ctx.stream = jax.device_put(stream)
        del tables, stream
    ctx.keys = jax.jit(functools.partial(tpcc.key_lists, scale=scale))
    ctx.backlog = jax.tree.map(lambda a: a[:n], ctx.stream)
    ctx.read, ctx.write = ctx.keys(ctx.backlog)
    ctx.pos, ctx.now = n, 1
    ctx.fill = {t: 0 for t in tpcc.LOG_COLUMNS}
    ctx.valid = jnp.ones(n, bool)
    ctx.tick = functools.partial(scheduler.tick, policy=cfg["policy"],
                                 order=cfg["order"], keys=True)
    ctx.apply = jax.jit(functools.partial(tpcc.apply_tick, scale=scale),
                        donate_argnums=0)
    ctx.copy = jax.jit(lambda tree: jax.tree.map(jnp.copy, tree))
    most = {t: min(n * (tpcc.MAX_LINES if t == "order_line" else 1),
                   ctx.capacity[t]) for t in tpcc.LOG_COLUMNS}
    ctx.tail = jax.jit(lambda logs, start: {
        t: {c: jax.lax.dynamic_slice_in_dim(col, start[t], most[t])
            for c, col in cols.items()} for t, cols in logs.items()})
    ctx.shapes = {"conflict_keys": {"n": n, "kr": int(ctx.read.shape[1]),
                                    "kw": int(ctx.write.shape[1])}}
    with span("bench.warm_up"):
        none = jnp.zeros(n, bool)
        for _ in range(2):          # compile, then one warm step
            t0 = time.perf_counter()
            res = ctx.tick(ctx.read, ctx.write, ctx.valid)
            jax.block_until_ready(res.admitted)
            ctx.warm_tick_s = time.perf_counter() - t0
            jax.block_until_ready(ctx.copy(ctx.store.tables))
            jax.block_until_ready(ctx.tail(ctx.store.logs, ctx.fill))
            ctx.store, _, counts = ctx.apply(ctx.store, ctx.backlog, none,
                                             res.commit_rank, ctx.now)
            np.asarray(counts)
            jax.block_until_ready(ctx.keys(gen.refill(
                ctx.backlog, res.admitted, ctx.stream, ctx.pos)))
    with span("bench.burn_in"):
        st = {"ticks": [], "picks": {0}, "kept": {}}
        _steps(ctx, st, lambda: len(st["ticks"]) < burn_in, False)
    ctx.burn_in = st["ticks"]
    ctx.kept_burn_in = st["kept"]
    return ctx


def _steps(ctx: Ctx, st: dict, more, traced: bool) -> None:
    """Run steps of the loop (a tick, the apply, the refill) while
    ``more()``; the picked ticks keep their inputs and the store before
    and after on the device."""
    jax, span = ctx.jax, ctx.span
    while more():
        k = len(st["ticks"])
        keep = k in st["picks"]
        t0 = time.perf_counter()
        with span("bench.tick_call"):
            res = ctx.tick(ctx.read, ctx.write, ctx.valid)
            jax.block_until_ready(res.admitted)
        t1 = time.perf_counter()
        with span("bench.apply_call"):
            if keep:
                before = ctx.copy(ctx.store.tables)
            ctx.store, out, counts = ctx.apply(
                ctx.store, ctx.backlog, res.admitted, res.commit_rank,
                ctx.now)
            counts = np.asarray(counts)
        t2 = time.perf_counter()
        if counts[OVERFLOW]:
            raise RuntimeError(f"an append table ran out at tick "
                               f"{ctx.now}: {ctx.fill} of {ctx.capacity}")
        added = {"order": counts[NEW_ORDERS],
                 "new_order": counts[NEW_ORDERS],
                 "order_line": counts[LINES], "history": counts[PAYMENTS]}
        with span("bench.refill"):
            if keep:
                st["kept"][k] = {
                    "backlog": ctx.backlog, "read": ctx.read,
                    "write": ctx.write, "admitted": res.admitted,
                    "commit_rank": res.commit_rank, "before": before,
                    "after": ctx.copy(ctx.store.tables),
                    "appended": ctx.tail(ctx.store.logs, ctx.fill),
                    "added": {t: int(v) for t, v in added.items()},
                    "outputs": out, "now": ctx.now}
            ctx.backlog = ctx.gen.refill(ctx.backlog, res.admitted,
                                         ctx.stream, ctx.pos)
            ctx.read, ctx.write = ctx.keys(ctx.backlog)
            jax.block_until_ready(ctx.read)
            ctx.pos += int(counts[ADMITTED])
            ctx.now += 1
            for t, v in added.items():
                ctx.fill[t] += int(v)
        st["ticks"].append(Step(t1 - t0, t2 - t1, time.perf_counter() - t0,
                                int(counts[ADMITTED]),
                                int(counts[NEW_ORDERS]),
                                int(counts[PAYMENTS]), traced))


def window(ctx: Ctx, seconds: float, tracer) -> dict:
    # the compared ticks, drawn from the seed among the first half of
    # the steps the burn-in's pace says the window holds
    recent = ctx.burn_in[-100:]
    step_s = (sum(s.step_s for s in recent) / len(recent) if recent
              else ctx.warm_tick_s)
    est = max(ctx.check_ticks, int(seconds / step_s / 2))
    rng = np.random.default_rng([ctx.seed, 11])
    picks = {int(x) for x in rng.choice(est, ctx.check_ticks,
                                        replace=False)}
    st = {"ticks": [], "picks": picks, "kept": {}}
    t_begin = time.perf_counter()

    def more():
        if len(st["ticks"]) <= max(picks):
            return True
        return (time.perf_counter() - t_begin + st["ticks"][-1].step_s
                <= seconds)

    if tracer.on:
        _steps(ctx, st, lambda: len(st["ticks"]) < TRACE_AFTER_TICKS,
               False)
        n0, t0 = len(st["ticks"]), time.perf_counter()
        with tracer.part():
            _steps(ctx, st, lambda: (
                len(st["ticks"]) < n0 + TRACE_MIN_TICKS
                or time.perf_counter() - t0 < TRACE_SECONDS), True)
    _steps(ctx, st, more, False)
    loop_s = time.perf_counter() - t_begin
    ctx.kept = st["kept"]
    ticks = st["ticks"]
    commits = sum(s.commits for s in ticks)
    burn = [s.commits for s in ctx.burn_in]
    untraced = [s for s in ticks if not s.traced]
    return {
        "attempted": len(ticks) * ctx.n,
        "failed": 0,
        "e2e": {"sim_commits_per_s": commits / loop_s},
        "counters": {"ticks": len(ticks), "commits": commits,
                     "new_orders": sum(s.new_orders for s in ticks),
                     "payments": sum(s.payments for s in ticks),
                     "loop_seconds": loop_s,
                     "tick_seconds": sum(s.tick_s for s in ticks),
                     "store_seconds": sum(s.store_s for s in ticks),
                     "burn_in_ticks": len(burn),
                     "burn_in_commits": sum(burn),
                     "stream_rows_used": ctx.pos,
                     "log_rows": dict(ctx.fill),
                     "log_capacity": dict(ctx.capacity),
                     "tick_ms": [s.tick_s * 1e3 for s in untraced],
                     "store_ms": [s.store_s * 1e3 for s in untraced]},
        "shapes": ctx.shapes,
    }


def check(ctx: Ctx, rec: dict, seed: int) -> list:
    """The picked ticks against the reference: rows whose admission or
    commit rank differs, arcs the program's commit order runs
    backwards, and rows of the store that differ after the tick."""
    jax = ctx.jax
    t0 = time.perf_counter()
    adm_bad = rank_bad = violations = store_bad = 0
    kept = ([(f"burn-in tick {k}", v)
             for k, v in sorted(ctx.kept_burn_in.items())]
            + [(f"tick {k}", v) for k, v in sorted(ctx.kept.items())])
    for name, kt in kept:
        txn = jax.device_get(kt["backlog"])
        admitted = np.asarray(kt["admitted"])
        rank = np.asarray(kt["commit_rank"])
        ref = tpcc_tick.admit(txn)
        adm_bad += int((admitted != ref["admitted"]).sum())
        rank_bad += int((rank != ref["commit_rank"]).sum())
        violations += ycsb_tick.order_violations(ref["raw"], admitted,
                                                 rank)
        ref_tables, ref_logs, ref_out = tpcc_tick.execute(
            jax.device_get(kt["before"]), ctx.config, txn,
            ref["admitted"], ref["commit_rank"], kt["now"])
        logs = {t: {c: np.asarray(v)[:kt["added"][t]]
                    for c, v in cols.items()}
                for t, cols in jax.device_get(kt["appended"]).items()}
        bad = tpcc_tick.mismatches(
            jax.device_get(kt["after"]), logs,
            jax.device_get(kt["outputs"]), ref_tables, ref_logs, ref_out)
        store_bad += bad
        print(f"{name}: admitted {int(admitted.sum())} program, "
              f"{int(ref['admitted'].sum())} reference; store rows "
              f"differing {bad}", file=sys.stderr)
    print(f"reference {time.perf_counter() - t0:.1f} s", file=sys.stderr)
    rec["failed"] = adm_bad + rank_bad + violations + store_bad
    lim = ctx.limits
    return [("admitted_mismatches", adm_bad, lim["admitted_mismatches"]),
            ("commit_rank_mismatches", rank_bad,
             lim["commit_rank_mismatches"]),
            ("order_violations", violations, lim["order_violations"]),
            ("store_mismatches", store_bad, lim["store_mismatches"])]
