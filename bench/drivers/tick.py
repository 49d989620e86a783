"""Driver of the admission surface: ``scheduler.tick`` on key lists, a
closed loop over a backlog of pending transactions.

Set-up draws the cell's stream from the seed (``bench/traffic/<kind>``)
and puts it on the device, fills the backlog with its first rows, warms
the tick and the refill at the cell's shapes, and then runs the loop
for the traffic's ``burn_in_ticks`` (span ``bench.burn_in``): the
backlog starts as a fresh draw and fills with transactions on the hot
keys, so the commits a tick fall for the first thousand or so ticks;
after the burn-in the window reads the loop's steady mix.  The window
runs the loop's steps back to back: a tick, timed from the call to
``block_until_ready`` of ``admitted`` (span ``bench.tick_call``), then
the read-back of the admitted count and the refill, in which the
admitted rows leave and stream rows fill the tail (span
``bench.refill``).  ``sim_commits_per_s`` is the window's committed
transactions over the loop's whole wall time, ticks and refills: under
PPCC every admitted transaction commits.  A traced run profiles a part
that starts after ``TRACE_AFTER_TICKS`` untraced steps.  ``correct``
compares the loop's first tick (a fresh backlog, where the rule's class
tests decide many admissions; in the steady backlog they rarely decide
one) and three ticks of the window, drawn from the seed before it, all
kept on the device, with the plain reference: ``admitted`` and
``commit_rank`` exactly, and no arc between admitted transactions
against the commit order.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from reference import ycsb_tick

KERNELS = {"conflict_keys": "conflict_keys"}

# the traced part: after a few untraced steps, at least three ticks
TRACE_AFTER_TICKS = 10
TRACE_SECONDS = 1.0
TRACE_MIN_TICKS = 3


class Ctx:
    pass


def setup(cell, gen, seed: int, span) -> Ctx:
    # a program without key-list ticks stops here, before any work
    from repro.kernels.ops import conflict_keys  # noqa: F401
    import functools

    import jax
    import jax.numpy as jnp
    from repro.sched import scheduler
    cfg, tr = cell.config, cell.traffic
    ctx = Ctx()
    ctx.jax, ctx.gen, ctx.span, ctx.limits = jax, gen, span, cell.limits
    ctx.seed = seed
    ctx.n = int(tr["backlog"])
    ctx.check_ticks = int(tr["check_ticks"])
    burn_in = int(tr["burn_in_ticks"])
    keys, update = gen.stream(cfg, tr, seed)
    ctx.keys, ctx.update = jax.device_put(keys), jax.device_put(update)
    ctx.read, ctx.write = gen.sets(ctx.keys[:ctx.n], ctx.update[:ctx.n])
    ctx.pos = ctx.n
    ctx.valid = jnp.ones(ctx.n, bool)
    ctx.tick = functools.partial(scheduler.tick, policy=cfg["policy"],
                                 order=cfg["order"], keys=True)
    k = int(cfg["keys_per_txn"])
    ctx.shapes = {"conflict_keys": {"n": ctx.n, "kr": k, "kw": k}}
    with span("bench.warm_up"):
        for _ in range(2):          # compile, then one warm tick
            t0 = time.perf_counter()
            res = ctx.tick(ctx.read, ctx.write, ctx.valid)
            jax.block_until_ready(res.admitted)
            ctx.warm_tick_s = time.perf_counter() - t0
            jax.block_until_ready(gen.refill(
                ctx.read, ctx.write, res.admitted, ctx.keys, ctx.update,
                ctx.pos))
    with span("bench.burn_in"):
        st = {"ticks": [], "picks": {0}, "kept": {}}
        _steps(ctx, st, lambda: len(st["ticks"]) < burn_in, False)
    ctx.burn_in = st["ticks"]
    ctx.kept_burn_in = st["kept"]
    return ctx


def _steps(ctx: Ctx, st: dict, more, traced: bool) -> None:
    """Run steps of the loop (a tick, then the refill) while ``more()``;
    the picked ticks keep their inputs and outputs on the device."""
    jax, span = ctx.jax, ctx.span
    while more():
        k = len(st["ticks"])
        t0 = time.perf_counter()
        with span("bench.tick_call"):
            res = ctx.tick(ctx.read, ctx.write, ctx.valid)
            jax.block_until_ready(res.admitted)
        t1 = time.perf_counter()
        with span("bench.refill"):
            if k in st["picks"]:
                st["kept"][k] = (ctx.read, ctx.write, res.admitted,
                                 res.commit_rank)
            committed = int(np.count_nonzero(np.asarray(res.admitted)))
            ctx.read, ctx.write = ctx.gen.refill(
                ctx.read, ctx.write, res.admitted, ctx.keys, ctx.update,
                ctx.pos)
            jax.block_until_ready(ctx.read)
            ctx.pos += committed
        st["ticks"].append((t1 - t0, time.perf_counter() - t0, committed,
                            traced))


def window(ctx: Ctx, seconds: float, tracer) -> dict:
    # the compared ticks, drawn from the seed among the first half of
    # the steps the burn-in's pace says the window holds
    recent = ctx.burn_in[-100:]
    step_s = (sum(s for _, s, _, _ in recent) / len(recent) if recent
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
        return time.perf_counter() - t_begin + st["ticks"][-1][1] <= seconds

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
    commits = sum(c for _, _, c, _ in ticks)
    burn = [c for _, _, c, _ in ctx.burn_in]
    return {
        "attempted": len(ticks) * ctx.n,
        "failed": 0,
        "e2e": {"sim_commits_per_s": commits / loop_s},
        "counters": {"ticks": len(ticks), "commits": commits,
                     "loop_seconds": loop_s,
                     "tick_seconds": sum(t for t, _, _, _ in ticks),
                     "burn_in_ticks": len(burn),
                     "burn_in_commits": sum(burn),
                     "stream_rows_used": ctx.pos,
                     "tick_ms": [t * 1e3 for t, _, _, tr in ticks
                                 if not tr]},
        "shapes": ctx.shapes,
    }


def check(ctx: Ctx, rec: dict, seed: int) -> list:
    """The picked ticks against the reference: rows whose admission or
    commit rank differs, and arcs the program's commit order runs
    backwards."""
    t0 = time.perf_counter()
    adm_bad = rank_bad = violations = 0
    kept = ([(f"burn-in tick {k}", v)
             for k, v in sorted(ctx.kept_burn_in.items())]
            + [(f"tick {k}", v) for k, v in sorted(ctx.kept.items())])
    for name, arrays in kept:
        read, write, admitted, rank = (np.asarray(a) for a in arrays)
        ref = ycsb_tick.tick(read, write, np.ones(ctx.n, bool))
        adm_bad += int((admitted != ref["admitted"]).sum())
        rank_bad += int((rank != ref["commit_rank"]).sum())
        violations += ycsb_tick.order_violations(ref["raw"], admitted,
                                                 rank)
        print(f"{name}: admitted {int(admitted.sum())} program, "
              f"{int(ref['admitted'].sum())} reference", file=sys.stderr)
    print(f"reference {time.perf_counter() - t0:.1f} s", file=sys.stderr)
    rec["failed"] = adm_bad + rank_bad + violations
    lim = ctx.limits
    return [("admitted_mismatches", adm_bad, lim["admitted_mismatches"]),
            ("commit_rank_mismatches", rank_bad,
             lim["commit_rank_mismatches"]),
            ("order_violations", violations, lim["order_violations"])]
