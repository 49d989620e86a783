"""Driver of the simulator surface: the paper grid through
``sweep.Fleet.run_lanes``, the one executable ``sweep.run_grid`` builds.

Set-up builds the fleet over the configuration's static buckets and
runs it once on lanes at MPL 1 (same executable, little work), which
loads or compiles it.  The window runs grid calls back to back, each on
fresh lane seeds (one per lane); a call starts only while the last
call's duration still fits in the window.  ``correct`` compares one call, drawn from the
seed, with the plain event-driven simulator run on the same lanes,
figure by figure.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from reference import acl_sim

KERNELS = {"megastep": "megastep"}
KEYS = ("commits", "aborts", "blocks", "iters", "now")
METRICS = ("commits", "aborts", "blocks")     # compared with the reference


class Ctx:
    pass


def fig_params(config: dict, fig: int, horizon: float):
    from repro.core.types import SimParams
    return SimParams(**config["table1"], **config["figures"][str(fig)],
                     horizon=horizon)


def setup(cell, gen, seed: int, span) -> Ctx:
    import jax
    import jax.numpy as jnp
    from repro.core import bitset, jaxsim, sweep
    from repro.core.types import SimParams
    cfg, tr = cell.config, cell.traffic
    ctx = Ctx()
    ctx.jax, ctx.gen, ctx.cfg, ctx.tr, ctx.span = jax, gen, cfg, tr, span
    ctx.seed, ctx.limits = seed, cell.limits
    ctx.horizon = float(cfg["horizon"])
    figs, mpls = tr["figures"], cfg["mpl"]
    reps = int(tr["replicas_per_point"])
    ps = [fig_params(cfg, f, ctx.horizon) for f in figs]
    # the static buckets every figure fits, as run_grid builds them
    cover = SimParams(**cfg["table1"],
                      db_size=max(p.db_size for p in ps),
                      txn_size_mean=max(p.txn_size_mean for p in ps),
                      write_prob=ps[0].write_prob,
                      num_cpus=max(p.num_cpus for p in ps),
                      num_disks=max(p.num_disks for p in ps),
                      horizon=ctx.horizon)
    per_fig = len(mpls) * reps
    ctx.n_lanes = len(figs) * per_fig
    ctx.fleet = sweep.Fleet(cover, protocols=cfg["protocols"],
                            n_slots=sweep.slot_bucket(max(mpls)),
                            mesh=sweep.fleet_mesh(ctx.n_lanes))
    ctx.rt_l = jax.tree.map(
        lambda *xs: jnp.repeat(jnp.stack(xs), per_fig),
        *[jaxsim.rt_of(p) for p in ps])
    ctx.mpl_l = jnp.tile(jnp.repeat(jnp.asarray(mpls, jnp.int32), reps),
                         len(figs))
    ctx.shapes = {"megastep": {"lanes": ctx.n_lanes,
                               "n": ctx.fleet.n_slots,
                               "words": bitset.n_words(cover.db_size)}}
    with span("bench.warm_up"):
        out = ctx.fleet.run_lanes(lane_seed_vector(ctx, 0),
                                  jnp.ones_like(ctx.mpl_l), ctx.rt_l)
        jax.block_until_ready(out)
    return ctx


def lane_seed_vector(ctx: Ctx, call: int):
    lanes = ctx.gen.lanes(ctx.cfg, ctx.tr, ctx.seed, call)
    return ctx.jax.numpy.asarray([s for _, _, s in lanes], np.int32)


# the traced part: the first seconds of the first call, inside the ppcc
# loop (the protocols' loops run one after another in the call); a
# whole call is millions of device operations
TRACE_SECONDS = 2.0


def window(ctx: Ctx, seconds: float, tracer) -> dict:
    jax, span = ctx.jax, ctx.span
    calls = []
    traces = ctx.fleet.traces
    t_begin = time.perf_counter()
    while True:
        seed_l = lane_seed_vector(ctx, len(calls))
        t0 = time.perf_counter()
        with span("bench.grid_call"):
            if tracer.on and not calls:
                with tracer.part(), span("bench.grid_call"):
                    out = ctx.fleet.run_lanes(seed_l, ctx.mpl_l, ctx.rt_l)
                    time.sleep(TRACE_SECONDS)
            else:
                out = ctx.fleet.run_lanes(seed_l, ctx.mpl_l, ctx.rt_l)
            jax.block_until_ready(out)
        with span("bench.result_transfer"):
            host = {p: {k: np.asarray(out[p][k]) for k in KEYS}
                    for p in out}
        t1 = time.perf_counter()
        calls.append({"seconds": t1 - t0, "out": host})
        if (t1 - t_begin) + (t1 - t0) > seconds:
            break
    if ctx.fleet.traces != traces:
        raise RuntimeError("the fleet traced again inside the window")
    ctx.calls = calls
    call_s = sum(c["seconds"] for c in calls)
    commits = sum(int(r["commits"].sum()) for c in calls
                  for r in c["out"].values())
    iters = sum(int(r["iters"].max()) for c in calls
                for r in c["out"].values())
    return {
        "attempted": len(calls) * ctx.n_lanes * len(ctx.cfg["protocols"]),
        "failed": 0,
        "e2e": {"sim_commits_per_s": commits / call_s},
        "counters": {"calls": len(calls), "call_seconds": call_s,
                     "iters_slowest_lanes": iters, "commits": commits},
        "shapes": ctx.shapes,
    }


def gap(prog: int, ref: int) -> float:
    """How far two counts lie apart, in units of the spread of a Poisson
    count of their size.  A share of the reference would swing on a
    figure's small counts and let a shift of some percent pass on its
    large ones."""
    return abs(prog - ref) / float(np.sqrt(prog + ref + 1))


def padded(a, n: int) -> np.ndarray:
    """``a`` as ``n`` lanes; lanes the program did not return count 0."""
    out = np.zeros(n, np.int64)
    a = np.asarray(a, np.int64).reshape(-1)[:n]
    out[:len(a)] = a
    return out


def check(ctx: Ctx, rec: dict, seed: int) -> list:
    """One call, drawn from the seed, against the reference on its own
    lanes: for each figure and protocol the summed commits, aborts and
    blocks of its lanes, and the worst ``gap`` of each over all figures
    and protocols; and every lane of every call must have run to the
    horizon."""
    import dataclasses
    k = int(np.random.default_rng([seed, 7]).integers(len(ctx.calls)))
    lanes = ctx.gen.lanes(ctx.cfg, ctx.tr, ctx.seed, k)
    fields = {f.name for f in dataclasses.fields(acl_sim.Params)}
    short = 0
    for c in ctx.calls:
        for proto in ctx.cfg["protocols"]:
            now = c["out"].get(proto, {}).get("now", np.zeros(0))
            short += ctx.n_lanes - int((np.asarray(now) > ctx.horizon).sum())
    figs = np.asarray([f for f, _, _ in lanes])
    worst = {m: (0.0, "") for m in METRICS}
    out = ctx.calls[k]["out"]
    t0 = time.perf_counter()
    for proto in ctx.cfg["protocols"]:
        ref = np.asarray([
            acl_sim.simulate(acl_sim.Params(**{
                f: v for f, v in ctx.gen.lane_params(
                    ctx.cfg, fig, mpl, s, ctx.horizon).items()
                if f in fields}), proto)
            for fig, mpl, s in lanes], np.int64)
        res = out.get(proto, {})
        for i, m in enumerate(METRICS):
            got = padded(res.get(m, 0), ctx.n_lanes)
            for fig in ctx.tr["figures"]:
                on = figs == fig
                g = gap(int(got[on].sum()), int(ref[on, i].sum()))
                if g > worst[m][0]:
                    worst[m] = (g, f"{proto} fig {fig}")
    print(f"reference {time.perf_counter() - t0:.1f} s", file=sys.stderr)
    for m, (g, where) in worst.items():
        print(f"worst {m}_gap {g!r} at {where}", file=sys.stderr)
    rec["failed"] = short
    lim = ctx.limits
    return [(f"{m}_gap", worst[m][0], lim[f"{m}_gap"]) for m in METRICS] + [
        ("lanes_short", short, lim["lanes_short"])]
