"""Chip smoke test: drive both main paths once on a TPU at the paper's
full state size and check every answer against a reference.

    python chip_smoke.py               # one chip (the default)
    python chip_smoke.py --four-chips  # lane-sharded grid on a 4-chip host

One process, the normal entry points, no CPU fallback: the script exits
non-zero before any phase unless JAX's first device is a TPU.

Phases on one chip:

1. ``grid`` — ``sweep.run_grid`` over the paper's figs 5-16 x MPL x seed
   grid for ppcc/2pl/occ as one fleet executable (160 slots, 500-item /
   20-op buckets, horizon 20 000, default engine body, which is the
   Pallas megakernel on the TPU).  Checks: the grid traced once, a warm
   re-run returns the same counts, every lane stopped at the horizon,
   and the mid-grid commits (MPL 50, seed 0) of fig 7 (500 items) and
   fig 6 (100 items) lie within ``ORACLE_BAND`` of the ``pysim``
   event-heap oracle, the band ``tests/test_jaxsim_vs_pysim.py`` uses.
2. ``megakernel`` — the fig-7 ppcc lanes through
   ``jaxsim.engine_parts`` with the megakernel on and off,
   vmapped as ``Fleet`` runs them: commits, aborts and iterations must
   be equal, and the default body must contain the compiled kernel.
3. ``scheduler`` — ``scheduler.tick`` at 256 transactions over 1024
   items: ppcc in priority order, in degree order and with carried
   state, 2pl and occ; every admitted set is bit-equal to the
   ``use_kernel=False`` reference path.

``--four-chips`` runs only the grid sharded over all four chips
(``run_grid(shard=True)``) and the same lanes unsharded, at horizon
2 000, and checks every figure's counts bit-identical.

Every timing line names the device it ran on.  The last line of
standard output is the JSON object ``{"ok": true, "device": {...}}``;
any failed check raises before it is printed.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import compile_cache  # noqa: E402
from repro.core import jaxsim, sweep  # noqa: E402
from repro.core.pysim import simulate as pysim_simulate  # noqa: E402
from repro.core.types import GRID_FIGS, paper_figure_params  # noqa: E402
from repro.sched import scheduler  # noqa: E402

MPLS = (5, 10, 25, 50, 75, 100, 150)
SEEDS = (0, 1)
HORIZON = 20_000.0
ORACLE_FIGS = (7, 6)          # one 500-item and one 100-item figure
ORACLE_SEEDS = (0, 1, 2)      # pysim reference = mean over these seeds
ORACLE_BAND = (0.55, 1.6)     # fleet / oracle commits, as in the tests
# the four-chip check is bit-identity of the lane sharding, which a
# tenth of the horizon shows as well while costing a tenth of the time
# on four chips
FOUR_CHIP_HORIZON = 2_000.0


class SmokeError(RuntimeError):
    """A check of the smoke test failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeError(what)


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def say(dev: dict, msg: str) -> None:
    print(f"[{dev['platform']} {dev['kind']} x{dev['count']}] {msg}",
          flush=True)


def phase_grid(dev: dict, figs=GRID_FIGS, mpls=MPLS, seeds=SEEDS,
               horizon: float = HORIZON, oracle_figs=ORACLE_FIGS) -> dict:
    """The figs grid through ``run_grid`` (cold, then warm on the same
    executable) plus the mid-grid pysim oracle check."""
    t0 = time.perf_counter()
    out, fleet = sweep.run_grid(figs, mpls, seeds, horizon)
    cold = time.perf_counter() - t0
    seed_l, mpl_l, rt_l = sweep.grid_lanes(figs, mpls, seeds)
    t0 = time.perf_counter()
    flat = jax.block_until_ready(fleet.run_lanes(seed_l, mpl_l, rt_l))
    warm = time.perf_counter() - t0
    check(fleet.traces == 1, f"grid traced {fleet.traces} times, not 1")
    commits = int(sum(np.asarray(res["commits"]).sum()
                      for res in flat.values()))
    say(dev, f"grid lanes={len(figs) * len(mpls) * len(seeds)} x "
             f"{len(fleet.protocols)} protocols horizon={horizon:g} "
             f"n_slots={fleet.n_slots}: cold_s={cold:.3f} (compile "
             f"included) warm_s={warm:.3f} simulated_commits={commits} "
             f"commits_per_wall_s={commits / warm:.1f}")

    shape = (len(figs), len(mpls), len(seeds))
    for proto, res in flat.items():
        for metric in sweep.METRICS:
            a = np.asarray(res[metric]).reshape(shape)
            check(all(np.array_equal(a[i], out[f][proto][metric])
                      for i, f in enumerate(figs)),
                  f"warm {proto} {metric} differs from the cold run")
        # a lane that ends early hit the iteration cap or ran dry
        check(bool((np.asarray(res["now"]) > horizon).all()),
              f"{proto}: a lane stopped before the horizon")
        for f in figs:
            check(out[f][proto]["commits"].sum() > 0,
                  f"fig{f} {proto}: no commits")

    mid = len(mpls) // 2
    for f in oracle_figs:
        base = paper_figure_params(f).with_(mpl=mpls[mid], horizon=horizon)
        for proto in fleet.protocols:
            ref = float(np.mean([pysim_simulate(base.with_(seed=s),
                                                proto).commits
                                 for s in ORACLE_SEEDS]))
            got = int(out[f][proto]["commits"][mid, 0])
            check(ref > 0, f"fig{f} {proto}: pysim committed nothing")
            lo, hi = ORACLE_BAND
            say(dev, f"oracle fig{f} {proto} mpl={mpls[mid]} seed=0: "
                     f"fleet_commits={got} pysim_mean={ref:.1f} "
                     f"ratio={got / ref:.3f}")
            check(lo * ref <= got <= hi * ref,
                  f"fig{f} {proto}: fleet {got} outside "
                  f"[{lo}, {hi}] x pysim {ref:.1f}")
    return {"cold_s": cold, "warm_s": warm, "commits": commits}


def phase_megakernel(dev: dict, mpls=MPLS, seeds=SEEDS,
                     horizon: float = HORIZON) -> None:
    """Fig-7 ppcc lanes with the megakernel on and off: equal counts.
    Built as ``Fleet`` builds its lanes (same slot bucket and pool)."""
    p = paper_figure_params(7).with_(horizon=horizon)
    n_slots = sweep.slot_bucket(max(mpls))
    pool = max(4096, int(horizon) // 6)
    seed_l = jnp.asarray(np.tile(seeds, len(mpls)), jnp.int32)
    mpl_l = jnp.asarray(np.repeat(mpls, len(seeds)), jnp.int32)
    rts = jax.tree.map(lambda x: jnp.broadcast_to(x, mpl_l.shape),
                       jaxsim.rt_of(p))

    def parts(megakernel):
        return jaxsim.engine_parts(p, "ppcc", n_slots=n_slots, pool=pool,
                                   megakernel=megakernel)

    # the default body must carry the kernel exactly where it compiles
    init, _, step = parts(None)
    hlo = step.lower(init(0, 5, jaxsim.rt_of(p))).as_text()
    has_kernel = "tpu_custom_call" in hlo
    check(has_kernel == (jax.default_backend() == "tpu"),
          f"default engine body: megakernel present={has_kernel} on "
          f"{jax.default_backend()}")

    res = {}
    for mk in (True, False):
        init, cond, step = parts(mk)
        run = jax.jit(jax.vmap(
            lambda s, m, r: jax.lax.while_loop(cond, step, init(s, m, r))))
        t0 = time.perf_counter()
        fin = jax.block_until_ready(run(seed_l, mpl_l, rts))
        wall = time.perf_counter() - t0
        res[mk] = {k: np.asarray(getattr(fin, k))
                   for k in ("commits", "aborts", "iters")}
        say(dev, f"fig7 ppcc lanes={seed_l.shape[0]} megakernel={mk}: "
                 f"cold_s={wall:.3f} (compile included) "
                 f"commits={int(res[mk]['commits'].sum())} "
                 f"iters_max={int(res[mk]['iters'].max())}")
    check(res[True]["commits"].sum() > 0, "fig7 ppcc lanes: no commits")
    for k in ("commits", "aborts", "iters"):
        check(np.array_equal(res[True][k], res[False][k]),
              f"megakernel vs jnp twin: {k} differ")
    say(dev, f"megakernel default_body_has_kernel={has_kernel}; "
             "megakernel == jnp twin on commits, aborts, iters")


def _tick_inputs(n: int, d: int, seed: int):
    rng = np.random.default_rng(seed)
    reads = jnp.asarray(rng.random((n, d)) < 10 / d)
    writes = jnp.asarray(rng.random((n, d)) < 5 / d)
    valid = jnp.asarray(rng.random(n) < 0.9)
    return reads, writes, valid


def _same(a: scheduler.TickResult, b: scheduler.TickResult,
          what: str) -> None:
    for field in ("admitted", "aborted", "commit_rank"):
        check(np.array_equal(np.asarray(getattr(a, field)),
                             np.asarray(getattr(b, field))),
              f"scheduler {what}: {field} differs from the reference")


def phase_scheduler(dev: dict, n: int = 256, d: int = 1024,
                    warm_ticks: int = 20) -> None:
    """``tick`` (Pallas conflict kernels) vs the ``use_kernel=False``
    reference, per policy and ppcc variant."""
    r, w, v = _tick_inputs(n, d, 0)
    r2, w2, _ = _tick_inputs(n, d, 1)
    n_valid = int(np.asarray(v).sum())
    cases = {   # name: (tick keywords, reference tick)
        "ppcc": ({}, scheduler.ppcc_tick),
        "ppcc_degree": ({"order": "degree"},
                        functools.partial(scheduler.ppcc_tick,
                                          order="degree")),
        "2pl": ({"policy": "2pl"}, scheduler.twopl_tick),
        "occ": ({"policy": "occ"}, scheduler.occ_tick),
    }
    for name, (kw, ref_tick) in cases.items():
        t0 = time.perf_counter()
        got = jax.block_until_ready(scheduler.tick(r, w, v, **kw))
        cold = time.perf_counter() - t0
        times = []
        for _ in range(warm_ticks):
            t0 = time.perf_counter()
            jax.block_until_ready(scheduler.tick(r, w, v, **kw))
            times.append(time.perf_counter() - t0)
        want = jax.jit(functools.partial(ref_tick, use_kernel=False))(
            r, w, v)
        _same(got, want, name)
        adm = int(np.asarray(got.admitted).sum())
        check(0 < adm < n_valid, f"scheduler {name}: degenerate batch "
                                 f"({adm} of {n_valid} admitted)")
        say(dev, f"tick {name} n={n} d={d}: cold_s={cold:.4f} (compile "
                 f"included) warm_p50_s={np.median(times):.6f} "
                 f"admitted={adm}/{n_valid}")

    # carried state: an unchanged batch reuses the carried relations,
    # a changed one relaunches; both against the reference carry
    carry_ref = jax.jit(functools.partial(
        scheduler.ppcc_tick, use_kernel=False, return_carry=True))
    got0, c_got = scheduler.tick(r, w, v, return_carry=True)
    want0, c_want = carry_ref(r, w, v)
    _same(got0, want0, "ppcc_carry first")
    for label, (rr, ww) in (("unchanged", (r, w)), ("changed", (r2, w2))):
        got1, _ = scheduler.tick(rr, ww, v, carry=c_got, return_carry=True)
        want1, _ = carry_ref(rr, ww, v, carry=c_want)
        _same(got1, want1, f"ppcc_carry {label}")
    say(dev, "tick ppcc_carry: unchanged and changed batches equal the "
             "reference")


def phase_four_chips(dev: dict, figs=GRID_FIGS, mpls=MPLS, seeds=SEEDS,
                     horizon: float = FOUR_CHIP_HORIZON) -> None:
    """The grid sharded over every device vs the same lanes unsharded:
    per-figure counts bit-identical."""
    t0 = time.perf_counter()
    sharded, f_sh = sweep.run_grid(figs, mpls, seeds, horizon, shard=True)
    t_sh = time.perf_counter() - t0
    check(f_sh.mesh is not None and f_sh.mesh.size == dev["count"],
          f"sharded grid mesh {f_sh.mesh} does not span "
          f"{dev['count']} devices")
    t0 = time.perf_counter()
    plain, _ = sweep.run_grid(figs, mpls, seeds, horizon, shard=False)
    t_pl = time.perf_counter() - t0
    for f in figs:
        for proto in f_sh.protocols:
            for metric in sweep.METRICS + ("now",):
                check(np.array_equal(sharded[f][proto][metric],
                                     plain[f][proto][metric]),
                      f"fig{f} {proto} {metric}: sharded != unsharded")
    say(dev, f"grid horizon={horizon:g} sharded over {f_sh.mesh.size} "
             f"devices: "
             f"cold_s={t_sh:.3f}; unsharded on one device: "
             f"cold_s={t_pl:.3f} (compile included in both); "
             f"{len(figs)} figures bit-identical")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip sharded-vs-unsharded grid")
    args = ap.parse_args(argv)
    dev = device_info()
    if dev["platform"] != "tpu":
        print(f"no TPU: JAX's first device is {dev['platform']}",
              file=sys.stderr)
        return 1
    if args.four_chips and dev["count"] != 4:
        print(f"--four-chips needs 4 devices, found {dev['count']}",
              file=sys.stderr)
        return 1
    cache = compile_cache.enable()
    say(dev, f"compile cache: {cache}")
    if args.four_chips:
        phase_four_chips(dev)
    else:
        phase_grid(dev)
        phase_megakernel(dev)
        phase_scheduler(dev)
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
