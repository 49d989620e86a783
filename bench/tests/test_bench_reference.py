"""The plain reference against the system at a tiny size on the CPU:
the event-driven simulator against ``Fleet.run_lanes`` (same model,
own random streams: in distribution)."""
import dataclasses
import json

import numpy as np
import pytest

from bench_test_util import BENCH, DATA

import run
from reference import acl_sim

GRID = run.load_module(BENCH / "traffic" / "grid_lanes.py")
FIELDS = [f.name for f in dataclasses.fields(acl_sim.Params)]


def test_simulator_matches_the_fleet_in_distribution():
    import jax
    import jax.numpy as jnp
    from repro.core import jaxsim, sweep
    from repro.core.types import SimParams
    cfg = json.loads((DATA / "tiny-sim.json").read_text())
    horizon, mpl = 3000.0, 20
    p = SimParams(**cfg["table1"], **cfg["figures"]["6"], mpl=mpl,
                  horizon=horizon)
    seeds = list(range(8))
    fleet = sweep.Fleet(p)
    rt = jax.tree.map(lambda x: jnp.broadcast_to(x, (len(seeds),)),
                      jaxsim.rt_of(p))
    out = fleet.run_lanes(seeds, [mpl] * len(seeds), rt)
    for proto in sweep.PROTOCOLS:
        ref = np.zeros(3)
        for s in seeds:
            lp = GRID.lane_params(cfg, 6, mpl, 1000 + s, horizon)
            ref += acl_sim.simulate(
                acl_sim.Params(**{f: lp[f] for f in FIELDS}), proto)
        got = [int(np.asarray(out[proto][m]).sum())
               for m in ("commits", "aborts", "blocks")]
        assert abs(got[0] - ref[0]) <= 0.15 * ref[0], (proto, got, ref)
        for g, r in zip(got[1:], ref[1:]):
            assert abs(g - r) <= 0.35 * max(r, 20), (proto, got, ref)


@pytest.mark.parametrize("proto", ["ppcc", "2pl", "occ"])
def test_simulator_copy_equals_the_program_oracle(proto):
    """The copy kept under bench/ is event-for-event the repository's
    ``pysim`` (same random stream), so moving or changing that one
    leaves the benchmark's reference as it was."""
    from repro.core.pysim import simulate
    from repro.core.types import paper_figure_params
    for fig, mpl, seed in ((6, 25, 3), (7, 10, 4), (15, 50, 5)):
        p = paper_figure_params(fig).with_(mpl=mpl, horizon=1500.0,
                                           seed=seed)
        r = simulate(p, proto)
        got = acl_sim.simulate(
            acl_sim.Params(**{f: getattr(p, f) for f in FIELDS}), proto)
        assert got == (r.commits, r.aborts, r.blocks)
