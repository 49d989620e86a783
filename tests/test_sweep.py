"""Padded-lane engines and fleet sweeps (DESIGN.md §2.4).

* a padded run at MPL=m must match the unpadded MPL=m engine
  statistically (same model, different RNG shapes),
* padded slots must stay inert (never active, Theorem-1 invariants hold
  per cohort step),
* the full fig7 grid must compile exactly once, and MPL must be a
  runtime value (no retrace across MPL points).
"""
import subprocess
import sys

import jax.numpy as jnp
import pytest

from repro.core import jaxsim, ppcc, sweep
from repro.core.types import SimParams

GRID = SimParams(db_size=100, txn_size_mean=8, write_prob=0.2, mpl=16,
                 horizon=5_000.0, seed=0)


@pytest.mark.parametrize("protocol", ["ppcc", "2pl", "occ"])
def test_padded_matches_unpadded_same_mpl(protocol):
    """Padding the slot axis must not change the model: commit/abort
    counts track the unpadded engine within the established statistical
    tolerance (RNG streams differ because vector draw shapes differ)."""
    un = jaxsim.simulate(GRID, protocol)
    run = jaxsim.make_padded_engine(GRID, protocol, n_slots=48)
    s = run(jnp.int32(0), jnp.int32(GRID.mpl))
    commits = int(s.commits)
    assert commits > 0
    assert 0.7 * un.commits <= commits <= 1.4 * un.commits, \
        (commits, un.commits)
    assert abs(int(s.aborts) - un.aborts) <= max(10, 0.8 * un.aborts), \
        (int(s.aborts), un.aborts)
    # padded slots never activate
    assert not bool(s.pstate.active[GRID.mpl:].any())
    assert bool((s.phase[GRID.mpl:] == jaxsim.PH_OFF).all())


def test_padded_engine_mpl_is_runtime():
    """One executable serves every MPL point up to the bucket."""
    p = GRID.with_(horizon=1_000.0)
    run = jaxsim.make_padded_engine(p, "ppcc", n_slots=24)
    s8 = run(jnp.int32(0), jnp.int32(8))
    s16 = run(jnp.int32(0), jnp.int32(16))
    s24 = run(jnp.int32(0), jnp.int32(24))
    assert run._cache_size() == 1          # no retrace across MPL values
    assert int(s8.commits) > 0
    # closed-loop model: more slots, more work admitted (weak sanity)
    assert int(s24.pstate.active.sum()) >= int(s8.pstate.active.sum())
    assert not bool(s16.pstate.active[16:].any())


def test_invariants_and_inertness_with_padded_lanes():
    """Theorem-1 invariants hold after every cohort step of a padded
    fleet-body engine, and padded slots stay frozen throughout."""
    p = SimParams(db_size=50, txn_size_mean=8, write_prob=0.5, mpl=12,
                  horizon=1_500.0, seed=3)
    init, cond, step = jaxsim.engine_parts(p, "ppcc", n_slots=32)
    s = init(0, 12)
    steps = 0
    while bool(cond(s)) and steps < 250:
        s = step(s)
        steps += 1
        assert bool(ppcc.acyclic(s.pstate)), f"cycle after step {steps}"
        assert bool(ppcc.path_length_leq_one(s.pstate)), \
            f"path length 2 after step {steps}"
        assert bool(ppcc.classes_consistent(s.pstate)), \
            f"class bits inconsistent after step {steps}"
        assert not bool(s.pstate.active[12:].any()), \
            f"padded slot became active at step {steps}"
        assert bool((s.next_time[12:] > 1e29).all()), \
            f"padded slot scheduled an event at step {steps}"
    assert steps > 50 and int(s.commits) > 0


def test_fig7_grid_compiles_exactly_once():
    """The whole point of the fleet: the full fig7 grid (3 protocols x
    7 MPL points x 2 seeds) is ONE compiled executable, and re-running
    with new MPL/seed values of the same shape does not retrace."""
    mpls = (5, 10, 25, 50, 75, 100, 150)
    out, fleet = sweep.run_fleet(7, mpls, (0, 1), horizon=250.0,
                                 max_iters=40)
    assert fleet.traces == 1
    for proto in sweep.PROTOCOLS:
        assert out[proto]["commits"].shape == (len(mpls), 2)
        assert (out[proto]["iters"] > 0).all()
    fleet((6, 11, 26, 51, 76, 101, 160), (2, 3))     # new values
    assert fleet.traces == 1
    with pytest.raises(ValueError):
        fleet((200,) * len(mpls), (0, 1))            # beyond the bucket


def test_fleet_matches_padded_engine_lanes():
    """Each fleet lane must equal a direct padded-engine run with the
    same (seed, mpl) — the fleet adds vmap, not semantics."""
    p = GRID.with_(horizon=1_500.0)
    fleet = sweep.Fleet(p, protocols=("ppcc",), n_slots=32)
    out = fleet((8, 16), (0, 1))
    run = jaxsim.make_padded_engine(p, "ppcc", n_slots=32, pool=4096)
    for mi, mpl in enumerate((8, 16)):
        for si, seed in enumerate((0, 1)):
            s = run(jnp.int32(seed), jnp.int32(mpl))
            assert int(out["ppcc"]["commits"][mi, si]) == int(s.commits)
            assert int(out["ppcc"]["aborts"][mi, si]) == int(s.aborts)


def test_slot_bucket():
    assert sweep.slot_bucket(5) == 32
    assert sweep.slot_bucket(32) == 32
    assert sweep.slot_bucket(33) == 64
    assert sweep.slot_bucket(150) == 160


_SHARD_SCRIPT = r"""
import jax
assert jax.device_count() == 4, jax.device_count()
from repro.core import sweep
from repro.core.types import paper_figure_params
mesh = sweep.fleet_mesh(4)
assert mesh is not None and mesh.shape["data"] == 4, mesh
p = paper_figure_params(7).with_(horizon=400.0, mpl=5)
sharded = sweep.Fleet(p, protocols=("ppcc",), n_slots=8, mesh=mesh,
                      max_iters=50)
plain = sweep.Fleet(p, protocols=("ppcc",), n_slots=8, max_iters=50)
a = sharded((3, 5), (0, 1))
b = plain((3, 5), (0, 1))
import numpy as np
np.testing.assert_array_equal(np.asarray(a["ppcc"]["commits"]),
                              np.asarray(b["ppcc"]["commits"]))
print("SHARD_OK", np.asarray(a["ppcc"]["commits"]).tolist())
"""


_POD_SCRIPT = r"""
import jax
from repro.core import mesh as fleet_mesh
ok = fleet_mesh.init_distributed(coordinator_address="localhost:12397",
                               num_processes=1, process_id=0)
assert ok and jax.process_count() == 1
assert not fleet_mesh.init_distributed()      # second call: no-op
mesh = fleet_mesh.pod_mesh(n_data=4)
assert mesh is not None, "pod mesh absent after init_distributed"
assert dict(mesh.shape) == {"pod": 1, "data": 4, "model": 1}, mesh
assert fleet_mesh.data_axes(mesh) == ("pod", "data")
from repro.core import sweep
from repro.core.types import paper_figure_params
m2 = sweep.fleet_mesh(8, pods=True)
assert m2 is not None and "pod" in m2.axis_names, m2
p = paper_figure_params(7).with_(horizon=400.0, mpl=5)
sharded = sweep.Fleet(p, protocols=("ppcc",), n_slots=8, mesh=m2,
                      max_iters=50)
plain = sweep.Fleet(p, protocols=("ppcc",), n_slots=8, max_iters=50)
import numpy as np
a = sharded((3, 5), (0, 1, 2, 3))
b = plain((3, 5), (0, 1, 2, 3))
np.testing.assert_array_equal(np.asarray(a["ppcc"]["commits"]),
                              np.asarray(b["ppcc"]["commits"]))
print("POD_OK")
"""


def test_fleet_pod_mesh_single_process_smoke():
    """The multi-host path, single-process: jax.distributed up, the
    ("pod", "data", "model") mesh built, lanes sharded over
    ("pod", "data") — results identical to the unsharded fleet.  Real
    multi-host needs >1 host; this pins the wiring so a pod run only
    differs by process count."""
    import os
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", _POD_SCRIPT],
                       capture_output=True, text=True, timeout=900,
                       env=env, cwd=str(__import__("pathlib").Path(
                           __file__).resolve().parents[1]))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "POD_OK" in r.stdout


def test_fleet_shard_map_over_host_mesh():
    """shard_map over the ("data", "model") mesh splits lanes across
    devices without changing results.  Forced host devices require a
    fresh process (XLA_FLAGS is read at backend init)."""
    import os
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", _SHARD_SCRIPT],
                       capture_output=True, text=True, timeout=900,
                       env=env, cwd=str(__import__("pathlib").Path(
                           __file__).resolve().parents[1]))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "SHARD_OK" in r.stdout
