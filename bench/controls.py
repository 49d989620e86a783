"""Controls and planted faults: the timed path replaced or broken
underneath, to show that ``correct`` comes out false.

    python3 bench/controls.py --workload <cell> --control <name> \
        --seeds 1,2,3 --seconds 5

runs the cell with a control of its surface in the program's place, at
the cell's own size, once per seed, and prints each run's compared
numbers (the control's readings).  The benchmark's own runs never run
this.  The controls of the ``sim`` surface, each the plain simulator on
the program's lanes with one guarantee of the configuration broken:

* ``no_cc``: concurrency control off in every protocol (every operation
  proceeds, every transaction commits without validation);
* ``ppcc_no_class_tests``: PPCC without the Prudent Precedence Rule's
  two class tests; 2PL and OCC as the reference runs them.

``FAULTS`` plant faults in the program's entry point; the tests drive
a whole run with each and see ``correct`` false.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run  # noqa: E402
from reference import acl_sim  # noqa: E402


class NoCC(acl_sim.Protocol):
    """Every operation proceeds; validation always passes."""

    def try_op(self, t, kind, x):
        (t.write_set if kind == acl_sim.WRITE else t.read_set).add(x)
        return acl_sim.PROCEED

    def on_read_done(self, t):
        return "flush"

    def on_leave(self, t):
        pass


class PPCCNoClassTests(acl_sim.PPCC):
    """PPCC without the rule's two class tests: a read may precede a
    writer and a write follow a reader whatever their classes, so
    precedence paths grow past length one."""

    def try_op(self, t, kind, x):
        owner = self.locks.get(x)
        if owner is not None and owner is not t:
            return acl_sim.ABORT if owner in t.succ else acl_sim.BLOCK
        if kind == acl_sim.READ:
            for j in self.writers.get(x, ()):
                if j is not t and j not in t.succ:
                    self._arc(t, j)
            t.read_set.add(x)
            self.readers.setdefault(x, set()).add(t)
            return acl_sim.PROCEED
        for j in self.readers.get(x, ()):
            if j is not t and j not in t.pred:
                self._arc(j, t)
        t.write_set.add(x)
        self.writers.setdefault(x, set()).add(t)
        return acl_sim.PROCEED


# the simulator of each protocol under a control; a protocol it does not
# name runs as the reference runs it
SIM_CONTROLS = {
    "no_cc": {"ppcc": NoCC, "2pl": NoCC, "occ": NoCC},
    "ppcc_no_class_tests": {"ppcc": PPCCNoClassTests},
}


def lanes_of(cfg: dict, tr: dict, seed_vec):
    """(figure, MPL, lane seed) of each lane of the flat seed vector the
    driver hands ``run_lanes`` (figure-major, as ``grid_lanes`` says)."""
    import numpy as np
    s = np.asarray(seed_vec)
    per = len(s) // len(tr["figures"])
    k = per // len(cfg["mpl"])
    return [(f, m, int(s[i * per + j * k + r]))
            for i, f in enumerate(tr["figures"])
            for j, m in enumerate(cfg["mpl"]) for r in range(k)]


def sim_control(mp, cell_name, root=run.ROOT, control="no_cc"):
    """Replace ``Fleet.run_lanes`` by the control ``control``."""
    import dataclasses

    import numpy as np
    from repro.core import sweep
    cell = run.Cell(cell_name, root)
    cfg, tr = cell.config, cell.traffic
    gen = cell.generator()
    fields = {f.name for f in dataclasses.fields(acl_sim.Params)}
    horizon = float(cfg["horizon"])
    sims = SIM_CONTROLS[control]

    def run_lanes(self, seeds, mpls, rts):
        out = {}
        warm = int(np.asarray(mpls).max()) == 1
        for proto in cfg["protocols"]:
            rows = []
            for fig, mpl, s in lanes_of(cfg, tr, seeds):
                lp = gen.lane_params(cfg, fig, 1 if warm else mpl, s,
                                     horizon)
                p = acl_sim.Params(**{f: lp[f] for f in fields})
                rows.append(acl_sim.simulate(p, sims.get(proto, proto)))
            a = np.asarray(rows, np.int64).T
            n = a.shape[1]
            out[proto] = {"commits": a[0], "aborts": a[1], "blocks": a[2],
                          "iters": np.ones(n, np.int64),
                          "now": np.full(n, horizon + 1.0)}
        return out
    mp.setattr(sweep.Fleet, "run_lanes", run_lanes)


CONTROLS = {"sim": sim_control}


# -- planted faults ---------------------------------------------------------

def _wrap(mp, owner, attr, after):
    orig = getattr(owner, attr)

    def wrapped(*a, **kw):
        return after(orig, *a, **kw)
    mp.setattr(owner, attr, wrapped)


def sim_unchanged(mp):
    """The loop hands back its initial state: nothing counted, no lane
    past time zero."""
    import jax
    import jax.numpy as jnp
    from repro.core import sweep
    _wrap(mp, sweep.Fleet, "run_lanes",
          lambda orig, *a: jax.tree.map(jnp.zeros_like, orig(*a)))


def sim_half_batch(mp):
    """Only the first half of the lanes is run and returned."""
    import jax
    from repro.core import sweep

    def after(orig, self, seeds, mpls, rts):
        h = len(seeds) // 2
        return orig(self, seeds[:h], mpls[:h],
                    jax.tree.map(lambda x: x[:h], rts))
    _wrap(mp, sweep.Fleet, "run_lanes", after)


def sim_altered(mp):
    """The ppcc lanes report no blocks."""
    import jax.numpy as jnp
    from repro.core import sweep

    def after(orig, *a):
        out = dict(orig(*a))
        out["ppcc"] = dict(out["ppcc"],
                           blocks=jnp.zeros_like(out["ppcc"]["blocks"]))
        return out
    _wrap(mp, sweep.Fleet, "run_lanes", after)


FAULTS = {
    "sim": {"unchanged": sim_unchanged, "half_batch": sim_half_batch,
            "altered": sim_altered},
}


class Patch:
    """A minimal ``monkeypatch`` for use outside pytest."""

    def __init__(self):
        self.undo = []

    def setattr(self, owner, name, value):
        self.undo.append((owner, name, getattr(owner, name)))
        setattr(owner, name, value)

    def close(self):
        for owner, name, value in reversed(self.undo):
            setattr(owner, name, value)
        self.undo.clear()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--control", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    cell = run.Cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        mp = Patch()
        try:
            CONTROLS[cell.config["surface"]](mp, args.workload,
                                             control=args.control)
            out = run.run(args.workload, seed, args.seconds, False)
        finally:
            mp.close()
        print(json.dumps({"seed": seed, "control": args.control,
                          "correct": out["correct"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
