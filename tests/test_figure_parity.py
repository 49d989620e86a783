"""Every paper figure's lanes through the one fleet executable against
the event-heap oracle (``pysim``), one case per figure and protocol.

``sweep.run_grid`` runs figs 5-16 at MPL 5 and 25 with seeds 0 and 1,
horizon 2 000; ``pysim`` runs the same lanes.  For each figure and
protocol the summed commits, aborts and blocks of its four lanes must
lie within ``LIMIT`` of the oracle's, in the gap |P - R| / sqrt(P + R + 1)
the benchmark's check uses (``bench/drivers/sim.py``): a distance in
units of the spread of a Poisson count of that size.  The engine and
the oracle share the model but not their random streams, so the bound
is statistical.

Two controls of the benchmark (``bench/controls.py``) show the check has
power: each runs the plain simulator with one guarantee broken, in the
engine's place, on the same lanes, and the check must flag it.
"""
import dataclasses
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core import pysim, sweep
from repro.core.types import GRID_FIGS, paper_figure_params

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "bench"))
import controls  # noqa: E402
from reference import acl_sim  # noqa: E402

MPLS, SEEDS, HORIZON = (5, 25), (0, 1), 2_000.0
METRICS = ("commits", "aborts", "blocks")
# about twice the worst gap the engine shows on these lanes; a
# correct simulator drawing its own streams stays under it
LIMIT = 4.0


def lane_params(fig: int):
    return [paper_figure_params(fig).with_(mpl=m, seed=s, horizon=HORIZON)
            for m in MPLS for s in SEEDS]


def gap(prog, ref) -> float:
    return abs(int(prog) - int(ref)) / float(np.sqrt(prog + ref + 1))


def worst_gap(got, ref) -> float:
    return max(gap(g, r) for g, r in zip(got, ref))


@pytest.fixture(scope="module")
def engine():
    """{(figure, protocol): summed [commits, aborts, blocks]}."""
    grid, _ = sweep.run_grid(GRID_FIGS, MPLS, SEEDS, horizon=HORIZON)
    return {(fig, proto): [int(grid[fig][proto][m].sum()) for m in METRICS]
            for fig in GRID_FIGS for proto in sweep.PROTOCOLS}


@pytest.fixture(scope="module")
def reference():
    out = {}
    for fig in GRID_FIGS:
        for proto in sweep.PROTOCOLS:
            runs = [pysim.simulate(p, proto) for p in lane_params(fig)]
            out[fig, proto] = [sum(getattr(r, m) for r in runs)
                               for m in METRICS]
    return out


@pytest.mark.parametrize("protocol", sweep.PROTOCOLS)
@pytest.mark.parametrize("fig", GRID_FIGS)
def test_figure_parity(engine, reference, fig, protocol):
    got, ref = engine[fig, protocol], reference[fig, protocol]
    assert got[0] > 0
    gaps = dict(zip(METRICS, (gap(g, r) for g, r in zip(got, ref))))
    assert max(gaps.values()) <= LIMIT, (gaps, got, ref)


@pytest.mark.parametrize("control", ["no_cc", "ppcc_no_class_tests"])
def test_figure_parity_flags_control(reference, control):
    """The control's protocols through ``acl_sim`` in the engine's place:
    at least one of their (figure, protocol) cases is flagged.  A
    protocol the control does not name runs as the reference runs it,
    so only the named ones are run."""
    sims = controls.SIM_CONTROLS[control]
    fields = {f.name for f in dataclasses.fields(acl_sim.Params)}
    flagged = []
    for proto, sim in sims.items():
        for fig in GRID_FIGS:
            got = np.zeros(len(METRICS), np.int64)
            for p in lane_params(fig):
                got += acl_sim.simulate(acl_sim.Params(**{
                    f: v for f, v in dataclasses.asdict(p).items()
                    if f in fields}), sim)
            if worst_gap(got, reference[fig, proto]) > LIMIT:
                flagged.append((fig, proto))
    assert flagged
