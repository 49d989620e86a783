"""Long-horizon drift gates on the peaks of the figs 5-16 grid.

A figure's peak, per protocol, is the largest over MPL of its mean
commits over seeds 0, 1 and 2, from ``sweep.run_grid`` over the
paper's MPL grid.  Two gates, each failing on any (figure, protocol)
peak more than ``PEAK_TOL`` (relative) away from its pin:

* at horizon 20 000 against ``REPRO_PEAKS_20K``, peaks pinned at the
  commit that introduced the one-executable grid (CPU) — a regression
  gate on the protocol physics;
* at the paper's horizon 100 000 against the paper's own peaks
  (``types.PAPER_PEAKS``).

Both take long on a CPU (the 20 000 gate is the nightly job), so they
run only when pytest is given ``--paper-peaks``:

    PYTHONPATH=src python -m pytest --paper-peaks tests/test_paper_peaks.py

Re-pin ``REPRO_PEAKS_20K`` only when a change means to alter the
simulator's behaviour; the paper gate still bounds the result.
"""
import pytest

from repro.core import sweep
from repro.core.types import GRID_FIGS, PAPER_PEAKS

MPL_GRID = (5, 10, 25, 50, 75, 100, 150)
SEEDS = (0, 1, 2)
PEAK_TOL = 0.35
SNAPSHOT_HORIZON = 20_000.0
PAPER_HORIZON = 100_000.0
# (ppcc, 2pl, occ) peaks at SNAPSHOT_HORIZON, to the report's rounding
# (±0.5 commit).  The paper's peaks do not bound a 20 000 run: curves
# converge sublinearly in the horizon, 2PL's worst (fig 16 at -0.66).
REPRO_PEAKS_20K = {
    5: (525.0, 497.0, 385.0),
    6: (330.0, 268.0, 250.0),
    7: (191.0, 175.0, 146.0),
    8: (81.0, 59.0, 76.0),
    9: (494.0, 474.0, 350.0),
    10: (240.0, 175.0, 205.0),
    11: (157.0, 141.0, 129.0),
    12: (53.0, 39.0, 59.0),
    13: (1568.0, 1232.0, 1140.0),
    14: (405.0, 276.0, 517.0),
    15: (1158.0, 714.0, 930.0),
    16: (244.7, 151.0, 367.0),
}


@pytest.fixture
def paper_peaks(request):
    if not request.config.getoption("--paper-peaks"):
        pytest.skip("long-horizon gate: give pytest --paper-peaks")


def grid_peaks(horizon: float) -> dict:
    out, _ = sweep.run_grid(GRID_FIGS, MPL_GRID, SEEDS, horizon=horizon)
    return {(fig, proto): float(out[fig][proto]["commits"]
                                .mean(axis=1).max())
            for fig in GRID_FIGS for proto in sweep.PROTOCOLS}


def drifts(peaks: dict, pins: dict) -> list:
    """(figure, protocol, peak, pin, relative delta) past PEAK_TOL."""
    out = []
    for fig in GRID_FIGS:
        for proto, pin in zip(sweep.PROTOCOLS, pins[fig]):
            got = peaks[fig, proto]
            rel = (got - pin) / max(pin, 1.0)
            print(f"fig {fig} {proto}: peak {got} pin {pin} rel {rel:+.4f}")
            if abs(rel) > PEAK_TOL:
                out.append((fig, proto, got, pin, rel))
    return out


def test_snapshot_peaks_20k(paper_peaks):
    assert not drifts(grid_peaks(SNAPSHOT_HORIZON), REPRO_PEAKS_20K)


def test_paper_peaks_100k(paper_peaks):
    assert not drifts(grid_peaks(PAPER_HORIZON), PAPER_PEAKS)
