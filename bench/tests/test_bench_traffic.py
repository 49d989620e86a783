"""The traffic generator on the CPU: the lanes of the paper grid per
call."""
import json

import pytest

from bench_test_util import BENCH

import run

GRID = run.load_module(BENCH / "traffic" / "grid_lanes.py")


def test_grid_lane_seeds_per_call():
    cfg = json.loads((BENCH / "configs" / "acl-table1.json").read_text())
    tr = {"figures": [5, 6], "replicas_per_point": 2}
    a = GRID.lanes(cfg, tr, 2**31 + 9, 0)
    assert a == GRID.lanes(cfg, tr, 2**31 + 9, 0)
    b = GRID.lanes(cfg, tr, 2**31 + 9, 1)
    c = GRID.lanes(cfg, tr, 2**31 + 10, 0)
    seeds = [s for _, _, s in a + b + c]
    assert len(set(seeds)) == len(seeds) == 3 * 2 * 7 * 2
    assert all(0 <= x < 2**31 for x in seeds)
    assert [(f, m) for f, m, _ in a] == [(f, m) for f, m, _ in b]


@pytest.mark.parametrize("reps", [1, 2])
def test_grid_lanes_are_figure_major(reps):
    cfg = json.loads((BENCH / "configs" / "acl-table1.json").read_text())
    tr = json.loads((BENCH / "traffic" / "paper-grid.json").read_text())
    tr["replicas_per_point"] = reps
    lanes = GRID.lanes(cfg, tr, 3, 0)
    assert len(lanes) == 12 * 7 * reps
    assert lanes[0][:2] == (5, 5) and lanes[reps][:2] == (5, 10)
    assert lanes[7 * reps][:2] == (6, 5)
    lp = GRID.lane_params(cfg, 7, 50, lanes[0][2], 2000.0)
    assert (lp["txn_size_mean"], lp["db_size"], lp["mpl"]) == (16, 500, 50)
