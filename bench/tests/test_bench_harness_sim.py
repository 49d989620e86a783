"""The harness end to end on the CPU, simulator surface, at a tiny
size: a cell it has never seen runs and is correct; each control and
each planted fault make ``correct`` false.  Also: without a TPU, and
in a directory holding only the benchmark's files, a run exits non-zero
and prints no result."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench_test_util import BENCH, REPO, new_cell_root, run_cpu

import controls

TRAFFIC = {"kind": "grid_lanes", "figures": [6, 10],
           "replicas_per_point": 1}
# the tiny cell's own limits, set from its readings on the CPU: sound
# runs read gaps of 0.7-2.1 on four seeds, the PPCC control's blocks
# 3.5-4.4 and every fault 5.6 or more on one of the numbers
LIMITS = {"commits_gap": 3.0, "aborts_gap": 3.0, "blocks_gap": 3.0,
          "lanes_short": 0}
SEED = 2**31 + 977


@pytest.fixture
def root(tmp_path):
    return new_cell_root(tmp_path, "sim-grid-fig5-16", "tiny-grid",
                         "tiny-sim", TRAFFIC, LIMITS)


def test_new_cell_runs_and_is_correct(monkeypatch, root):
    out = run_cpu(monkeypatch, root, "tiny-grid", SEED, 0.1)
    assert out["correct"], out["checks"]
    assert out["metrics"]["sim_commits_per_s"]["value"] > 0
    assert out["metrics"]["setup_s"]["value"] > 0
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == set(LIMITS)


@pytest.mark.parametrize("fault", sorted(controls.FAULTS["sim"]))
def test_fault_is_not_correct(monkeypatch, root, fault):
    controls.FAULTS["sim"][fault](monkeypatch)
    out = run_cpu(monkeypatch, root, "tiny-grid", SEED, 0.1)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("control", sorted(controls.SIM_CONTROLS))
def test_control_is_not_correct(monkeypatch, root, control):
    controls.sim_control(monkeypatch, "tiny-grid", root, control)
    out = run_cpu(monkeypatch, root, "tiny-grid", SEED, 0.1)
    assert not out["correct"], out["checks"]


def _bench_run(cwd, env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "sim-grid-fig5-16",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = _bench_run(REPO, env)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = _bench_run(tmp_path, env)
    assert p.returncode != 0
    assert not p.stdout.strip().startswith("{")
    json.loads((tmp_path / "BENCHMARK.json").read_text())
