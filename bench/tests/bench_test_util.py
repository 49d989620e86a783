"""Helpers of the benchmark's CPU tests: a checkout root holding
``BENCHMARK.json`` plus data files of a cell the harness has never
seen, so that the harness is shown to find a new cell by its files."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
DATA = Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(REPO / "src"))

import run  # noqa: E402


def new_cell_root(tmp: Path, like: str, name: str, config: str,
                  traffic: dict, limits: dict) -> Path:
    """A root with a new cell ``name`` on the test configuration
    ``config`` (``data/<config>.json``), reporting the same metrics as
    the existing cell ``like``."""
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": config, "source": "test",
                             "file": f"bench/configs/{config}.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": name, "config": config,
                               "traffic": name, "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if like in m.get("workloads", []):
            m["workloads"].append(name)
    for sub in ("cells", "configs", "traffic"):
        (tmp / "bench" / sub).mkdir(parents=True, exist_ok=True)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    shutil.copy(DATA / f"{config}.json", tmp / "bench/configs")
    (tmp / "bench/traffic" / f"{name}.json").write_text(json.dumps(traffic))
    (tmp / "bench/cells" / f"{name}.json").write_text(json.dumps(
        {"config": config, "traffic": name, "chips": 1, "why": "test",
         "limits": limits}))
    return tmp


def run_cpu(monkeypatch, root: Path, name: str, seed: int,
            seconds: float) -> dict:
    """A whole run on the CPU: no look for a chip, no compile cache."""
    monkeypatch.setattr(run, "enable_cache", lambda jax: None)
    return run.run(name, seed, seconds, False, root=root, require_tpu=False)
