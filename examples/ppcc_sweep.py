"""Vectorised parameter sweep of the tensorised simulator.

The paper's whole experiment suite as one SPMD computation: ``vmap``
over seeds (and protocols via python loop), shardable over the mesh's
data axis — the TPU-native replacement for running the event-heap
simulator hundreds of times (DESIGN.md §2).

    PYTHONPATH=src python examples/ppcc_sweep.py --seeds 4
"""
import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax
import numpy as np

from repro import compile_cache
from repro.core import jaxsim
from repro.core.types import SimParams


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=4)
    ap.add_argument("--horizon", type=float, default=5_000.0)
    ap.add_argument("--mpl", type=int, default=16)
    args = ap.parse_args()
    compile_cache.enable()

    for wp in (0.2, 0.5):
        p = SimParams(db_size=100, txn_size_mean=8, write_prob=wp,
                      mpl=args.mpl, horizon=args.horizon)
        row = [f"wp={wp}"]
        for proto in ("ppcc", "2pl", "occ"):
            t0 = time.time()
            out = jaxsim.simulate_sweep(p, proto, list(range(args.seeds)))
            commits = np.asarray(out["commits"])
            row.append(f"{proto}={commits.mean():.0f}"
                       f"±{commits.std():.0f} ({time.time() - t0:.1f}s)")
        print("  ".join(row))


if __name__ == "__main__":
    main()
