"""Async elastic training with PPCC-scheduled commits.

Simulates K data-parallel replicas with heterogeneous step times
(stragglers).  Each replica's delayed gradient push is a *transaction*
over the parameter-shard pages it touches; per tick the PPCC scheduler
admits a serializable subset instead of (2PL ~) barriering on the
slowest replica or (OCC ~) hogwild-with-rollback:

    PYTHONPATH=src python examples/async_training.py --policy ppcc

Reported: wall-ticks to finish N total updates + final loss on a tiny
quadratic model (so convergence is measurable exactly).
"""
import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.sched import txstore
from repro.sched.txstore import TxBatch


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--policy", default="ppcc",
                    choices=["ppcc", "2pl", "occ"])
    ap.add_argument("--replicas", type=int, default=16)
    ap.add_argument("--pages", type=int, default=32)
    ap.add_argument("--updates", type=int, default=200)
    args = ap.parse_args()
    compile_cache.enable()

    rng = np.random.default_rng(0)
    k, pages, width = args.replicas, args.pages, 8
    # target: pages should converge to `target`
    target = jnp.array(rng.standard_normal((pages, width)), jnp.float32)
    store = jnp.zeros((pages, width))
    lr = 0.2

    # straggler model: replica i finishes a step every `period[i]` ticks
    period = rng.integers(1, 4, k)
    ready_at = period.copy()
    done = 0
    tick = 0
    aborted_work = 0
    while done < args.updates and tick < 10_000:
        tick += 1
        ready = ready_at <= tick
        if not ready.any():
            continue
        # each ready replica reads `r` pages and pushes grads to them
        reads = np.zeros((k, pages), bool)
        for i in np.where(ready)[0]:
            reads[i, rng.choice(pages, 4, replace=False)] = True
        writes = reads.copy()
        grads = np.zeros((k, pages, width), np.float32)
        err = np.asarray(target - store)
        for i in np.where(ready)[0]:
            grads[i][reads[i]] = lr * err[reads[i]] / 1.0
        batch = TxBatch(read_sets=jnp.array(reads),
                        write_sets=jnp.array(writes),
                        payload=jnp.array(grads),
                        additive=jnp.ones(k, bool),
                        valid=jnp.array(ready))
        store, _, stats = txstore.apply_tick(store, batch, args.policy)
        admitted = np.asarray(stats.admitted)
        aborted_work += int(np.asarray(stats.aborted).sum())
        done += int(admitted.sum())
        # admitted (and occ-aborted) replicas start their next step
        for i in np.where(ready)[0]:
            if admitted[i] or bool(np.asarray(stats.aborted)[i]):
                ready_at[i] = tick + period[i]
    loss = float(jnp.mean((store - target) ** 2))
    print(f"policy={args.policy} updates={done} ticks={tick} "
          f"aborted_work={aborted_work} final_mse={loss:.4f}")


if __name__ == "__main__":
    main()
