"""Serve a small model with batched requests, PPCC-scheduled admission.

Requests contend for shared KV-page slots (shared-prefix pages are
read-shared; per-request pages are written).  Each serving tick:

1. the PPCC batch scheduler admits a serializable subset of pending
   requests (2PL/OCC selectable for comparison — the paper's experiment
   at the serving layer),
2. admitted requests run one batched ``decode_step`` through the model,
3. their KV-page writes commit in precedence order.

    PYTHONPATH=src python examples/serve_batch.py --requests 24
"""
import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache, configs
from repro.models import LM
from repro.launch import steps as steps_mod
from repro.obs import metrics as obs_metrics
from repro.sched import scheduler


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--ticks", type=int, default=6)
    ap.add_argument("--policy", default="ppcc",
                    choices=["ppcc", "2pl", "occ"])
    ap.add_argument("--arch", default="qwen3_0p6b")
    args = ap.parse_args()
    compile_cache.enable()

    cfg = configs.get_smoke(args.arch)
    lm = LM(cfg)
    key = jax.random.PRNGKey(0)
    params = lm.init(key)
    serve = jax.jit(steps_mod.make_serve_step(cfg))

    n_req, n_pages = args.requests, 64
    rng = np.random.default_rng(0)
    # each request reads some shared-prefix pages and writes its own page
    shared = rng.random((n_req, n_pages)) < 0.1
    own = np.zeros((n_req, n_pages), bool)
    own[np.arange(n_req), rng.integers(0, n_pages, n_req)] = True
    reads = jnp.array(shared | own)
    writes = jnp.array(own | (shared & (rng.random(shared.shape) < 0.3)))

    seq = 32
    caches = lm.init_caches(n_req, seq)
    tokens = jax.random.randint(key, (n_req, 1), 0, cfg.vocab)
    pending = np.ones(n_req, bool)
    served = 0
    # obs-layer accounting: per-request commit latency in ticks (shared
    # log-spaced bins), abort causes, per-tick conflict-degree stats
    lat_hist = obs_metrics.HostHist()
    abort_causes = {c: 0 for c in obs_metrics.ABORT_CAUSES}
    for tick in range(args.ticks):
        if not pending.any():
            break
        res = scheduler.tick(reads, writes, jnp.array(pending),
                             policy=args.policy)
        stats = scheduler.tick_stats(reads, writes, jnp.array(pending),
                                     res)
        admitted = np.asarray(res.admitted)
        if admitted.any():
            logits, caches = serve(params, caches, tokens,
                                   jnp.int32(tick))
            tokens = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        for _ in range(int(admitted.sum())):
            lat_hist.add(tick + 1)        # commit latency in ticks
        # occ is the only tick policy that aborts (validation failure
        # at admission = the engine's read-phase validation cause)
        abort_causes["validate_read"] += stats["aborted"]
        served += int(admitted.sum())
        pending &= ~admitted
        print(f"tick {tick}: admitted={stats['admitted']:3d} "
              f"aborted={stats['aborted']:3d} "
              f"pending={int(pending.sum()):3d} "
              f"conflict degree max={stats['degree_max']} "
              f"mean={stats['degree_mean']:.1f}")
    pct = lat_hist.percentiles()
    causes = {c: v for c, v in abort_causes.items() if v}
    print(f"policy={args.policy} served={served}/{n_req} "
          f"in {tick + 1} ticks")
    print(f"commit latency (ticks): p50={pct['p50']:.1f} "
          f"p99={pct['p99']:.1f} over {lat_hist.count} commits; "
          f"abort causes: {causes or 'none'}")


if __name__ == "__main__":
    main()
