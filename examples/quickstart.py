"""Quickstart: the paper's protocol in three layers, in two minutes.

1. PPCC vs 2PL vs OCC on the paper's simulation model (Fig. 6 setting),
2. the tensorised protocol as a batch scheduler over a transactional
   page store,
3. a reduced-config LM train step + decode step through the same
   framework that the 512-chip dry-run lowers.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache

compile_cache.enable()

# --- 1. the paper: protocol comparison under high data contention -------
from repro.core.pysim import simulate
from repro.core.types import SimParams

print("=== 1. Paper reproduction (Fig. 6 setting, 20k time units) ===")
p = SimParams(db_size=100, txn_size_mean=8, write_prob=0.2,
              num_cpus=4, num_disks=8, mpl=50, horizon=20_000)
for proto in ("ppcc", "2pl", "occ"):
    r = simulate(p, proto)
    print(f"  {proto:5s} commits={r.commits:4d} aborts={r.aborts:4d} "
          f"blocks={r.blocks}")

# --- 2. PPCC as a batch scheduler over shared state ---------------------
from repro.sched import txstore
from repro.sched.txstore import TxBatch

print("=== 2. PPCC batch scheduler over a transactional page store ===")
rng = np.random.default_rng(0)
n, pages, width = 48, 64, 16
reads = jnp.array(rng.random((n, pages)) < 0.08)
writes = reads & jnp.array(rng.random((n, pages)) < 0.5)
batch = TxBatch(read_sets=reads, write_sets=writes,
                payload=jnp.ones((n, pages, width)),
                additive=jnp.ones(n, bool), valid=jnp.ones(n, bool))
store = jnp.zeros((pages, width))
for policy in ("ppcc", "2pl", "occ"):
    _, _, stats = txstore.apply_tick(store, batch, policy)
    print(f"  {policy:5s} admitted={int(stats.n_admitted):2d}/48 "
          f"aborted={int(stats.aborted.sum())}")

# --- 3. the model substrate the dry-run exercises -----------------------
from repro import configs
from repro.models import LM
from repro.launch import steps as steps_mod
from repro.optim import adamw

print("=== 3. Reduced-config LM: one train step + one decode step ===")
cfg = configs.get_smoke("llama3p2_1b")
lm = LM(cfg)
key = jax.random.PRNGKey(0)
params = lm.init(key)
tokens = jax.random.randint(key, (2, 32), 0, cfg.vocab)
train = jax.jit(steps_mod.make_train_step(
    cfg, adamw.AdamWConfig(peak_lr=1e-3, warmup_steps=1, total_steps=5)))
opt = adamw.init(params)
params, opt, metrics = train(params, opt,
                             {"tokens": tokens, "labels": tokens})
print(f"  train loss={float(metrics['loss']):.4f} "
      f"grad_norm={float(metrics['grad_norm']):.3f}")
caches = lm.init_caches(2, 32)
logits, caches = jax.jit(steps_mod.make_serve_step(cfg))(
    params, caches, tokens[:, :1], jnp.int32(0))
print(f"  decode logits shape={logits.shape} "
      f"finite={bool(jnp.isfinite(logits).all())}")
print("quickstart OK")
