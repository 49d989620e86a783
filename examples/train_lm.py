"""End-to-end driver: train a ~small LM for a few hundred steps with the
full production stack — sharded params, AdamW, deterministic data
pipeline, async checkpoints, restart-on-failure.

    PYTHONPATH=src python examples/train_lm.py --steps 200

(This is the assignment's (b) end-to-end driver; with --arch/--no-smoke
it trains any of the 10 assigned architectures on a real fleet.)
"""
import argparse
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3p2_1b")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()
    cmd = [sys.executable, "-m", "repro.launch.train",
           "--arch", args.arch, "--smoke",
           "--steps", str(args.steps), "--batch", "8", "--seq", "128",
           "--ckpt-dir", args.ckpt_dir, "--ckpt-every", "50"]
    env = {"PYTHONPATH": str(ROOT / "src"), "PATH": "/usr/bin:/bin"}
    import os
    env.update({k: v for k, v in os.environ.items()
                if k not in env})
    # the child compiles: hand it the checkout's persistent cache
    # unless the caller placed one
    from repro.compile_cache import CACHE_DIR
    env.setdefault("JAX_COMPILATION_CACHE_DIR", str(CACHE_DIR))
    raise SystemExit(subprocess.call(cmd, env=env))
