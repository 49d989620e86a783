"""The chip smoke script's phases at a tiny size on the CPU, with the
Pallas kernels in interpret mode: wrong paths, arguments and control
flow show up here before any chip time is spent.  The script itself
refuses to run without a TPU."""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro import compile_cache

ROOT = Path(__file__).resolve().parents[1]


def _load():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_phases_tiny_on_cpu(capsys):
    cs = _load()
    dev = cs.device_info()
    assert dev["platform"] == "cpu"
    got = cs.phase_grid(dev, figs=(6, 7), mpls=(5, 25, 50), seeds=(0,),
                        horizon=5_000.0)
    assert got["commits"] > 0 and got["warm_s"] > 0
    cs.phase_megakernel(dev, mpls=(4, 8), seeds=(0, 1), horizon=1_500.0)
    cs.phase_scheduler(dev, n=64, d=256, warm_ticks=2)
    out = capsys.readouterr().out
    assert "[cpu " in out and "oracle fig6 2pl" in out
    assert "megakernel == jnp twin" in out
    assert "tick ppcc_carry" in out


def test_smoke_four_chip_phase_on_virtual_devices():
    """The --four-chips comparison on four forced CPU host devices."""
    code = ("import chip_smoke as cs; dev = cs.device_info(); "
            "assert dev['count'] == 4, dev; "
            "cs.phase_four_chips(dev, figs=(6, 7), mpls=(5, 10), "
            "seeds=(0, 1), horizon=1_000.0)")
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=600, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 PYTHONPATH=str(ROOT / "src"),
                 XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert r.returncode == 0, r.stderr[-2000:]
    assert "bit-identical" in r.stdout, r.stdout


def test_smoke_refuses_without_tpu():
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       capture_output=True, text=True, timeout=120,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert jax.default_backend() == "cpu"


def test_compile_cache_placement(monkeypatch):
    """An outside ``JAX_COMPILATION_CACHE_DIR`` wins and nothing is set;
    without it the cache sits at a fixed, git-ignored checkout path."""
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/outside")
        assert compile_cache.enable() == "/placed/outside"
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        got = compile_cache.enable()
        assert Path(got) == ROOT / ".jax_cache"
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    ignore = (ROOT / ".gitignore").read_text().split()
    assert Path(got).name + "/" in ignore
