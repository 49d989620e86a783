"""Benchmark harness: one cell of BENCHMARK.json, one process, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name: the cell in ``bench/cells/<cell>.json``,
its configuration in the file ``BENCHMARK.json`` names, its traffic in
``bench/traffic/<traffic>.json`` (read by ``bench/traffic/<kind>.py``),
the driver of the configuration's surface in
``bench/drivers/<surface>.py`` and each per-layer metric's reader in
``bench/metrics/<metric>.py``.  A new cell, configuration or metric is
therefore new files only.

A run: check for the chips the cell asks for (none: exit 1, no result),
set up and warm every shape (``setup_s``), measure for ``--seconds``,
read the peak device memory, then check what the timed path produced
against the plain reference.  ``--trace 0`` prints the cell's
end-to-end metrics, ``--trace 1`` traces part of the window and prints
its per-layer metrics with the device's busy time and a breakdown.  The
last line of standard output is one JSON object; the numbers compared
for ``correct`` are also the last lines of standard error.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path

T_START = time.perf_counter()       # set-up is timed from here

HERE = Path(__file__).resolve().parent          # bench/
ROOT = HERE.parent                              # the checkout
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))


class BenchError(RuntimeError):
    """The run cannot produce a result."""


def load_module(path: Path):
    """Import a harness file by path (metric names carry dots)."""
    if not path.is_file():
        raise BenchError(f"missing {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: Path) -> dict:
    if not path.is_file():
        raise BenchError(f"missing {path}")
    return json.loads(path.read_text())


class Cell:
    """A cell with everything it names, resolved from data files under
    ``root`` (``BENCHMARK.json`` and ``bench/{cells,configs,traffic}``)."""

    def __init__(self, name: str, root: Path = ROOT):
        self.bench = read_json(root / "BENCHMARK.json")
        self.name = name
        self.spec = read_json(root / "bench" / "cells" / f"{name}.json")
        configs = {c["name"]: c for c in self.bench["configs"]}
        if self.spec["config"] not in configs:
            raise BenchError(f"config {self.spec['config']!r} is not in "
                             "BENCHMARK.json")
        self.config = read_json(root / configs[self.spec["config"]]["file"])
        self.traffic = read_json(
            root / "bench" / "traffic" / f"{self.spec['traffic']}.json")
        self.chips = int(self.spec["chips"])
        self.limits = self.spec["limits"]
        self.end_to_end = [m for m in self.bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        e2e = {m["name"] for m in self.end_to_end}
        self.per_layer = [
            m for m in self.bench["per_layer"]
            if (name in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]

    def driver(self):
        return load_module(HERE / "drivers" / f"{self.config['surface']}.py")

    def generator(self):
        return load_module(HERE / "traffic" / f"{self.traffic['kind']}.py")


def device_info(jax) -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(jax) -> int:
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def enable_cache(jax) -> str:
    """Persistent compile cache inside the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), holding every program."""
    from repro import compile_cache
    where = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return where


class Tracer:
    """Profiles the part of the window a driver marks with ``part()``
    (at most once per run; a no-op unless ``on``) and reduces the trace
    afterwards."""

    def __init__(self, jax, on: bool, out_dir: Path):
        self.jax, self.on, self.dir = jax, on, out_dir
        self.done = False

    @contextlib.contextmanager
    def part(self):
        if not self.on or self.done:
            yield
            return
        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True)
        opts = self.jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # host spans only, no calls
        self.jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        try:
            with self.jax.profiler.TraceAnnotation("bench.traced_window"):
                yield
        finally:
            self.jax.profiler.stop_trace()
            self.done = True

    def reduce(self, kernels: dict) -> dict | None:
        if not self.done:
            return None
        import trace_reduce
        files = sorted(self.dir.rglob("*.xplane.pb"))
        if not files:
            raise BenchError(f"no trace written under {self.dir}")
        try:
            return trace_reduce.reduce(files[-1], kernels)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def span(jax, name: str):
    """A host span in the profiler's trace (free when not tracing)."""
    return jax.profiler.TraceAnnotation(name)


def run(name: str, seed: int, seconds: float, trace: bool,
        root: Path = ROOT, require_tpu: bool = True) -> dict:
    """One run of cell ``name``; returns the result object.  Data files
    are found under ``root``; ``require_tpu=False`` (tests) skips the
    look for a chip."""
    cell = Cell(name, root)
    import jax
    dev = device_info(jax)
    if require_tpu and dev["platform"] != "tpu":
        raise BenchError(f"no TPU: JAX's first device is {dev['platform']}")
    if dev["count"] < cell.chips:
        raise BenchError(f"cell {name} needs {cell.chips} chips, "
                         f"found {dev['count']}")
    enable_cache(jax)
    drv = cell.driver()
    ctx = drv.setup(cell, cell.generator(), seed,
                    lambda n: span(jax, n))
    setup_s = time.perf_counter() - T_START
    tracer = Tracer(jax, trace, root / ".bench_trace" / name)
    rec = drv.window(ctx, seconds, tracer)
    dev["memory_peak_bytes"] = memory_peak(jax)
    checks = drv.check(ctx, rec, seed)
    rec["trace"] = tracer.reduce(drv.KERNELS) if trace else None
    return result(cell, dev, rec, setup_s, checks, trace)


def result(cell: Cell, dev: dict, rec: dict, setup_s: float,
           checks: list, trace: bool) -> dict:
    """Assemble the result line.  ``checks``: (name, value, limit)."""
    correct = all(v <= lim for _, v, lim in checks)
    out = {"correct": correct, "attempted": rec["attempted"],
           "failed": rec["failed"]}
    if trace:
        tr = rec["trace"]
        rec["peaks"] = peaks_for(dev["kind"])
        metrics = {}
        for m in cell.per_layer:
            reader = load_module(HERE / "metrics" / f"{m['name']}.py")
            v = reader.read(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        dev["busy_s"] = tr["busy_s"]
        dev["window_s"] = tr["window_s"]
        out["metrics"] = metrics
        out["breakdown"] = {"device_ops": tr["top_ops"],
                            "idle_gaps": tr["idle_gaps"]}
    else:
        metrics = {m["name"]: {"value": rec["e2e"][m["name"]],
                               "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] != "setup_s"}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        out["metrics"] = metrics
    out["device"] = dev
    out["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    return out


def peaks_for(kind: str) -> dict:
    table = read_json(HERE / "peaks.json")
    if kind not in table["devices"]:
        raise BenchError(f"no peaks for device kind {kind!r} in peaks.json")
    return table["devices"][kind]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    for n, c in out["checks"].items():
        print(f"check {n}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
