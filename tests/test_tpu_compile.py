"""Compile the main path's Pallas kernels for a v5e chip that is
described, not attached (the TPU compiler ships with jaxlib).

Interpret mode hides what Mosaic refuses — block shapes off the (8, 128)
tiling, in-kernel gathers, dynamic slices of values, selects on bools —
so each kernel is lowered here with ``interpret=False`` at the size the
main path runs it: the cohort-step megakernel at the fleet's 160 slots
x 16 words, the scheduler's conflict kernels at 256 transactions x 32
words and at a 1 024-row backlog (16 words, or 16-key lists), and
PPCC's whole tick on those key lists, with the admission-scan kernel
``ppcc_admit_scan``.  Nothing runs; a refusal raises.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library at a time, and every test
worker imports this file.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import jaxsim
from repro.kernels import conflict as KC
from repro.kernels import megastep as MS
from repro.kernels import ops as kops
from repro.sched import scheduler, tpcc, txstore

N_SLOTS, SLOT_WORDS, LANES = 160, 16, 4
N_TXN, TXN_WORDS = 256, 32
BACKLOG, KEYS = 1024, 16


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to a persistent cache
    # but cannot be read back without one: keep it out of any cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    """Lower ``fn`` for the described chip; return the compiled HLO."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


U32, I32, BOOL, F32 = jnp.uint32, jnp.int32, jnp.bool_, jnp.float32
FLEET_LANES, CPU_POOL, DISK_POOL = 168, 16, 32


def _computations(hlo):
    """Map each computation's name in HLO text to its instruction lines."""
    comps, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) \(", line)
        if head and line.rstrip().endswith("{"):
            name = head.group(1)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)
    return comps


def _called(comps, root):
    """Lines of ``root`` and of every computation it calls, transitively."""
    seen, todo, lines = set(), [root], []
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        for line in comps[name]:
            lines.append(line)
            todo += [c for c in re.findall(r"%([\w.\-]+)", line)
                     if c in comps]
    return lines


def test_megastep_compiles_vmapped_over_lanes(one_chip):
    n, w, lanes = N_SLOTS, SLOT_WORDS, LANES
    fn = jax.vmap(lambda *a: MS.megastep(*a, interpret=False))
    hlo = _compile(fn, one_chip, *([((lanes, n, w), U32)] * 3),
                   ((lanes, n), I32), *([((lanes, n), BOOL)] * 4))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("kernel", ["conflict_fused",
                                    "conflict_fused_full"])
def test_conflict_kernels_compile(one_chip, kernel):
    fn = lambda r, w: getattr(KC, kernel)(r, w, interpret=False)  # noqa: E731
    shape = ((N_TXN, TXN_WORDS), U32)
    hlo = _compile(fn, one_chip, shape, shape)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("kernel,dtype", [("conflict_fused", U32),
                                          ("conflict_fused_full", U32),
                                          ("conflict_keys", I32)])
def test_conflict_kernels_compile_past_one_block(one_chip, kernel, dtype):
    """A 4 x 4 grid of 256-row blocks: the per-row degree outputs are
    ``(256, 1)`` blocks of ``int32[N, 1]`` (a ``(256,)`` block clashes
    with XLA's T(1024) layout of an ``int32[1024]``).  The keyed
    kernel's custom call carries its name, which the benchmark's trace
    reduction finds."""
    fn = lambda r, w: getattr(KC, kernel)(r, w, interpret=False)  # noqa: E731
    shape = ((BACKLOG, KEYS), dtype)
    hlo = _compile(fn, one_chip, shape, shape)
    calls = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    assert calls
    if kernel == "conflict_keys":
        assert all(ln.strip().startswith("%conflict_keys") for ln in calls)


def test_reserve_scan_body_has_no_gather_or_scatter(one_chip):
    """The fleet's FCFS reservation scan (``_reserve_cohort`` vmapped
    over the grid's lanes) reads and writes its pools with ``min`` and
    a one-hot select: a per-lane gather or scatter in the loop body is
    applied one row at a time on the TPU, every step."""
    lanes, n = FLEET_LANES, N_SLOTS
    hlo = _compile(jax.vmap(jaxsim._reserve_cohort), one_chip,
                   ((lanes, CPU_POOL), F32), ((lanes, DISK_POOL), F32),
                   *([((lanes, n), F32)] * 3), *([((lanes, n), BOOL)] * 2))
    comps = _computations(hlo)
    bodies = re.findall(r" while\(.*?body=%([\w.\-]+)", hlo)
    assert bodies, "the scan compiled to no while loop"
    for body in bodies:
        ops = " ".join(_called(comps, body))
        assert "scatter(" not in ops and "gather(" not in ops, body


def test_tick_scan_body_has_no_square_copy_or_update(one_chip,
                                                     monkeypatch):
    """PPCC's admission scan (``ppcc_tick`` on 16-key lists over a
    1 024-row backlog, the kernels compiled as on the chip) is one
    ``ppcc_admit_scan`` launch: under ``tick.scan`` the kernel's custom
    call is there, no XLA ``while`` is left (a loop of about ten ops a
    step), and no n x n ``pred`` is copied or updated in place (an
    n x n carry written by row and by column is relaid out and updated
    whole, every step)."""
    monkeypatch.setattr(kops, "_interpret_default", lambda: False)
    n = BACKLOG
    fn = lambda r, w, v: scheduler.ppcc_tick(r, w, v, keys=True)  # noqa: E731
    # the jitted kernel wrappers keep their traces by shape: drop those
    # an interpret-mode test made, and those made here for the chip
    jax.clear_caches()
    try:
        hlo = _compile(fn, one_chip, ((n, KEYS), I32), ((n, KEYS), I32),
                       ((n,), BOOL))
    finally:
        jax.clear_caches()
    scan = [ln for ln in hlo.splitlines() if "tick.scan" in ln]
    calls = [ln for ln in scan if "tpu_custom_call" in ln]
    assert calls, "no kernel under tick.scan"
    assert all(ln.strip().startswith("%ppcc_admit_scan") for ln in calls)
    assert not [ln for ln in scan if " while(" in ln]
    square = f"pred[{n},{n}]"
    bad = [ln for ln in scan if square in ln
           and (" copy(" in ln or " dynamic-update-slice(" in ln)]
    assert not bad, bad


def test_keyed_pass_compiles_on_ragged_lists(one_chip):
    """TPC-C's key lists: 33 read keys, 16 write keys, 1 024 rows."""
    fn = lambda r, w: KC.conflict_keys(r, w, interpret=False)  # noqa: E731
    hlo = _compile(fn, one_chip, ((BACKLOG, tpcc.READ_KEYS), I32),
                   ((BACKLOG, tpcc.WRITE_KEYS), I32))
    calls = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    assert calls and all(ln.strip().startswith("%conflict_keys")
                         for ln in calls)


def test_tpcc_apply_relays_out_no_wide_column(one_chip):
    """The keyed store's TPC-C apply at 4 warehouses, the store donated
    as the benchmark's driver donates it: no copy or transpose of a
    table's or log's byte column of 128 bytes or more (a gathered byte
    column laid out transposed is relaid out whole, every tick), and
    little scratch beside the store."""
    scale = tpcc.Scale(4)
    small, _ = tpcc.populate(tpcc.Scale(1, 10, 30, 100), 1)
    rows = scale.rows()

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    tables = {t: {c: spec((rows[t],) + v.shape[1:], v.dtype)
                  for c, v in cols.items()} for t, cols in small.items()}
    cap = tpcc.log_capacity(2**20, 0.5)
    logs = {t: {c: spec((cap[t],) + tuple(shape), dtype)
                for c, (shape, dtype) in cols.items()}
            for t, cols in tpcc.LOG_COLUMNS.items()}
    store = txstore.KeyedStore(tables, logs, {t: spec((), I32)
                                              for t in tpcc.LOG_COLUMNS})
    txn = {f: spec((BACKLOG, tpcc.MAX_LINES) if f in ("i_id", "supply_w",
                                                     "qty")
                   else (BACKLOG,), I32) for f in tpcc.TXN_FIELDS}
    fn = jax.jit(functools.partial(tpcc.apply_tick, scale=scale),
                 donate_argnums=0)
    compiled = fn.lower(store, txn, spec((BACKLOG,), BOOL),
                        spec((BACKLOG,), I32), spec((), I32)).compile()
    wide = [f"u8[{rows[t]},{v.shape[1]}" for t, cols in small.items()
            for v in cols.values() if v.dtype == jnp.uint8
            and v.ndim == 2 and v.shape[1] >= 128]
    assert wide
    bad = [ln for ln in compiled.as_text().splitlines()
           if any(w in ln for w in wide)
           and re.search(r" (copy|copy-start|transpose)\(", ln)]
    assert not bad, bad[:3]
    assert compiled.memory_analysis().temp_size_in_bytes < 2**28
