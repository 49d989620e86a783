"""Padded-lane fleet sweeps: one compiled executable for the paper grid.

The figure harness needs the full (protocol × MPL × seed) grid of
Table 1 (DESIGN.md §2.4).  Run per point, every point pays a fresh
trace + XLA compile because the slot count is baked into the trace
shape.  Here the slot axis is padded to a static bucket
(``slot_bucket``) and MPL becomes a *runtime* int32, so

* one ``jax.jit`` call compiles the whole grid exactly once
  (``Fleet.traces`` counts retraces — new MPL values or seeds of the
  same grid shape reuse the executable), and
* the (MPL × seed) lanes of each protocol ``vmap`` into one SPMD
  computation whose ``lax.while_loop`` runs while ANY lane is active
  (the batching rule freezes finished lanes via select).

The remaining workload axes are runtime scalars too
(``jaxsim.RtParams``: item count, write_prob, txn-length bounds,
resource-pool sizes), carried per lane — so lanes of DIFFERENT paper
figures ride the same executable as long as their shapes fit the
fleet's static buckets.  ``run_grid`` runs figs 5–16 as one launch this
way: the item axis pads to the ``db_size=500`` word bucket (pad bits
invariantly zero, §1.1), op lists to the ``max_ops=20`` bucket (pad
ops stay ``-1``), resource pools to 16/32 (``free_at=INF`` beyond the
live size) — each figure's lanes bit-identical to a per-figure fleet.

Protocol selection is a trace-time branch in the engine
(``EngCfg.protocol``), so the fleet stacks one vmapped sub-sweep per
protocol inside the single jitted call — still one executable, without
paying the run-all-protocols select a traced ``lax.switch`` would cost
under vmap.  Lane bodies use ``fleet=True`` engines: the
quiet-iteration ``lax.cond`` gates of the cohort body are dropped
because under vmap they decay into computing both branches plus a
full-state select.

Lane bodies stream the packed ``uint32[n, ceil(d/32)]`` set words of
``repro.core.bitset`` (DESIGN.md §1.1) — the fleet's dominant memory
traffic is the set arrays, and packing cuts it ~8x at the paper's
``db_size=500``.

Multi-device hosts shard the lane axis over the standard
``("data", "model")`` mesh (``repro.parallel.sharding.host_mesh``) via
``shard_map``: every device then runs its lane shard's while_loop
independently — lanes on different devices are not even in lockstep.
Multi-host runs extend the mesh with a leading pod axis
(``sharding.pod_mesh`` after ``sharding.init_distributed``); lanes
then shard over ``("pod", "data")`` — hosts first, local devices
second.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import bitset as B
from . import jaxsim
from .types import (GRID_FIGS, SimParams, grid_cover_params,
                    paper_figure_params)

PROTOCOLS = ("ppcc", "2pl", "occ")
METRICS = ("commits", "aborts", "blocks", "ops_done", "iters")


def slot_bucket(max_mpl: int, quantum: int = 32) -> int:
    """Pad the slot axis to a multiple of ``quantum`` so nearby grids
    (e.g. adding MPL=120 to the paper grid) hit the same executable.
    Same quantiser as the item-word and op axes (``bitset.bucket``)."""
    return B.bucket(max_mpl, quantum)


def fleet_mesh(n_lanes: int, pods: Optional[bool] = None):
    """Largest mesh whose lane axes divide ``n_lanes`` (shard_map needs
    an even lane split); None on single-device hosts.

    Single-process: the ``("data", "model")`` host mesh.  Multi-process
    (``jax.process_count() > 1``, after ``sharding.init_distributed``)
    — or ``pods=True`` to force the pod-axis path single-process — the
    ``("pod", "data", "model")`` mesh; lanes then shard over
    ``("pod", "data")``.
    """
    from ..parallel.sharding import host_mesh, pod_mesh
    if pods is None:
        pods = jax.process_count() > 1
    if pods:
        mesh = pod_mesh(n_data=1)
        if mesh is None:
            return None
        n_pods = mesh.shape["pod"]
        if n_lanes % n_pods:
            return None         # lanes must split evenly across hosts
        nd = len(jax.devices()) // n_pods
        while nd > 1 and n_lanes % (n_pods * nd):
            nd -= 1
        return pod_mesh(nd)
    mesh = host_mesh()
    if mesh is None:
        return None
    nd = mesh.shape["data"]
    while nd > 1 and n_lanes % nd:
        nd -= 1
    return host_mesh(nd) if nd > 1 else None


class Fleet:
    """One compiled executable for a (protocol × MPL × seed) grid.

    ``fleet(mpls, seeds)`` runs every lane of the grid and returns
    ``{protocol: {metric: int array[M, S]}}`` plus per-lane ``now``.
    MPL and seed are runtime values: any grid of the same (M, S) shape
    with ``max(mpls) <= n_slots`` reuses the executable (``traces``
    stays at 1).

    ``run_lanes(seeds, mpls, rts)`` is the general form: flat lane
    vectors plus per-lane ``jaxsim.RtParams``, so lanes of different
    paper figures share the executable (``run_grid`` builds the
    figs 5–16 grid this way).  ``p`` then only fixes the static
    buckets every lane's values must fit inside.

    ``fused=False`` runs the ppcc lanes through the legacy multipass
    cohort chain instead of ``ppcc.cohort_step_fused`` — bit-identical
    results, kept for the fused-vs-multipass benchmark comparison.
    ``delta=True`` carries the ppcc relation tables across iterations
    and updates only the dirty rows per quantum (DESIGN.md §3.2) —
    also bit-identical; the delta-vs-full benchmark compares the two.
    """

    def __init__(self, p: SimParams, protocols: Sequence[str] = PROTOCOLS,
                 n_slots: Optional[int] = None, max_iters: int = 400_000,
                 cohort_dt: Optional[float] = None, mesh=None,
                 pool: Optional[int] = None, fused: bool = True,
                 order: str = "index", delta: bool = False,
                 delta_k: int = 0, telemetry: bool = False,
                 trace_every: int = 0, trace_len: int = 256):
        if n_slots is None:
            n_slots = slot_bucket(p.mpl)
        if pool is None:
            # per-lane commits are bounded well under horizon/6 across
            # the paper grid (figs 13/15 peak ~6.8k per 100k horizon);
            # a wrapped pool would replay early-run workload, so size
            # it past the bound instead
            pool = max(4096, int(p.horizon) // 6)
        self.params = p
        self.protocols = tuple(protocols)
        self.n_slots = n_slots
        self.mesh = mesh
        self.telemetry = telemetry
        self.traces = 0
        parts = {
            proto: jaxsim.engine_parts(
                p, proto, max_iters=max_iters, cohort_dt=cohort_dt,
                n_slots=n_slots, fleet=True, pool=pool, fused=fused,
                order=order, delta=delta, delta_k=delta_k,
                telemetry=telemetry, trace_every=trace_every,
                trace_len=trace_len)
            for proto in self.protocols
        }

        def lane_runner(proto: str):
            init, cond, step = parts[proto]

            def run_one(seed, mpl, rt):
                with jax.named_scope("fleet.init"):
                    s0 = init(seed, mpl, rt)
                return jax.lax.while_loop(cond, step, s0)

            runner = jax.vmap(run_one)
            if mesh is not None:
                from jax.sharding import PartitionSpec as P
                from ..parallel.sharding import data_axes
                lane = P(data_axes(mesh))
                runner = jax.shard_map(
                    runner, mesh=mesh, in_specs=(lane, lane, lane),
                    out_specs=lane, check_vma=False)
            return runner

        runners = {proto: lane_runner(proto) for proto in self.protocols}

        def fleet_fn(seed_l, mpl_l, rt_l):
            self.traces += 1          # python side effect: counts traces
            out = {}
            for proto in self.protocols:
                with jax.named_scope(f"fleet.{proto}"):
                    fin = runners[proto](seed_l, mpl_l, rt_l)
                res = {k: getattr(fin, k) for k in METRICS}
                res["now"] = fin.now
                if self.telemetry:
                    # per-lane accumulator blocks (leading lane axis) —
                    # hosts aggregate with obs.metrics.summarize
                    res["telemetry"] = {
                        "lat_hist": fin.tm.lat_hist,
                        "wait_hist": fin.tm.wait_hist,
                        "restart_hist": fin.tm.restart_hist,
                        "abort_causes": fin.tm.abort_causes,
                        "block_causes": fin.tm.block_causes,
                        "trace": fin.tm.trace,
                    }
                out[proto] = res
            return out

        self._jit = jax.jit(fleet_fn)

    def run_lanes(self, seeds, mpls, rts: jaxsim.RtParams):
        """Run flat lane vectors: ``{protocol: {metric: array[L]}}``.

        ``rts`` leaves are per-lane ``[L]`` vectors; every lane's
        values must fit the fleet's static buckets (``check_rt``).
        Same lane count -> same executable (``traces`` proves it).

        Two host spans label a profiler trace: ``fleet.check`` (the
        bucket checks, which read values back from the device) and
        ``fleet.dispatch`` (the jitted call).  Inside the program each
        protocol's lanes run under the scope ``fleet.<protocol>`` and
        their set-up under ``fleet.init``; scopes change only the
        compiled program's ``op_name`` metadata.
        """
        seeds = jnp.asarray(seeds, jnp.int32)
        mpls = jnp.asarray(mpls, jnp.int32)
        with jax.profiler.TraceAnnotation("fleet.check"):
            if int(mpls.max()) > self.n_slots:
                raise ValueError(
                    f"max(mpls)={int(mpls.max())} exceeds "
                    f"n_slots={self.n_slots}")
            jaxsim.check_rt(self.params, rts)
        with jax.profiler.TraceAnnotation("fleet.dispatch"):
            return self._jit(seeds, mpls, rts)

    def __call__(self, mpls, seeds):
        mpls = np.asarray(mpls, np.int32)
        seeds = np.asarray(seeds, np.int32)
        m, s = mpls.shape[0], seeds.shape[0]
        rt = jaxsim.rt_of(self.params)
        rts = jax.tree.map(lambda x: jnp.broadcast_to(x, (m * s,)), rt)
        flat = self.run_lanes(np.tile(seeds, m), np.repeat(mpls, s), rts)
        # telemetry blocks carry trailing accumulator axes — fold only
        # the leading lane axis to (m, s)
        return {proto: jax.tree.map(
            lambda v: v.reshape((m, s) + v.shape[1:]), res)
            for proto, res in flat.items()}


def run_fleet(fig: int, mpl_grid: Sequence[int], seeds: Sequence[int],
              horizon: float, protocols: Sequence[str] = PROTOCOLS,
              n_slots: Optional[int] = None, max_iters: int = 400_000,
              shard: bool = True, fused: bool = True, delta: bool = False,
              telemetry: bool = False, trace_every: int = 0,
              trace_len: int = 256,
              ) -> Tuple[Dict[str, Dict[str, np.ndarray]], Fleet]:
    """Run one paper figure's full grid as a single compiled call.

    Returns ``({protocol: {metric: np.ndarray[M, S]}}, fleet)``; reuse
    the returned ``Fleet`` to re-run the same figure shape (different
    MPLs/seeds/horizons of the same grid shape) with zero recompiles.
    """
    p = paper_figure_params(fig).with_(horizon=horizon)
    if n_slots is None:
        n_slots = slot_bucket(max(mpl_grid))
    n_lanes = len(mpl_grid) * len(seeds)
    mesh = fleet_mesh(n_lanes) if shard else None
    fleet = Fleet(p, protocols=protocols, n_slots=n_slots,
                  max_iters=max_iters, mesh=mesh, fused=fused, delta=delta,
                  telemetry=telemetry, trace_every=trace_every,
                  trace_len=trace_len)
    out = fleet(list(mpl_grid), list(seeds))
    host = jax.tree.map(np.asarray, out)
    return host, fleet


def grid_lanes(figs: Sequence[int], mpl_grid: Sequence[int],
               seeds: Sequence[int]
               ) -> Tuple[jax.Array, jax.Array, jaxsim.RtParams]:
    """Flat (seed, mpl, rt) lane vectors for a figure × MPL × seed
    grid, figure-major (lane ``f*M*S + m*S + s`` is figure ``figs[f]``
    at ``mpl_grid[m]``, ``seeds[s]`` — reshape to ``[F, M, S]``)."""
    m, s = len(mpl_grid), len(seeds)
    rts = [jaxsim.rt_of(paper_figure_params(f)) for f in figs]
    rt_l = jax.tree.map(
        lambda *xs: jnp.repeat(jnp.stack(xs), m * s), *rts)
    mpl_l = jnp.tile(jnp.repeat(jnp.asarray(mpl_grid, jnp.int32), s),
                     len(figs))
    seed_l = jnp.tile(jnp.asarray(seeds, jnp.int32), len(figs) * m)
    return seed_l, mpl_l, rt_l


def run_grid(figs: Sequence[int] = GRID_FIGS,
             mpl_grid: Sequence[int] = (5, 10, 25, 50, 75, 100, 150),
             seeds: Sequence[int] = (0, 1), horizon: float = 20_000.0,
             protocols: Sequence[str] = PROTOCOLS,
             n_slots: Optional[int] = None, max_iters: int = 400_000,
             shard: bool = True, fused: bool = True, delta: bool = False,
             fleet: Optional[Fleet] = None, telemetry: bool = False,
             trace_every: int = 0, trace_len: int = 256,
             ) -> Tuple[Dict[int, Dict[str, Dict[str, np.ndarray]]],
                        Fleet]:
    """EVERY paper figure's grid in one compiled fleet launch.

    The fleet's static buckets cover all the figures
    (``grid_cover_params``: 500-item words, 20-op lists, 16/32
    resource pools); each figure contributes (MPL × seed) lanes whose
    per-lane ``RtParams`` carry its live values.  Returns
    ``({fig: {protocol: {metric: np.ndarray[M, S]}}}, fleet)`` — each
    figure's block bit-identical to ``run_fleet(fig, ...)`` at the
    same horizon.  Pass ``fleet`` (from a prior call with the same
    lane count) to reuse the executable.
    """
    figs = tuple(figs)
    n_lanes = len(figs) * len(mpl_grid) * len(seeds)
    if fleet is None:
        cover = grid_cover_params(figs).with_(horizon=horizon)
        if n_slots is None:
            n_slots = slot_bucket(max(mpl_grid))
        mesh = fleet_mesh(n_lanes) if shard else None
        fleet = Fleet(cover, protocols=protocols, n_slots=n_slots,
                      max_iters=max_iters, mesh=mesh, fused=fused,
                      delta=delta, telemetry=telemetry,
                      trace_every=trace_every, trace_len=trace_len)
    seed_l, mpl_l, rt_l = grid_lanes(figs, mpl_grid, seeds)
    flat = fleet.run_lanes(seed_l, mpl_l, rt_l)
    shape = (len(figs), len(mpl_grid), len(seeds))

    def fold(v, i):
        # fold the flat lane axis to [F, M, S] and take figure i; the
        # telemetry blocks keep their trailing accumulator axes
        a = np.asarray(v)
        return a.reshape(shape + a.shape[1:])[i]

    out = {
        fig: {proto: jax.tree.map(lambda v, i=i: fold(v, i), res)
              for proto, res in flat.items()}
        for i, fig in enumerate(figs)
    }
    return out, fleet
