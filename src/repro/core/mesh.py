"""Device meshes for fleet sweeps (DESIGN.md §2.4, §7).

``sweep.fleet_mesh`` shards a fleet's lanes over these meshes: the
single-host ``("data", "model")`` mesh, or the multi-host
``("pod", "data", "model")`` mesh after ``init_distributed``.  The
module imports JAX and numpy only.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

MODEL_AXIS = "model"


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def host_mesh(n_data: Optional[int] = None) -> Optional[Mesh]:
    """The standard ``("data", "model")`` mesh over the host's devices,
    with everything on the data axis — the shape fleet sweeps shard
    lanes over (DESIGN.md §2.4).  ``n_data`` caps the data-axis size;
    returns None when only one device is available (callers fall back
    to an unsharded vmap)."""
    devs = jax.devices()
    nd = len(devs) if n_data is None else min(n_data, len(devs))
    if nd <= 1:
        return None
    return Mesh(np.asarray(devs[:nd]).reshape(nd, 1), ("data", MODEL_AXIS))


def init_distributed(coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None, **kw) -> bool:
    """Idempotent ``jax.distributed.initialize`` wrapper.

    Multi-host fleets call this before building ``pod_mesh``; launchers
    that already initialized (or single-process runs that re-enter) get
    a no-op instead of the runtime's already-initialized error.  A
    single-process smoke exercises the full path with
    ``init_distributed("localhost:<port>", num_processes=1,
    process_id=0)``.  Returns True when this call performed the init.
    """
    if jax.distributed.is_initialized():
        return False
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id, **kw)
    return True


def pod_mesh(n_data: Optional[int] = None) -> Optional[Mesh]:
    """``("pod", "data", "model")`` mesh spanning every process's
    devices: the pod axis enumerates processes (hosts), the data axis
    each process's local devices — so a fleet's lane shard over
    ``("pod", "data")`` (see ``data_axes``) splits lanes first across
    hosts, then across the devices within each (DESIGN.md §7).

    Requires ``jax.distributed`` to be initialized for >1 process
    (``init_distributed``).  ``n_data`` caps the per-process data-axis
    size.  Returns None when the mesh would be a single device — except
    in the single-process case with an explicit ``n_data``, where the
    trivial ``pod=1`` mesh is still returned so the pod-axis code path
    can be exercised on one host.
    """
    devs = jax.devices()
    pods = jax.process_count()
    per = len(devs) // pods
    nd = per if n_data is None else min(n_data, per)
    if nd < 1:
        return None
    if pods * nd <= 1 and n_data is None:
        return None
    grid = np.asarray(devs).reshape(pods, per, 1)[:, :nd, :]
    return Mesh(grid, ("pod", "data", MODEL_AXIS))
