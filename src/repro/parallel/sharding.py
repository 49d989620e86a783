"""Logical-axis sharding rules -> NamedSharding trees.

The production mesh is ``(data=16, model=16)`` per pod, with an optional
leading ``pod`` axis (multi-pod).  Conventions (DESIGN.md §7):

* batch shards over ``(pod, data)``; when global_batch < data size (the
  long_500k cell) sequence shards over ``data`` instead (SP),
* TP: head/FFN/vocab output dims shard over ``model``; the matching
  input dims of the following matmul shard over ``model`` too,
* FSDP (``cfg.fsdp``): the non-TP dim of every large weight additionally
  shards over ``(pod, data)``,
* MoE experts shard over ``model`` (EP),
* any dim that does not divide evenly by its axis replicates instead
  (guarded by ``_fits``) — e.g. hubert's 504-way vocab head.

Rules are name-based over the param-tree path, which keeps the model
code free of sharding annotations; ``param_specs`` works on a
``jax.eval_shape`` tree, so no arrays are materialised.
"""
from __future__ import annotations

import re
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.config import ModelConfig, ShapeSpec

MODEL_AXIS = "model"


def get_abstract_mesh():
    """The ambient (context) mesh as an ``AbstractMesh``
    (``jax.sharding.get_abstract_mesh``), or ``None`` when no mesh is
    ambient."""
    am = jax.sharding.get_abstract_mesh()
    if am is None or not am.axis_names:
        return None
    return am


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


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _fits(dim: int, mesh: Mesh, axes) -> bool:
    return dim % _axis_size(mesh, axes) == 0


def _spec(mesh: Mesh, shape: Tuple[int, ...], *axes) -> P:
    """Build a PartitionSpec, dropping any axis the dim doesn't divide by."""
    out = []
    for dim, ax in zip(shape, axes):
        out.append(ax if (ax is not None and _fits(dim, mesh, ax)) else None)
    return P(*out)


# --------------------------------------------------------------------------
# parameter rules
# --------------------------------------------------------------------------

def _param_rule(path: str, shape: Tuple[int, ...], mesh: Mesh,
                cfg: ModelConfig) -> P:
    """Sharding rule for one parameter leaf, identified by its tree path.

    Paths look like ``blocks/attn/wq``, ``moe_blocks/moe/wi_gate``,
    ``mamba_groups/ssm/wz`` etc.  Leading stack dims ([L] or [G, k]) are
    detected by rank and never sharded.
    """
    fsdp = data_axes(mesh) if cfg.fsdp else None
    name = path.split("/")[-1]
    # routed-expert weights only; the llama4 shared expert is a plain MLP
    is_moe = ("/moe/" in path or path.startswith("moe/")) \
        and "/shared/" not in path
    in_chan_mix = "/chan/" in path        # rwkv channel mixing

    def rule2(a0, a1):
        """Rule for the last two dims; leading stack dims replicate."""
        n_stack = len(shape) - 2
        return _spec(mesh, shape, *([None] * n_stack), a0, a1)

    def rule1(a0):
        n_stack = len(shape) - 1
        return _spec(mesh, shape, *([None] * n_stack), a0)

    # --- embeddings / heads ------------------------------------------------
    if name == "embed":
        return _spec(mesh, shape, MODEL_AXIS, fsdp)
    if name == "lm_head":
        return _spec(mesh, shape, fsdp, MODEL_AXIS)
    if name == "in_proj":                      # audio frontend adapter
        return _spec(mesh, shape, None, MODEL_AXIS)

    # --- MoE expert weights [E, d, f] / [E, f, d]: EP over model -----------
    if is_moe and name in ("wi_gate", "wi_up"):
        n_stack = len(shape) - 3
        return _spec(mesh, shape, *([None] * n_stack), MODEL_AXIS, fsdp,
                     None)
    if is_moe and name == "wo":
        n_stack = len(shape) - 3
        return _spec(mesh, shape, *([None] * n_stack), MODEL_AXIS, None,
                     fsdp)
    if name == "router":
        return rule2(None, None)

    # --- attention ----------------------------------------------------------
    if in_chan_mix and name == "wv":       # rwkv channel-mix down-proj [f, d]
        return rule2(MODEL_AXIS, fsdp)
    # NOTE: replicating the channel-mix gate (chan/wr) removes 57% of the
    # per-layer collectives on rwkv6 but XLA then keeps fp32 layer saves
    # alive (+42 GiB temp, exceeding HBM) — measured and REVERTED, see
    # EXPERIMENTS.md §Perf iteration log.
    if name in ("wq", "wk", "wv"):
        return rule2(fsdp, MODEL_AXIS)
    if name == "wo":                           # attn / mlp / rwkv out
        return rule2(MODEL_AXIS, fsdp)
    if name in ("wi_gate", "wi_up"):           # dense mlp
        return rule2(fsdp, MODEL_AXIS)

    # --- rwkv ---------------------------------------------------------------
    if name in ("wr", "wg"):
        return rule2(fsdp, MODEL_AXIS)
    if name in ("wB",):
        return rule2(None, MODEL_AXIS)
    if name in ("w0", "u", "ln_g"):
        return rule1(MODEL_AXIS)
    if name in ("wA", "mix_A", "mix_B", "mu_x", "mu_rkvwg", "mu_k", "mu_r"):
        return P(*([None] * len(shape)))

    # --- mamba2 -------------------------------------------------------------
    if name in ("wz", "wxs"):
        return rule2(fsdp, MODEL_AXIS)
    if name == "wdt":
        return rule2(None, MODEL_AXIS)
    if name == "out_proj":
        return rule2(MODEL_AXIS, fsdp)
    if name == "conv_xs":
        return rule2(None, MODEL_AXIS)
    if name in ("A_log", "D", "dt_bias"):
        return rule1(MODEL_AXIS)
    if name == "norm_g":
        return rule1(MODEL_AXIS)

    # --- everything else (norms, biases, gates, conv_BC, wBC) --------------
    return P(*([None] * len(shape)))


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
    return "/".join(parts)


def param_specs(cfg: ModelConfig, params_shapes: Any, mesh: Mesh) -> Any:
    """PartitionSpec tree matching a params shape tree (from eval_shape)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _param_rule(_path_str(path), leaf.shape, mesh,
                                       cfg),
        params_shapes)


def param_shardings(cfg: ModelConfig, params_shapes: Any, mesh: Mesh) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_specs(cfg, params_shapes, mesh),
                        is_leaf=lambda x: isinstance(x, P))


# --------------------------------------------------------------------------
# batch / cache rules
# --------------------------------------------------------------------------

def batch_specs(cfg: ModelConfig, shape_spec: ShapeSpec, mesh: Mesh,
                batch_shapes: Any) -> Any:
    """Input-batch PartitionSpecs.

    Batch dim shards over (pod, data) when divisible; otherwise (the
    long_500k single-sequence cell) the sequence dim shards over data.
    """
    dax = data_axes(mesh)
    bsz = shape_spec.global_batch
    seq_sharded = not _fits(bsz, mesh, dax)

    def rule(path, leaf):
        shape = leaf.shape
        if len(shape) == 0:
            return P()
        if seq_sharded:
            # [B, S, ...]: shard S over data if long enough
            if len(shape) >= 2 and shape[1] % _axis_size(mesh, dax) == 0:
                return P(None, dax, *([None] * (len(shape) - 2)))
            return P(*([None] * len(shape)))
        return _spec(mesh, shape, dax, *([None] * (len(shape) - 1)))

    return jax.tree_util.tree_map_with_path(rule, batch_shapes)


def cache_specs(cfg: ModelConfig, shape_spec: ShapeSpec, mesh: Mesh,
                cache_shapes: Any) -> Any:
    """Decode-cache PartitionSpecs.

    KV caches [L, B, S, H, Dh]: batch over (pod, data), heads over model.
    If batch doesn't divide (long_500k), shard the cache SEQUENCE over
    data instead (flash-decode style distributed KV).
    SSM states [L, B, H, P, N] / [G, k, B, H, P, N]: heads over model.
    RWKV states [L, B, H, dk, dv]: heads over model.
    """
    dax = data_axes(mesh)
    bsz = shape_spec.global_batch
    batch_ok = _fits(bsz, mesh, dax)

    def rule(path, leaf):
        shape = leaf.shape
        name = _path_str(path).split("/")[-1]
        nd = len(shape)
        if name in ("k", "v", "k_scale", "v_scale", "cross_k", "cross_v"):
            # [stack..., B, S, H, Dh] (scales have Dh == 1 -> replicated)
            lead = nd - 4
            b_ax = dax if batch_ok else None
            s_ax = None if batch_ok else dax
            return _spec(mesh, shape, *([None] * lead), b_ax, s_ax,
                         MODEL_AXIS, None)
        if name == "pos":
            return P(*([None] * nd))
        if name == "state":
            # [stack..., B, H, p, n] (mamba) / [stack..., B, H, dk, dv]
            lead = nd - 4
            b_ax = dax if batch_ok else None
            return _spec(mesh, shape, *([None] * lead), b_ax, MODEL_AXIS,
                         None, None)
        if name in ("conv", "shift_t", "shift_c"):
            # [stack..., B, w, C]: shard trailing channel dim over model
            lead = nd - 3
            b_ax = dax if batch_ok else None
            return _spec(mesh, shape, *([None] * lead), b_ax, None,
                         MODEL_AXIS)
        return P(*([None] * nd))

    return jax.tree_util.tree_map_with_path(rule, cache_shapes)


def to_shardings(mesh: Mesh, specs: Any) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


# --------------------------------------------------------------------------
# activation constraints (perf lever: stop GSPMD layout flip-flopping)
# --------------------------------------------------------------------------

def constrain_act(x: jax.Array, *, last_model: bool = False) -> jax.Array:
    """Pin an activation's canonical layout: batch over (pod, data),
    optionally the trailing feature dim over model.  No-ops when there is
    no ambient mesh (smoke tests) or when a dim does not divide."""
    am = get_abstract_mesh()
    if am is None or not am.axis_names or MODEL_AXIS not in am.axis_names:
        return x
    sizes = dict(zip(am.axis_names, am.axis_sizes))
    dax = tuple(a for a in ("pod", "data") if a in am.axis_names)
    dsz = 1
    for a in dax:
        dsz *= sizes[a]
    spec = [None] * x.ndim
    if x.shape[0] % dsz == 0 and x.shape[0] > 0:
        spec[0] = dax
    if last_model and x.shape[-1] % sizes[MODEL_AXIS] == 0:
        spec[-1] = MODEL_AXIS
    return jax.lax.with_sharding_constraint(x, P(*spec))
