"""ShapeDtypeStruct input stand-ins for every (arch x shape) cell.

``input_specs`` returns (args, in_specs) where args is a pytree of
ShapeDtypeStructs for the step function and in_specs the matching
PartitionSpec tree — weak-type-correct, shardable, zero allocation.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..core.mesh import data_axes
from ..models import LM
from ..models.config import (ALL_SHAPES, ModelConfig, ShapeSpec)
from ..parallel import sharding as shd

SDS = jax.ShapeDtypeStruct


def shape_by_name(name: str) -> ShapeSpec:
    for s in ALL_SHAPES:
        if s.name == name:
            return s
    raise KeyError(name)


def train_batch_specs(cfg: ModelConfig, sp: ShapeSpec
                      ) -> Dict[str, jax.ShapeDtypeStruct]:
    b, s = sp.global_batch, sp.seq_len
    batch = {
        "tokens": SDS((b, s), jnp.int32),
        "labels": SDS((b, s), jnp.int32),
    }
    if cfg.family == "audio":
        batch["frames"] = SDS((b, s, cfg.d_model), jnp.bfloat16)
        del batch["tokens"]
    if cfg.family == "vlm":
        batch["img"] = SDS((b, cfg.n_img_tokens, cfg.d_model), jnp.bfloat16)
    return batch


def decode_args(cfg: ModelConfig, sp: ShapeSpec) -> Tuple[Any, Any, Any]:
    """(caches, token, pos) ShapeDtypeStructs for serve_step."""
    lm = LM(cfg)
    caches = jax.eval_shape(
        lambda: lm.init_caches(sp.global_batch, sp.seq_len))
    token = SDS((sp.global_batch, 1), jnp.int32)
    pos = SDS((), jnp.int32)
    return caches, token, pos


def cell_specs(cfg: ModelConfig, shape_name: str, mesh: Mesh
               ) -> Tuple[Tuple, Tuple]:
    """Returns (args, in_specs) for the step function of this cell.

    train/prefill cells: args = (batch,); decode cells: args =
    (caches, token, pos).  Params/opt-state specs are handled separately
    by the launchers.
    """
    sp = shape_by_name(shape_name)
    if sp.kind == "train" or sp.kind == "prefill":
        batch = train_batch_specs(cfg, sp)
        specs = shd.batch_specs(cfg, sp, mesh, batch)
        return (batch,), (specs,)
    caches, token, pos = decode_args(cfg, sp)
    cache_sp = shd.cache_specs(cfg, sp, mesh, caches)
    dax = data_axes(mesh)
    tok_sp = (P(dax, None)
              if sp.global_batch % shd._axis_size(mesh, dax) == 0
              else P(None, None))
    return (caches, token, pos), (cache_sp, tok_sp, P())
