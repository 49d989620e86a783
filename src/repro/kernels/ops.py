"""Jit'd public wrappers for the Pallas kernels.

On non-TPU backends (this container is CPU) the kernels execute in
``interpret=True`` mode — the kernel body runs op-by-op in Python on the
host, which validates correctness against the ``ref.py`` oracles.  On a
real TPU the same calls lower to Mosaic.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from . import admit as _admit
from . import conflict as _conflict
from . import flash_attention as _flash
from . import megastep as _megastep
from . import wkv as _wkv
from . import ref  # noqa: F401  (re-exported for tests/benchmarks)


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "window",
                                             "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 256, block_k: int = 256):
    """q [B, Hq, S, D]; k/v [B, Hkv, T, D]."""
    return _flash.flash_attention(
        q, k, v, causal=causal, window=window, block_q=block_q,
        block_k=block_k, interpret=_interpret_default())


@functools.partial(jax.jit, static_argnames=("block",))
def conflict_matrix(read_bits, write_bits, *, block: int = 256):
    return _conflict.conflict_matrix(
        read_bits, write_bits, block=block,
        interpret=_interpret_default())


@functools.partial(jax.jit, static_argnames=("block",))
def conflict_fused(read_bits, write_bits, *, block: int = 256):
    """One launch -> (raw, ww, raw_deg, ww_deg); see kernels.conflict."""
    return _conflict.conflict_fused(
        read_bits, write_bits, block=block,
        interpret=_interpret_default())


@functools.partial(jax.jit, static_argnames=("block",))
def conflict_fused_full(read_bits, write_bits, *, block: int = 256):
    """One launch -> (raw, ww, raw_deg, war_deg, ww_deg, diag_raw,
    diag_ww) — the degree-ordered admission tick's whole input."""
    return _conflict.conflict_fused_full(
        read_bits, write_bits, block=block,
        interpret=_interpret_default())


@functools.partial(jax.jit, static_argnames=("block",))
def conflict_keys(read_keys, write_keys, *, block: int = 256):
    """Key lists int32[N, k] (negative ids are pads) -> the
    ``conflict_fused_full`` 7-tuple; see kernels.conflict."""
    return _conflict.conflict_keys(
        read_keys, write_keys, block=block,
        interpret=_interpret_default())


@functools.partial(jax.jit, static_argnames=("rule",))
def ppcc_admit_scan(raw, valid, seq, *, rule):
    """PPCC's admission scan in one launch -> (admitted, preceding,
    preceded); see kernels.admit.  ``raw`` bool[n, n] (diagonal off),
    ``valid`` bool[n], ``seq`` the visiting order, ``rule`` the
    per-row admission test."""
    return _admit.admit_scan(raw, valid, seq, rule=rule,
                             interpret=_interpret_default())


@jax.jit
def megastep_relations(read_bits, write_bits, dirty_bits, item, is_write,
                       active, ready, haslocks):
    """Cohort-step megakernel: one launch -> (dep, ww, writers_at,
    readers_at, deg, lockhit, dirty_hit); see kernels.megastep.
    Compiled on the TPU, interpret mode elsewhere."""
    return _megastep.megastep(
        read_bits, write_bits, dirty_bits, item, is_write, active, ready,
        haslocks, interpret=_interpret_default())


# the protocol-wide packer (repro.core.bitset.pack), jitted; conflict
# re-exports it so the historical kernels import path keeps working
pack_bitsets = jax.jit(_conflict.pack_bitsets)


@functools.partial(jax.jit, static_argnames=("chunk",))
def wkv_chunked(r, k, v, log_w, u, *, chunk: int = 64):
    """r/k/v/log_w [B, H, S, D]; u [H, D]."""
    return _wkv.wkv_chunked(r, k, v, log_w, u, chunk=chunk,
                            interpret=_interpret_default())
