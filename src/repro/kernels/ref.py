"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, window: int = 0,
                        sm_scale: Optional[float] = None) -> jax.Array:
    """q [B, Hq, S, D]; k/v [B, Hkv, T, D] — plain softmax attention."""
    b, hq, s, d = q.shape
    hkv, t = k.shape[1], k.shape[2]
    g = hq // hkv
    if g > 1:
        k = jnp.repeat(k, g, axis=1)
        v = jnp.repeat(v, g, axis=1)
    sm_scale = sm_scale if sm_scale is not None else d ** -0.5
    s_ = jnp.einsum("bhsd,bhtd->bhst", q.astype(jnp.float32),
                    k.astype(jnp.float32)) * sm_scale
    qpos = jnp.arange(s)[:, None]
    kpos = jnp.arange(t)[None, :]
    mask = jnp.ones((s, t), bool)
    if causal:
        mask &= qpos >= kpos
    if window > 0:
        mask &= qpos - kpos < window
    s_ = jnp.where(mask[None, None], s_, -jnp.inf)
    w = jax.nn.softmax(s_, axis=-1)
    w = jnp.where(jnp.isnan(w), 0.0, w)          # fully-masked rows
    return jnp.einsum("bhst,bhtd->bhsd", w,
                      v.astype(jnp.float32)).astype(q.dtype)


def conflict_matrix_ref(read_bits: jax.Array, write_bits: jax.Array
                        ) -> jax.Array:
    """uint32[N, W] x uint32[N, W] -> bool[N, N]."""
    return ((read_bits[:, None, :] & write_bits[None, :, :]) != 0
            ).any(axis=-1)


def conflict_fused_ref(read_bits: jax.Array, write_bits: jax.Array):
    """Oracle for the fused one-pass kernel: (raw, ww, raw_deg, ww_deg).
    Degrees are per-row popcounts including the diagonal."""
    raw = conflict_matrix_ref(read_bits, write_bits)
    ww = conflict_matrix_ref(write_bits, write_bits)
    return (raw, ww, raw.sum(axis=1).astype(jnp.int32),
            ww.sum(axis=1).astype(jnp.int32))


def conflict_fused_full_ref(read_bits: jax.Array, write_bits: jax.Array):
    """Oracle for ``conflict_fused_full``: everything degree-ordered
    admission needs from ONE launch — (raw, ww, raw_deg, war_deg,
    ww_deg, diag_raw, diag_ww).  ``war_deg`` is the COLUMN sum of raw
    (who reads what I write); row/column degrees include the diagonal,
    the diag vectors let callers strip self-conflicts."""
    return _full(conflict_matrix_ref(read_bits, write_bits),
                 conflict_matrix_ref(write_bits, write_bits))


def _full(raw: jax.Array, ww: jax.Array):
    return (raw, ww, raw.sum(axis=1).astype(jnp.int32),
            raw.sum(axis=0).astype(jnp.int32),
            ww.sum(axis=1).astype(jnp.int32),
            jnp.diagonal(raw), jnp.diagonal(ww))


def conflict_keys_ref(read_keys: jax.Array, write_keys: jax.Array):
    """Oracle for ``conflict_keys``: the ``conflict_fused_full_ref``
    7-tuple of transactions given as key lists ``int32[N, k]``, where a
    negative id is a pad and matches nothing."""
    def overlap(a, b):
        eq = a[:, None, :, None] == b[None, :, None, :]
        real = (a >= 0)[:, None, :, None] & (b >= 0)[None, :, None, :]
        return (eq & real).any(axis=(2, 3))

    return _full(overlap(read_keys, write_keys),
                 overlap(write_keys, write_keys))


def ppcc_admit_scan_ref(raw: jax.Array, valid: jax.Array, seq: jax.Array,
                        *, rule: Callable):
    """Oracle for ``ppcc_admit_scan``: the Prudent Precedence Rule as a
    ``lax.scan``, one step per row in the order ``seq``, carrying only
    the three ``bool[n]`` class vectors.  ``raw`` is ``bool[n, n]`` with
    the diagonal off; ``rule(r_i, w_i, preceding, preceded)`` tests row
    i's admitted RAW arcs out and WAR arcs in (it may keep its reduced
    axes).  Returns ``(admitted, preceding, preceded)``."""
    n = raw.shape[0]

    def step(carry, i):
        admitted, preceding, preceded = carry
        r_i = raw[i] & admitted                      # i -> j arcs (RAW)
        w_i = raw[:, i] & admitted                   # k -> i arcs (WAR)
        ok = (valid[i] & rule(r_i, w_i, preceding, preceded)).reshape(())
        admitted = admitted.at[i].set(ok)
        preceding = preceding.at[i].set(ok & r_i.any()) | (w_i & ok)
        preceded = preceded.at[i].set(ok & w_i.any()) | (r_i & ok)
        return (admitted, preceding, preceded), ok

    init = (jnp.zeros(n, bool), jnp.zeros(n, bool), jnp.zeros(n, bool))
    out, _ = jax.lax.scan(step, init, seq)
    return out


def megastep_ref(read_bits: jax.Array, write_bits: jax.Array,
                 dirty_bits: jax.Array, item: jax.Array,
                 is_write: jax.Array, active: jax.Array, ready: jax.Array,
                 haslocks: jax.Array):
    """Oracle for the cohort-step megakernel (``kernels.megastep``):
    (dep, ww, writers_at, readers_at, deg, lockhit, dirty_hit) — the
    same relations ``ppcc.cohort_step_fused`` derives per quantum.
    ``item`` is slot i's pending op item; party/dependence semantics
    follow DESIGN.md §2.3."""
    n = read_bits.shape[0]
    eye = jnp.eye(n, dtype=bool)
    w_idx, b_idx = item >> 5, (item & 31).astype(jnp.uint32)
    # op tables: [i, k] = item_i present in {write,read}_set[k]
    writers_at = ((write_bits[:, w_idx] >> b_idx[None, :])
                  & jnp.uint32(1)).astype(bool).T
    readers_at = ((read_bits[:, w_idx] >> b_idx[None, :])
                  & jnp.uint32(1)).astype(bool).T
    others = jnp.where(is_write[:, None], readers_at, writers_at)
    party = (others & active[None, :] & ~eye) | eye
    dep = (party[:, None, :] & party[None, :, :]).any(axis=-1)
    same_item = item[:, None] == item[None, :]
    either_w = is_write[:, None] | is_write[None, :]
    dep = (dep | (same_item & either_w)) & ~eye
    deg = (dep & ready[None, :]).sum(axis=1).astype(jnp.int32)
    ww = conflict_matrix_ref(write_bits, write_bits) & ~eye
    lockhit = (ww & haslocks[None, :]).any(axis=1)
    dirty_hit = ((read_bits & dirty_bits) != 0).any(axis=-1)
    return dep, ww, writers_at, readers_at, deg, lockhit, dirty_hit


def wkv_ref(r: jax.Array, k: jax.Array, v: jax.Array, log_w: jax.Array,
            u: jax.Array, head_dim: int,
            state0: Optional[jax.Array] = None):
    """Sequential (step-by-step) WKV6 recurrence — the gold semantics.

    r/k/v [B, S, D] (D = H * head_dim), log_w [B, S, D] fp32, u [D].
    Returns (out [B, S, D] fp32, final_state [B, H, dk, dv] fp32).
    """
    b, s, d = r.shape
    h = d // head_dim
    rr = r.astype(jnp.float32).reshape(b, s, h, head_dim)
    kk = k.astype(jnp.float32).reshape(b, s, h, head_dim)
    vv = v.astype(jnp.float32).reshape(b, s, h, head_dim)
    ww = jnp.exp(log_w.astype(jnp.float32)).reshape(b, s, h, head_dim)
    uu = u.astype(jnp.float32).reshape(h, head_dim)
    state = (jnp.zeros((b, h, head_dim, head_dim), jnp.float32)
             if state0 is None else state0)

    def step(state, inp):
        rt, kt, vt, wt = inp                      # [b,h,k] / [b,h,v]
        kv = jnp.einsum("bhk,bhv->bhkv", kt, vt)
        out = jnp.einsum("bhk,bhkv->bhv", rt,
                         state + uu[None, :, :, None] * kv)
        state = wt[..., None] * state + kv
        return state, out

    inp = tuple(jnp.moveaxis(x, 1, 0) for x in (rr, kk, vv, ww))
    state, outs = jax.lax.scan(step, state, inp)
    return jnp.moveaxis(outs, 0, 1).reshape(b, s, d), state
