"""Cohort-step megakernel (DESIGN.md §3): bit-exactness of the Pallas
kernel against the ``ref.py`` oracle at tile edges, equality of the
megakernel relations path with the jnp single-pass twin inside
``ppcc.cohort_step_fused``, and the fused-full conflict kernel that
feeds degree-ordered admission."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bitset, ppcc
from repro.core.types import SimParams
from repro.kernels import megastep as MS
from repro.kernels import ops as kops
from repro.kernels import ref

I = jnp.int32


def _random_step_inputs(seed, n, d):
    """A warmed protocol state plus one quantum's op/phase vectors."""
    rng = np.random.default_rng(seed)
    s = ppcc.init_state(n, d)
    for i in range(n):
        s = ppcc.begin(s, I(i))
    for _ in range(3 * n):
        s, _ = ppcc.try_op(s, I(rng.integers(0, n)), I(rng.integers(0, d)),
                           jnp.bool_(rng.random() < 0.4))
    wc_mask = jnp.array(rng.random(n) < 0.3)
    s, _ = ppcc.wc_acquire_many(s, wc_mask, exact=False)
    item = jnp.array(rng.integers(0, d, n), I)
    is_w = jnp.array(rng.random(n) < 0.4)
    ready = jnp.array(rng.random(n) < 0.6) & ~wc_mask
    dirty = bitset.pack(jnp.array(rng.random((n, d)) < 0.1))
    return s, item, is_w, ready, wc_mask, dirty


# n and d deliberately NOT multiples of the (8, 128) tile or of the
# 32-bit word: the kernel holds whole arrays and relies on the packed
# zero-pad-bit invariant along the word axis.
EDGE_SHAPES = [(12, 30), (33, 100), (7, 31), (40, 64), (160, 500)]


@pytest.mark.parametrize("n,d", EDGE_SHAPES)
def test_megastep_matches_oracle_at_tile_edges(n, d):
    s, item, is_w, ready, wc_mask, dirty = _random_step_inputs(
        n * 7 + d, n, d)
    args = (s.read_set, s.write_set, dirty, item, is_w, s.active, ready,
            s.haslocks)
    got = MS.megastep(*args, interpret=True)
    want = ref.megastep_ref(*args)
    names = ("dep", "ww", "writers_at", "readers_at", "deg", "lockhit",
             "dirty_hit")
    for g, w, name in zip(got, want, names):
        np.testing.assert_array_equal(
            np.asarray(g), np.asarray(w),
            err_msg=f"{name} n={n} d={d}")


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("order", ["index", "degree"])
def test_fused_step_with_megakernel_relations_equals_jnp_twin(seed, order):
    """``cohort_step_fused(relations=megastep(...))`` — the engine's
    megakernel path — must be bit-identical to the inline jnp twin."""
    n, d = 24, 70
    s, item, is_w, ready, wc_mask, dirty = _random_step_inputs(seed, n, d)
    rel = MS.megastep(s.read_set, s.write_set, dirty, item, is_w,
                      s.active, ready, s.haslocks, interpret=True)[:6]
    a = ppcc.cohort_step_fused(s, item, is_w, ready, wc_mask, order=order)
    b = ppcc.cohort_step_fused(s, item, is_w, ready, wc_mask, order=order,
                               relations=rel)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("n,d", [(64, 200), (256, 1024), (96, 31)])
def test_conflict_fused_full_matches_ref(n, d):
    rng = np.random.default_rng(n + d)
    rb = bitset.pack(jnp.array(rng.random((n, d)) < 0.05))
    wb = bitset.pack(jnp.array(rng.random((n, d)) < 0.02))
    got = kops.conflict_fused_full(rb, wb, block=32)
    want = ref.conflict_fused_full_ref(rb, wb)
    names = ("raw", "ww", "raw_deg", "war_deg", "ww_deg", "diag_raw",
             "diag_ww")
    for g, w, name in zip(got, want, names):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)


def test_engine_megakernel_path_bit_identical():
    """Smoke the engine end to end with the megakernel supplying the
    relations: identical trajectory to the inline fused body."""
    from repro.core import jaxsim
    p = SimParams(db_size=100, txn_size_mean=8, write_prob=0.3, mpl=16,
                  horizon=2_000.0, seed=3)
    states = []
    for mk in (False, True):
        init, cond, step = jaxsim.engine_parts(
            p, "ppcc", step_mode="cohort", fused=True, megakernel=mk)
        s = init(0)
        it = 0
        while bool(cond(s)) and it < 1500:
            s = step(s)
            it += 1
        states.append((s, it))
    (s0, it0), (s1, it1) = states
    assert it0 == it1
    assert int(s0.commits) > 0
    for a, b in zip(jax.tree.leaves(s0), jax.tree.leaves(s1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_scheduler_degree_order_kernel_matches_ref():
    from repro.sched import scheduler
    rng = np.random.default_rng(9)
    rs = jnp.array(rng.random((64, 128)) < 0.1)
    ws = jnp.array(rng.random((64, 128)) < 0.05)
    v = jnp.ones(64, bool)
    a = scheduler.tick(rs, ws, v, policy="ppcc", order="degree")
    b = scheduler.ppcc_tick(rs, ws, v, use_kernel=False, order="degree")
    np.testing.assert_array_equal(np.asarray(a.admitted),
                                  np.asarray(b.admitted))
    np.testing.assert_array_equal(np.asarray(a.commit_rank),
                                  np.asarray(b.commit_rank))
    assert int(a.admitted.sum()) > 0
