"""Delta-maintained conflict relations (DESIGN.md §3.2).

The dirty-row rule (``ppcc.dirty_slots``) + (K, n) row-slab kernel +
row-and-mirrored-column scatter must keep the loop-carried relation
tables bit-identical to a full O(n²·w) recompute — at the kernel level
(oracle / jnp twin / Pallas interpret trio), under arbitrary random
primitive sequences including slab overflow, and end-to-end at the
engine and fleet levels (``EngCfg.delta``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bitset, jaxsim, ppcc
from repro.core.types import SimParams
from repro.kernels import conflict as KC
from repro.kernels import megastep as MS
from repro.kernels import ref

I = jnp.int32


def _warm_state(seed, n, d):
    """A warmed protocol state plus an op cursor (like the megastep
    tests' ``_random_step_inputs``)."""
    rng = np.random.default_rng(seed)
    s = ppcc.init_state(n, d)
    s = ppcc.begin_many(s, jnp.ones(n, bool))
    for _ in range(3 * n):
        s, _ = ppcc.try_op(s, I(rng.integers(0, n)), I(rng.integers(0, d)),
                           jnp.bool_(rng.random() < 0.4))
    s, _ = ppcc.wc_acquire_many(s, jnp.array(rng.random(n) < 0.3),
                                exact=False)
    item = jnp.array(rng.integers(0, d, n), I)
    is_w = jnp.array(rng.random(n) < 0.4)
    return s, item, is_w, rng


# n deliberately off the tile width; K at and off the lane quantum
EDGE_SHAPES = [(12, 30, 4), (33, 100, 8), (7, 31, 4), (40, 64, 16)]


@pytest.mark.parametrize("n,d,k", EDGE_SHAPES)
def test_rowslab_trio_bit_identical(n, d, k):
    """ref oracle == jnp twin == Pallas kernel (interpret), on carried
    tables that are STALE for the slab rows (the real call pattern),
    with invalid slab padding included."""
    s, item, is_w, rng = _warm_state(n * 3 + d, n, d)
    # carried tables: full recompute at an older cursor
    old_item = jnp.array(rng.integers(0, d, n), I)
    old_w = jnp.array(rng.random(n) < 0.4)
    rel = ppcc.compute_relations(s, old_item, old_w)
    nk = rng.integers(1, k + 1)
    slab = jnp.asarray(np.sort(rng.choice(n, size=nk, replace=False)), I)
    slab = jnp.concatenate([slab, jnp.full((k - nk,), n, I)])
    valid = slab < n
    args = (s.read_set, s.write_set, rel.writers_at, rel.readers_at,
            item, is_w, s.active, slab, valid)
    want = ref.rowslab_ref(*args)
    twin = KC.rowslab(*args)
    pallas = MS.rowslab(*args, interpret=True)
    names = ("dep_rows", "ww_rows", "wat_rows", "rat_rows")
    for w_, t_, p_, name in zip(want, twin, pallas, names):
        np.testing.assert_array_equal(np.asarray(t_), np.asarray(w_),
                                      err_msg=f"twin {name} n={n} k={k}")
        np.testing.assert_array_equal(np.asarray(p_), np.asarray(w_),
                                      err_msg=f"pallas {name} n={n} k={k}")


def _mutate(rng, s, n, d):
    """One random batch of protocol primitives (the engine's per-step
    state transitions, in random combination)."""
    c = rng.integers(0, 4)
    if c == 0:
        item = jnp.array(rng.integers(0, d, n), I)
        is_w = jnp.array(rng.random(n) < 0.4)
        sel = ppcc.cohort_select(s, item, is_w,
                                 jnp.array(rng.random(n) < 0.5) & s.active)
        s, _ = ppcc.try_ops_batched(s, item, is_w, sel)
    elif c == 1:
        s, _ = ppcc.wc_acquire_many(s, jnp.array(rng.random(n) < 0.2)
                                    & s.active, exact=False)
    elif c == 2:
        gone = jnp.array(rng.random(n) < 0.15) & s.active
        s = ppcc.commit_many(s, gone & ppcc.can_commit_many(s))
        s = ppcc.abort_many(s, gone & ~ppcc.can_commit_many(s))
        s = ppcc.begin_many(s, gone & (jnp.arange(n) % 2 == 0))
    else:
        s = ppcc.begin_many(s, jnp.array(rng.random(n) < 0.1) & ~s.active)
    return s


@pytest.mark.parametrize("n,d,k", [(33, 100, 8), (16, 40, 4)])
@pytest.mark.parametrize("seed", range(2))
def test_delta_property_random_sequences(n, d, k, seed):
    """Single-slab maintenance with the cond-style overflow fallback
    (the non-fleet engine path): bit-identical to full recompute after
    every step of an arbitrary admit/commit/abort sequence.  A forced
    mass-commit step guarantees the overflow branch is exercised."""
    rng = np.random.default_rng(seed)
    s, item, is_w, _ = _warm_state(seed + n, n, d)
    rel = ppcc.compute_relations(s, item, is_w)
    overflows = 0
    for t in range(40):
        if t == 15:
            # mass leave: dirties well over k rows at once
            gone = jnp.array(rng.random(n) < 0.7) & s.active
            s2 = ppcc.abort_many(s, gone)
            s2 = ppcc.begin_many(s2, gone)
        else:
            s2 = _mutate(rng, s, n, d)
        move = jnp.array(rng.random(n) < 0.3)
        item2 = jnp.where(move, jnp.array(rng.integers(0, d, n), I), item)
        is_w2 = jnp.where(move, jnp.array(rng.random(n) < 0.4), is_w)
        dirty = ppcc.dirty_slots(s, s2, item, item2, is_w, is_w2)
        slab, valid, cnt = ppcc.dirty_slab(dirty, k)
        if int(cnt) > k:
            overflows += 1
            rel = ppcc.compute_relations(s2, item2, is_w2)
        else:
            rows = KC.rowslab(s2.read_set, s2.write_set, rel.writers_at,
                              rel.readers_at, item2, is_w2, s2.active,
                              slab, valid)
            rel = ppcc.scatter_relations(rel, *rows, slab, valid)
        want = ppcc.compute_relations(s2, item2, is_w2)
        for got, exp, name in zip(rel, want, ppcc.Relations._fields):
            np.testing.assert_array_equal(
                np.asarray(got), np.asarray(exp),
                err_msg=f"{name} diverged at step {t} (cnt={int(cnt)})")
        s, item, is_w = s2, item2, is_w2
    assert overflows >= 1, "overflow fallback never exercised"


@pytest.mark.parametrize("seed", range(2))
def test_delta_property_chunked_drain(seed):
    """The fleet-path variant: no overflow fallback — ALL dirty ids are
    drained K at a time; later chunks' mirrored column writes repair the
    stale dirty×dirty cross entries, so the result is still exact."""
    n, d, k = 24, 60, 4
    rng = np.random.default_rng(seed + 77)
    s, item, is_w, _ = _warm_state(seed, n, d)
    rel = ppcc.compute_relations(s, item, is_w)
    max_chunks = 0
    for t in range(30):
        s2 = _mutate(rng, s, n, d)
        move = jnp.array(rng.random(n) < 0.4)
        item2 = jnp.where(move, jnp.array(rng.integers(0, d, n), I), item)
        is_w2 = jnp.where(move, jnp.array(rng.random(n) < 0.4), is_w)
        dirty = ppcc.dirty_slots(s, s2, item, item2, is_w, is_w2)
        ids = np.flatnonzero(np.asarray(dirty))
        max_chunks = max(max_chunks, -(-len(ids) // k))
        for c in range(0, len(ids), k):
            chunk = ids[c:c + k]
            slab = jnp.asarray(np.concatenate(
                [chunk, np.full(k - len(chunk), n)]), I)
            valid = slab < n
            rows = KC.rowslab(s2.read_set, s2.write_set, rel.writers_at,
                              rel.readers_at, item2, is_w2, s2.active,
                              slab, valid)
            rel = ppcc.scatter_relations(rel, *rows, slab, valid)
        want = ppcc.compute_relations(s2, item2, is_w2)
        for got, exp, name in zip(rel, want, ppcc.Relations._fields):
            np.testing.assert_array_equal(
                np.asarray(got), np.asarray(exp),
                err_msg=f"{name} diverged at step {t}")
        s, item, is_w = s2, item2, is_w2
    assert max_chunks >= 2, "multi-chunk repair never exercised"


@pytest.mark.parametrize("protocol", ["ppcc", "2pl", "occ"])
def test_engine_delta_bit_identical(protocol):
    """``EngCfg.delta=True`` must not change a single engine metric or
    state leaf, for every protocol (non-ppcc engines carry no tables
    and must be untouched)."""
    p = SimParams(db_size=100, txn_size_mean=8, write_prob=0.3, mpl=14,
                  horizon=1_500.0, seed=5)
    base = jaxsim.make_padded_engine(p, protocol, n_slots=16)(
        jnp.int32(2), 14)
    for delta_k in (0, 4):
        dlt = jaxsim.make_padded_engine(p, protocol, n_slots=16,
                                        delta=True, delta_k=delta_k)(
            jnp.int32(2), 14)
        assert int(base.commits) > 0
        for a, b in zip(jax.tree.leaves(base._replace(rel=dlt.rel)),
                        jax.tree.leaves(dlt)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("delta_k", [0, 4])
def test_fleet_delta_bit_identical(delta_k):
    """Fleet bodies (vmap lanes, chunked while_loop drain) — with
    ``delta_k=4`` the drain needs several chunks per commit step, the
    vmap-safe analogue of the overflow fallback."""
    p = SimParams(db_size=100, txn_size_mean=8, write_prob=0.3, mpl=14,
                  horizon=1_500.0, seed=5)
    base = jaxsim.make_padded_engine(p, "ppcc", n_slots=16, fleet=True,
                                     pool=256)(jnp.int32(2), 14)
    dlt = jaxsim.make_padded_engine(p, "ppcc", n_slots=16, fleet=True,
                                    pool=256, delta=True,
                                    delta_k=delta_k)(jnp.int32(2), 14)
    assert int(base.commits) > 0
    for a, b in zip(jax.tree.leaves(base._replace(rel=dlt.rel)),
                    jax.tree.leaves(dlt)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_scheduler_tick_carry_reuse_and_invalidation():
    """Satellite: ``tick`` threads carried conflict state — reusing it
    on unchanged inputs and recomputing (exactly) on changed ones."""
    from repro.sched import scheduler
    rng = np.random.default_rng(0)
    n, d = 24, 64
    r = jnp.asarray(rng.random((n, d)) < 0.1)
    w = jnp.asarray(rng.random((n, d)) < 0.04) & r
    v = jnp.asarray(rng.random(n) < 0.9)
    for order in ("priority", "degree"):
        base = scheduler.tick(r, w, v, policy="ppcc", order=order)
        res1, c1 = scheduler.tick(r, w, v, policy="ppcc", order=order,
                                  return_carry=True)
        res2 = scheduler.tick(r, w, v, policy="ppcc", order=order,
                              carry=c1)
        for a, b, c in zip(jax.tree.leaves(base), jax.tree.leaves(res1),
                           jax.tree.leaves(res2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
        # changed words: the carry must be invalidated, not reused
        r3 = r.at[0].set(~r[0])
        fresh = scheduler.tick(r3, w, v, policy="ppcc", order=order)
        res3 = scheduler.tick(r3, w, v, policy="ppcc", order=order,
                              carry=c1)
        for a, b in zip(jax.tree.leaves(fresh), jax.tree.leaves(res3)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError):
        scheduler.tick(r, w, v, policy="2pl", return_carry=True)
