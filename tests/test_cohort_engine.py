"""Cohort-stepped engine (DESIGN.md §2.3): batched-primitive exactness,
engine-level statistical parity with the one-event engine and the
event-heap oracle, and the paper's Theorem-1 invariants after every
cohort step."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import jaxsim, ppcc, pysim
from repro.core.types import SimParams

I = jnp.int32


def _state_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _warmed_state(rng, n=12, d=30, ops=25):
    s = ppcc.init_state(n, d)
    for i in range(n):
        s = ppcc.begin(s, I(i))
    for _ in range(int(rng.integers(0, ops))):
        s, _ = ppcc.try_op(s, I(rng.integers(0, n)),
                           I(rng.integers(0, d)),
                           jnp.bool_(rng.random() < 0.4))
    return s


# --------------------------------------------------------------------------
# batched primitives vs their sequential twins (property-style)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(8))
def test_try_ops_batched_matches_sequential_any_order(seed):
    """A cohort_select-ed set applied in ONE vectorized step must equal
    sequential try_op application in forward AND reverse order."""
    rng = np.random.default_rng(seed)
    n, d = 12, 30
    s = _warmed_state(rng, n, d)
    item = jnp.array(rng.integers(0, d, n), I)
    is_w = jnp.array(rng.random(n) < 0.4)
    ready = jnp.array(rng.random(n) < 0.8)
    sel = ppcc.cohort_select(s, item, is_w, ready)
    assert bool((sel <= ready).all())
    if bool(ready.any()):            # progress: first ready slot selected
        assert bool(sel[int(np.argmax(np.asarray(ready)))])
    sb, vb = ppcc.try_ops_batched(s, item, is_w, sel)
    for order in (range(n), reversed(range(n))):
        ss, vs = s, np.full(n, ppcc.BLOCK)
        for i in order:
            if bool(sel[i]):
                ss, v = ppcc.try_op(ss, I(i), item[i], is_w[i])
                vs[i] = int(v)
        _state_equal(sb, ss)
        np.testing.assert_array_equal(np.asarray(vb), vs)


@pytest.mark.parametrize("seed", range(4))
def test_wc_commit_begin_many_match_sequential(seed):
    rng = np.random.default_rng(100 + seed)
    n, d = 10, 20
    s = _warmed_state(rng, n, d, ops=30)
    mask = jnp.array(rng.random(n) < 0.5)
    sb, won = ppcc.wc_acquire_many(s, mask)          # exact greedy
    ss, wons = s, np.zeros(n, bool)
    for i in range(n):
        if bool(mask[i]):
            s2, got = ppcc.wc_acquire_locks(ss, I(i))
            if bool(got):
                ss = s2
            wons[i] = bool(got)
    np.testing.assert_array_equal(np.asarray(won), wons)
    _state_equal(sb, ss)
    # the vectorized relaxation only ever awards a subset of the greedy
    # winners, and a consistent one (disjoint write sets, feasible)
    _, won_fast = ppcc.wc_acquire_many(s, mask, exact=False)
    assert bool((won_fast <= won).all())
    cc = np.asarray(ppcc.can_commit_many(sb))
    for i in range(n):
        assert cc[i] == bool(ppcc.can_commit(sb, I(i)))
    cm = jnp.array(rng.random(n) < 0.4)
    sc = ppcc.commit_many(sb, cm)
    ss2 = sb
    for i in range(n):
        if bool(cm[i]):
            ss2 = ppcc.commit(ss2, I(i))
    _state_equal(sc, ss2)
    bm = jnp.array(rng.random(n) < 0.4)
    sg = ppcc.begin_many(sc, bm)
    ss3 = ss2
    for i in range(n):
        if bool(bm[i]):
            ss3 = ppcc.begin(ss3, I(i))
    _state_equal(sg, ss3)


@pytest.mark.parametrize("seed", range(3))
def test_admit_ops_blocked_bitwise_equals_admit_ops(seed):
    rng = np.random.default_rng(200 + seed)
    n, d, m = 16, 40, 100
    s = ppcc.init_state(n, d)
    for i in range(n):
        s = ppcc.begin(s, I(i))
    txn = jnp.array(rng.integers(0, n, m), I)
    item = jnp.array(rng.integers(0, d, m), I)
    wr = jnp.array(rng.random(m) < 0.3)
    valid = jnp.array(rng.random(m) < 0.9)
    a = ppcc.admit_ops(s, txn, item, wr, valid)
    b = ppcc.admit_ops_blocked(s, txn, item, wr, valid, block=16)
    np.testing.assert_array_equal(np.asarray(a.admitted),
                                  np.asarray(b.admitted))
    np.testing.assert_array_equal(np.asarray(a.blocked),
                                  np.asarray(b.blocked))
    np.testing.assert_array_equal(np.asarray(a.aborted),
                                  np.asarray(b.aborted))
    _state_equal(a.state, b.state)


@pytest.mark.parametrize("seed", range(3))
def test_admit_ops_blocked_degree_order_equals_permuted_admit_ops(seed):
    """``order="degree"`` is exactly ``admit_ops`` on the degree-sorted
    op list, with verdicts reported back in submission order."""
    rng = np.random.default_rng(300 + seed)
    n, d, m = 16, 40, 100
    s = ppcc.init_state(n, d)
    for i in range(n):
        s = ppcc.begin(s, I(i))
    txn = jnp.array(rng.integers(0, n, m), I)
    item = jnp.array(rng.integers(0, d, m), I)
    wr = jnp.array(rng.random(m) < 0.3)
    valid = jnp.array(rng.random(m) < 0.9)
    perm = ppcc.admit_order_degree(s, txn, item, wr, valid)
    pn = np.asarray(perm)
    assert sorted(pn.tolist()) == list(range(m))      # a permutation
    # per-transaction op order is preserved (rank is the primary key)
    tn = np.asarray(txn)
    for t in range(n):
        mine = pn[tn[pn] == t]
        assert (np.diff(mine) > 0).all() or mine.size <= 1
    a = ppcc.admit_ops(s, txn[perm], item[perm], wr[perm], valid[perm])
    b = ppcc.admit_ops_blocked(s, txn, item, wr, valid, block=16,
                               order="degree")
    np.testing.assert_array_equal(np.asarray(a.admitted),
                                  np.asarray(b.admitted)[pn])
    np.testing.assert_array_equal(np.asarray(a.blocked),
                                  np.asarray(b.blocked)[pn])
    np.testing.assert_array_equal(np.asarray(a.aborted),
                                  np.asarray(b.aborted)[pn])
    _state_equal(a.state, b.state)


@pytest.mark.parametrize("seed", range(4))
def test_cohort_step_fused_matches_multipass_substeps(seed):
    """One fused call == select -> try_ops_batched -> wc_acquire_many ->
    can_commit_many, bit for bit (order="index")."""
    rng = np.random.default_rng(400 + seed)
    n, d = 14, 36
    s = _warmed_state(rng, n, d, ops=40)
    wc_mask = jnp.array(rng.random(n) < 0.3)
    s, _ = ppcc.wc_acquire_many(s, wc_mask, exact=False)
    item = jnp.array(rng.integers(0, d, n), I)
    is_w = jnp.array(rng.random(n) < 0.4)
    ready = jnp.array(rng.random(n) < 0.7) & ~wc_mask
    fs = ppcc.cohort_step_fused(s, item, is_w, ready, wc_mask)
    sel = ppcc.cohort_select(s, item, is_w, ready)
    s1, verdict = ppcc.try_ops_batched(s, item, is_w, sel)
    s2, won = ppcc.wc_acquire_many(s1, wc_mask, exact=False)
    np.testing.assert_array_equal(np.asarray(fs.selected), np.asarray(sel))
    np.testing.assert_array_equal(np.asarray(fs.verdict),
                                  np.asarray(verdict))
    np.testing.assert_array_equal(np.asarray(fs.won), np.asarray(won))
    np.testing.assert_array_equal(np.asarray(fs.can_commit),
                                  np.asarray(ppcc.can_commit_many(s2)))
    _state_equal(fs.state, s2)


# --------------------------------------------------------------------------
# engine-level parity (the test_jaxsim_vs_pysim grid)
# --------------------------------------------------------------------------

GRID = SimParams(db_size=100, txn_size_mean=8, write_prob=0.2, mpl=16,
                 horizon=5_000.0, seed=0)


@pytest.mark.parametrize("protocol", ["ppcc", "2pl", "occ"])
def test_cohort_commits_aborts_match_event_engine(protocol):
    """Same model, different batching/RNG: mean commit and abort counts
    of the cohort engine over a few seeds must track the one-event
    engine's (a single seed's draw swings 2PL by more than the band)."""
    seeds = [0, 1, 2, 3]
    ev = jaxsim.simulate_sweep(GRID, protocol, seeds, step_mode="event")
    co = jaxsim.simulate_sweep(GRID, protocol, seeds, step_mode="cohort")
    ev_c, co_c = (float(np.mean(r["commits"])) for r in (ev, co))
    ev_a, co_a = (float(np.mean(r["aborts"])) for r in (ev, co))
    assert co_c > 0
    assert 0.7 * ev_c <= co_c <= 1.4 * ev_c, (co_c, ev_c)
    # aborts are rarer; allow a wider band plus slack for tiny counts
    assert abs(co_a - ev_a) <= max(10, 0.8 * ev_a), (co_a, ev_a)


@pytest.mark.parametrize("protocol", ["ppcc", "2pl", "occ"])
def test_cohort_commits_in_pysim_family(protocol):
    co = jaxsim.simulate(GRID, protocol, step_mode="cohort")
    ref = sum(pysim.simulate(GRID.with_(seed=s), protocol).commits
              for s in range(3)) / 3
    assert 0.55 * ref <= co.commits <= 1.6 * ref, (co.commits, ref)


def test_cohort_fewer_iterations_than_event():
    """The whole point: >= 3x fewer while_loop iterations."""
    p = GRID.with_(mpl=50, horizon=4_000.0)
    ev = jaxsim.make_engine(p, "ppcc", step_mode="event")(jnp.int32(0))
    co = jaxsim.make_engine(p, "ppcc", step_mode="cohort")(jnp.int32(0))
    assert int(co.iters) * 3 <= int(ev.iters), \
        (int(co.iters), int(ev.iters))


# --------------------------------------------------------------------------
# Theorem-1 invariants after every cohort step
# --------------------------------------------------------------------------

@pytest.mark.parametrize("fused", [True, False])
def test_invariants_hold_after_every_cohort_step(fused):
    p = SimParams(db_size=50, txn_size_mean=8, write_prob=0.5, mpl=24,
                  horizon=1_500.0, seed=3)
    init, cond, step = jaxsim.engine_parts(p, "ppcc", step_mode="cohort",
                                           fused=fused)
    s = init(0)
    steps = 0
    while bool(cond(s)) and steps < 400:
        s = step(s)
        steps += 1
        assert bool(ppcc.acyclic(s.pstate)), f"cycle after step {steps}"
        assert bool(ppcc.path_length_leq_one(s.pstate)), \
            f"path length 2 after step {steps}"
        assert bool(ppcc.classes_consistent(s.pstate)), \
            f"class bits inconsistent after step {steps}"
    assert steps > 50 and int(s.commits) > 0


@pytest.mark.parametrize("fleet", [False, True])
def test_fused_engine_bit_identical_to_multipass(fleet):
    """The fused cohort body (one ``cohort_step_fused`` call) must walk
    the exact same trajectory as the legacy multipass body
    (select -> try_ops -> wc -> commit as separate joins)."""
    p = SimParams(db_size=100, txn_size_mean=8, write_prob=0.3, mpl=16,
                  horizon=2_000.0, seed=7)
    states = []
    for fused in (True, False):
        init, cond, step = jaxsim.engine_parts(
            p, "ppcc", step_mode="cohort", fused=fused, fleet=fleet)
        s = init(0)
        it = 0
        while bool(cond(s)) and it < 1500:
            s = step(s)
            it += 1
        states.append((s, it))
    (sf, itf), (sm, itm) = states
    assert itf == itm
    assert int(sf.commits) > 0
    for a, b in zip(jax.tree.leaves(sf), jax.tree.leaves(sm)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
