"""Cohort-stepped engine (DESIGN.md §2.3): batched-primitive exactness,
engine-level statistical parity with the event-heap oracle, and the
paper's Theorem-1 invariants after every cohort step."""
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
# FCFS reservation scan: min + one-hot select vs the gather/scatter form
# --------------------------------------------------------------------------

def _reserve_cohort_indexed(cpu_free, disk_free, t_req, cpu_dur, io_dur,
                            cpu_m, disk_m):
    """Reference scan with indexed pool access: read ``pool[argmin(pool)]``
    and write back with ``.at[i].set`` (a per-lane gather and scatter
    under ``vmap``)."""
    def step(carry, inp):
        cpu, disk = carry
        t, cd, dd, cm, dm = inp
        ci = jnp.argmin(cpu)
        cdone = jnp.maximum(t, cpu[ci]) + cd
        cpu2 = jnp.where(cm, cpu.at[ci].set(cdone), cpu)
        di = jnp.argmin(disk)
        ddone = jnp.maximum(t, disk[di]) + dd
        disk2 = jnp.where(dm, disk.at[di].set(ddone), disk)
        return (cpu2, disk2), (jnp.where(cm, cdone, jaxsim.INF),
                               jnp.where(dm, ddone, jaxsim.INF))

    (cpu_free, disk_free), (cpu_done, disk_done) = jax.lax.scan(
        step, (cpu_free, disk_free), (t_req, cpu_dur, io_dur, cpu_m,
                                      disk_m))
    return cpu_free, disk_free, cpu_done, disk_done


def _reserve_case(case, rng, lanes=32, n=160, c_pool=16, d_pool=32):
    """Vmapped inputs of ``_reserve_cohort`` for one named case."""
    c_live, d_live = (c_pool, d_pool) if case == "full" else (4, 8)
    cpu = np.full((lanes, c_pool), float(jaxsim.INF), np.float32)
    disk = np.full((lanes, d_pool), float(jaxsim.INF), np.float32)
    cpu[:, :c_live] = rng.uniform(0, 50, (lanes, c_live))
    disk[:, :d_live] = rng.uniform(0, 50, (lanes, d_live))
    t = np.sort(rng.uniform(0, 60, (lanes, n)), axis=1).astype(np.float32)
    cd = rng.uniform(1, 15, (lanes, n)).astype(np.float32)
    dd = rng.uniform(5, 45, (lanes, n)).astype(np.float32)
    if case == "ties":
        # several idle servers, and requests whose done times collide
        cpu[:, :c_live] = np.where(rng.random((lanes, c_live)) < 0.5,
                                   0.0, np.round(cpu[:, :c_live]))
        disk[:, :d_live] = np.where(rng.random((lanes, d_live)) < 0.5,
                                    0.0, np.round(disk[:, :d_live]))
        t = np.round(t / 10) * 10
        cd, dd = np.round(cd), np.round(dd)
    if case == "none":
        cm = dm = np.zeros((lanes, n), bool)
    elif case == "all":
        cm = dm = np.ones((lanes, n), bool)
    else:
        pick = rng.integers(0, 3, (lanes, n))      # 0 none, 1 cpu, 2 disk
        cm, dm = pick == 1, pick == 2
    return tuple(jnp.asarray(a) for a in (cpu, disk, t, cd, dd, cm, dm))


@pytest.mark.parametrize("case", ["padded", "full", "ties", "none", "all"])
def test_reserve_cohort_matches_indexed_scan(case):
    """``_reserve_cohort`` (min + one-hot select) equals the gather /
    scatter scan bit for bit on all four outputs, vmapped over lanes."""
    args = _reserve_case(case, np.random.default_rng(len(case)))
    got = jax.jit(jax.vmap(jaxsim._reserve_cohort))(*args)
    want = jax.jit(jax.vmap(_reserve_cohort_indexed))(*args)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    if case == "ties":
        # the case reaches both tie-breaks: equal free times before the
        # scan, equal done times out of it
        first2 = np.sort(np.asarray(args[0]), axis=1)[:, :2]
        assert (first2[:, 0] == first2[:, 1]).any()
        done = np.asarray(got[2])
        assert any(len(np.unique(r[r < 1e29])) < (r < 1e29).sum()
                   for r in done)


# --------------------------------------------------------------------------
# engine-level parity (the test_jaxsim_vs_pysim grid)
# --------------------------------------------------------------------------

GRID = SimParams(db_size=100, txn_size_mean=8, write_prob=0.2, mpl=16,
                 horizon=5_000.0, seed=0)


@pytest.mark.parametrize("protocol", ["ppcc", "2pl", "occ"])
def test_cohort_commits_in_pysim_family(protocol):
    """Same model, different batching and random streams: the mean
    commit and abort counts of the cohort engine over four seeds track
    the oracle's over the same four seeds (a single seed's draw swings
    2PL by more than the band)."""
    seeds = [0, 1, 2, 3]
    co = jaxsim.simulate_sweep(GRID, protocol, seeds)
    co_c = float(np.mean(co["commits"]))
    co_a = float(np.mean(co["aborts"]))
    ref = [pysim.simulate(GRID.with_(seed=s), protocol) for s in seeds]
    ref_c = float(np.mean([r.commits for r in ref]))
    ref_a = float(np.mean([r.aborts for r in ref]))
    assert co_c > 0
    assert 0.55 * ref_c <= co_c <= 1.6 * ref_c, (co_c, ref_c)
    # aborts are rarer; allow a wider band plus slack for tiny counts
    assert abs(co_a - ref_a) <= max(10, 0.8 * ref_a), (co_a, ref_a)


# --------------------------------------------------------------------------
# Theorem-1 invariants after every cohort step
# --------------------------------------------------------------------------

def test_invariants_hold_after_every_cohort_step():
    p = SimParams(db_size=50, txn_size_mean=8, write_prob=0.5, mpl=24,
                  horizon=1_500.0, seed=3)
    init, cond, step = jaxsim.engine_parts(p, "ppcc")
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
