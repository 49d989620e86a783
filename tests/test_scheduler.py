"""Batch scheduler: policy semantics + the paper's claim at the
scheduler level (PPCC admits a superset of 2PL; OCC wastes work)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # dev-only dep (requirements-dev.txt)
from hypothesis import given, settings, strategies as st

from repro.core import ppcc
from repro.sched import scheduler, txstore


def rand_batch(seed, n=32, d=64, p_read=0.1, p_write=0.5):
    rng = np.random.default_rng(seed)
    reads = rng.random((n, d)) < p_read
    writes = reads & (rng.random((n, d)) < p_write)
    return jnp.array(reads), jnp.array(writes), jnp.ones(n, bool)


@pytest.mark.parametrize("seed", range(4))
def test_ppcc_admits_at_least_2pl(seed):
    r, w, v = rand_batch(seed)
    a_ppcc = scheduler.tick(r, w, v, policy="ppcc").admitted
    a_2pl = scheduler.tick(r, w, v, policy="2pl").admitted
    assert int(a_ppcc.sum()) >= int(a_2pl.sum())


@pytest.mark.parametrize("seed", range(4))
def test_2pl_admitted_set_conflict_free(seed):
    r, w, v = rand_batch(seed)
    res = scheduler.tick(r, w, v, policy="2pl")
    idx = np.where(np.asarray(res.admitted))[0]
    rn, wn = np.asarray(r), np.asarray(w)
    for i in idx:
        for j in idx:
            if i != j:
                assert not (rn[i] & wn[j]).any()
                assert not (wn[i] & wn[j]).any()


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.sampled_from([8, 16, 32]))
def test_ppcc_admitted_graph_invariants(seed, n):
    """Admitted set satisfies Theorem 1: acyclic, path length <= 1."""
    r, w, v = rand_batch(seed, n=n)
    res = scheduler.tick(r, w, v, policy="ppcc")
    s = res.state
    assert bool(ppcc.path_length_leq_one(s))
    assert bool(ppcc.acyclic(s))
    # commit order respects precedence: i -> j  =>  rank(i) < rank(j)
    prec = np.asarray(s.prec)
    rank = np.asarray(res.commit_rank)
    for i, j in zip(*np.where(prec)):
        assert rank[i] < rank[j], (i, j)


def test_occ_aborts_are_real_conflicts():
    r, w, v = rand_batch(0)
    res = scheduler.tick(r, w, v, policy="occ")
    rn, wn = np.asarray(r), np.asarray(w)
    surv = np.asarray(res.admitted)
    for i in np.where(np.asarray(res.aborted))[0]:
        earlier = [j for j in np.where(surv)[0] if j < i]
        assert any(((rn[i] & wn[j]) | (wn[i] & wn[j])).any()
                   for j in earlier)


def test_txstore_serializable_outcome():
    """Additive commits equal the sum of admitted payload writes."""
    r, w, v = rand_batch(3, n=16, d=32)
    n, d = 16, 32
    pay = jnp.array(np.random.default_rng(0).standard_normal((n, d, 4)),
                    jnp.float32)
    batch = txstore.TxBatch(read_sets=r, write_sets=w, payload=pay,
                            additive=jnp.ones(n, bool), valid=v)
    pages, reads, stats = txstore.apply_tick(jnp.zeros((d, 4)), batch,
                                             "ppcc")
    admitted = np.asarray(stats.admitted)
    expect = np.zeros((d, 4), np.float32)
    for i in np.where(admitted)[0]:
        expect[np.asarray(w)[i]] += np.asarray(pay)[i][np.asarray(w)[i]]
    np.testing.assert_allclose(np.asarray(pages), expect, atol=1e-5)


def test_scheduler_tick_carry_reuse_and_invalidation():
    """``tick`` threads carried conflict state — reusing it on unchanged
    inputs and recomputing (exactly) on changed ones."""
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
