"""Transactions as key lists: the ``conflict_keys`` kernel against its
jnp oracle, and ``tick(..., keys=True)`` against the same sets held as
dense masks, for every policy, both orders, the carry and
``tick_stats``; PPCC's tick against its form that carried ``prec``
through the scan; the admission kernel against its ``lax.scan``
oracle.  Interpret mode on the CPU, at small sizes."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import admit, ops, ref
from repro.sched import scheduler


def key_batch(seed, n, kr=8, kw=6, space=120, p_pad=0.2):
    """Key lists with duplicate keys across transactions, some pads in
    both lists and one row of pads only; ``int32[n, kr]``,
    ``int32[n, kw]``."""
    rng = np.random.default_rng(seed)
    rk = rng.integers(0, space, (n, kr)).astype(np.int32)
    wk = np.where(rng.random((n, kw)) < 0.5, rk[:, :kw],
                  rng.integers(0, space, (n, kw))).astype(np.int32)
    rk[rng.random((n, kr)) < p_pad] = -1
    wk[rng.random((n, kw)) < p_pad] = -7
    rk[n // 2], wk[n // 2] = -1, -1
    return rk, wk


def dense(keys, space=120):
    """bool[n, space]: the key lists as set masks (pads dropped)."""
    out = np.zeros((keys.shape[0], space), bool)
    for i, row in enumerate(keys):
        out[i, row[row >= 0]] = True
    return out


@pytest.mark.parametrize("n", [64, 512])
def test_conflict_keys_matches_oracle(n):
    rk, wk = key_batch(n, n)
    got = ops.conflict_keys(jnp.asarray(rk), jnp.asarray(wk))
    want = ref.conflict_keys_ref(jnp.asarray(rk), jnp.asarray(wk))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # the same relations as the dense fused kernel on the same sets
    rb = ops.pack_bitsets(jnp.asarray(dense(rk)))
    wb = ops.pack_bitsets(jnp.asarray(dense(wk)))
    for g, w in zip(got, ref.conflict_fused_full_ref(rb, wb)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert 0 < int(got[0].sum()) < n * n


def test_pads_match_nothing():
    """Two transactions of pads only, and pads against real keys: no
    conflict, no degree, no diagonal, whatever negative id pads."""
    rk = np.array([[-1, -1], [-1, -3], [5, -1], [-1, 5]], np.int32)
    wk = np.array([[-1, -1], [-1, -2], [-2, 5], [-1, -1]], np.int32)
    raw, ww, rdeg, war, wwdeg, dr, dw = (
        np.asarray(x) for x in ops.conflict_keys(jnp.asarray(rk),
                                                 jnp.asarray(wk)))
    expect_raw = np.zeros((4, 4), bool)
    expect_raw[2, 2] = expect_raw[3, 2] = True
    np.testing.assert_array_equal(raw, expect_raw)
    np.testing.assert_array_equal(ww, np.eye(4, dtype=bool) & (
        np.arange(4) == 2))
    np.testing.assert_array_equal(rdeg, [0, 0, 1, 1])
    np.testing.assert_array_equal(war, [0, 0, 2, 0])
    np.testing.assert_array_equal(wwdeg, [0, 0, 1, 0])
    np.testing.assert_array_equal(dr, [False, False, True, False])
    np.testing.assert_array_equal(dw, [False, False, True, False])


CASES = [("ppcc", "priority"), ("ppcc", "degree"), ("2pl", "priority"),
         ("occ", "priority")]


def same(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("policy,order", CASES)
@pytest.mark.parametrize("seed", [0, 1])
def test_keyed_tick_equals_dense_tick(policy, order, seed):
    n = 64
    rk, wk = key_batch(seed, n)
    valid = jnp.asarray(np.random.default_rng(seed).random(n) < 0.9)
    keyed = scheduler.tick(jnp.asarray(rk), jnp.asarray(wk), valid,
                           policy=policy, order=order, keys=True)
    plain = scheduler.tick(jnp.asarray(dense(rk)), jnp.asarray(dense(wk)),
                           valid, policy=policy, order=order)
    same(keyed, plain)
    assert 0 < int(keyed.admitted.sum()) < int(valid.sum())
    oracle = scheduler.POLICIES[policy]
    kw = {"order": order} if policy == "ppcc" else {}
    same(keyed, oracle(jnp.asarray(rk), jnp.asarray(wk), valid,
                       use_kernel=False, keys=True, **kw))


@pytest.mark.parametrize("order", ["priority", "degree"])
def test_keyed_carry_reuse_and_invalidation(order):
    n = 64
    rk, wk = (jnp.asarray(a) for a in key_batch(3, n))
    v = jnp.ones(n, bool)
    base = scheduler.tick(rk, wk, v, order=order, keys=True)
    res1, c1 = scheduler.tick(rk, wk, v, order=order, return_carry=True,
                              keys=True)
    res2 = scheduler.tick(rk, wk, v, order=order, carry=c1, keys=True)
    same(base, res1)
    same(base, res2)
    rk3 = rk.at[0].set(rk[5])
    fresh = scheduler.tick(rk3, wk, v, order=order, keys=True)
    same(fresh, scheduler.tick(rk3, wk, v, order=order, carry=c1,
                               keys=True))
    dense_res = scheduler.tick(jnp.asarray(dense(np.asarray(rk3))),
                               jnp.asarray(dense(np.asarray(wk))), v,
                               order=order)
    same(fresh, dense_res)


def test_keyed_tick_stats_equal_dense():
    n = 64
    rk, wk = key_batch(4, n)
    v = jnp.ones(n, bool)
    r, w = jnp.asarray(dense(rk)), jnp.asarray(dense(wk))
    res = scheduler.tick(r, w, v)
    keyed = scheduler.tick_stats(jnp.asarray(rk), jnp.asarray(wk), v, res,
                                 keys=True)
    assert keyed == scheduler.tick_stats(r, w, v, res)
    assert keyed["degree_max"] > 0


def test_keys_refuse_a_word_bucket():
    rk, wk = (jnp.asarray(a) for a in key_batch(0, 8))
    with pytest.raises(ValueError):
        scheduler.tick(rk, wk, jnp.ones(8, bool), words=4, keys=True)


def carry_form_ppcc(rs, ws, valid, order, keys):
    """PPCC's tick as it was before ``prec`` left the scan: the n x n
    matrix carried through every step, written by row and by column.
    ``(admitted, commit_rank, prec, preceding, preceded)``."""
    n = rs.shape[0]
    raw, _, raw_deg, war_deg, ww_deg, diag_raw, diag_ww = (
        scheduler._conflict_matrices(rs, ws, False, keys, full=True))
    if order == "degree":
        self_r = diag_raw.astype(jnp.int32)
        deg = (raw_deg - self_r + war_deg - self_r
               + ww_deg - diag_ww.astype(jnp.int32))
        seq = jnp.argsort(deg, stable=True).astype(jnp.int32)
    else:
        seq = jnp.arange(n, dtype=jnp.int32)
    raw = raw & ~jnp.eye(n, dtype=bool)

    def step(carry, i):
        admitted, preceding, preceded, prec = carry
        r_i = raw[i] & admitted
        w_i = raw[:, i] & admitted
        ok = (valid[i] & scheduler._prudent(r_i, w_i, preceding,
                                             preceded)).reshape(())
        admitted = admitted.at[i].set(ok)
        preceding = preceding.at[i].set(ok & r_i.any()) | (w_i & ok)
        preceded = preceded.at[i].set(ok & w_i.any()) | (r_i & ok)
        prec = prec.at[i, :].set(jnp.where(ok, r_i, prec[i, :]))
        prec = prec.at[:, i].set(jnp.where(ok, w_i, prec[:, i]))
        return (admitted, preceding, preceded, prec), ok

    init = (jnp.zeros(n, bool), jnp.zeros(n, bool), jnp.zeros(n, bool),
            jnp.zeros((n, n), bool))
    (admitted, preceding, preceded, prec), _ = jax.lax.scan(step, init, seq)
    rank_key = jnp.where(admitted, preceded.astype(jnp.int32), 2 ** 30)
    commit_order = jnp.argsort(rank_key, stable=True)
    rank = jnp.full((n,), -1, jnp.int32).at[commit_order].set(
        jnp.arange(n, dtype=jnp.int32))
    rank = jnp.where(admitted, rank, -1)
    return admitted, rank, prec, preceding, preceded


PREC_CASES = [  # order, set form, some rows invalid, through the carry
    ("priority", "bool", False, False),
    ("degree", "bool", False, False),
    ("priority", "keys", False, False),
    ("degree", "keys", False, False),
    ("priority", "bool", True, False),
    ("degree", "keys", True, False),
    ("priority", "keys", False, True),
    ("degree", "bool", True, True),
]


@pytest.mark.parametrize("order,form,invalid,carry", PREC_CASES)
def test_prec_after_scan_equals_carried_prec(order, form, invalid, carry):
    """``state.prec`` formed once after the scan equals the matrix the
    scan used to carry, bit for bit, and so do the admissions, the
    commit ranks and both class vectors."""
    n, keys = 64, form == "keys"
    rk, wk = key_batch(11 + invalid + 2 * carry, n)
    rs, ws = ((jnp.asarray(rk), jnp.asarray(wk)) if keys else
              (jnp.asarray(dense(rk)), jnp.asarray(dense(wk))))
    valid = jnp.asarray(np.random.default_rng(n).random(n) < 0.8
                        if invalid else np.ones(n, bool))
    want = carry_form_ppcc(*scheduler._operands(rs, ws, None, keys), valid,
                           order, keys)

    def tick(**kw):
        return scheduler.ppcc_tick(rs, ws, valid, use_kernel=False,
                                   order=order, keys=keys, **kw)

    results = [tick()]
    if carry:
        first, c = tick(return_carry=True)
        results += [first, tick(carry=c)]
    for res in results:
        s = res.state
        same((res.admitted, res.commit_rank, s.prec, s.preceding,
              s.preceded, s.active), want + (want[0],))
    assert bool(want[2].any()) and 0 < int(want[0].sum()) < int(valid.sum())


def scan_pair(n, permuted, invalid):
    """The admission kernel and its oracle on one random RAW relation
    (about three arcs a row, diagonal off), rows visited in index order
    or permuted, all rows valid or about a fifth not."""
    rng = np.random.default_rng([n, permuted, invalid])
    raw = rng.random((n, n)) < 3.0 / n
    np.fill_diagonal(raw, False)
    valid = rng.random(n) < (0.8 if invalid else 1.0)
    seq = rng.permutation(n) if permuted else np.arange(n)
    args = (jnp.asarray(raw), jnp.asarray(valid),
            jnp.asarray(seq, jnp.int32))
    got = ops.ppcc_admit_scan(*args, rule=scheduler._prudent)
    want = ref.ppcc_admit_scan_ref(*args, rule=scheduler._prudent)
    admitted, preceding, preceded = (np.asarray(x) for x in want)
    assert 0 < admitted.sum() < valid.sum()
    assert preceding.any() and preceded.any() and not admitted[~valid].any()
    return got, want


def tick_pair(order, form, invalid, carry):
    """``ppcc_tick`` with its kernels and with their oracles
    (``use_kernel`` on and off) on a ``PREC_CASES`` case: the plain
    tick, and with ``carry`` the tick that returns its carry and the
    tick that reuses it."""
    n, keys = 64, form == "keys"
    rk, wk = key_batch(11 + invalid + 2 * carry, n)
    rs, ws = ((jnp.asarray(rk), jnp.asarray(wk)) if keys else
              (jnp.asarray(dense(rk)), jnp.asarray(dense(wk))))
    valid = jnp.asarray(np.random.default_rng(n).random(n) < 0.8
                        if invalid else np.ones(n, bool))

    def ticks(use_kernel):
        def tick(**kw):
            return scheduler.ppcc_tick(rs, ws, valid, use_kernel=use_kernel,
                                       order=order, keys=keys, **kw)
        out = [tick()]
        if carry:
            first, c = tick(return_carry=True)
            out += [first, tick(carry=c)]
        return out

    return ticks(True), ticks(False)


ADMIT_CASES = [
    pytest.param(functools.partial(scan_pair, n, permuted, invalid),
                 id=f"scan-{n}-{'permuted' if permuted else 'index'}"
                    f"{'-invalid' if invalid else ''}")
    for n in (64, 200, 1024) for permuted in (False, True)
    for invalid in (False, True)
] + [
    pytest.param(functools.partial(tick_pair, *case),
                 id="tick-" + "-".join(map(str, case)))
    for case in PREC_CASES
]


@pytest.mark.parametrize("case", ADMIT_CASES)
def test_admission_kernel_equals_oracle(case):
    """``ppcc_admit_scan`` (interpret mode) against its ``lax.scan``
    oracle bit for bit: the kernel alone at n = 64, 200 (off the
    ``(8, 128)`` tiling) and 1 024, and inside ``ppcc_tick`` for both
    set forms, both orders, invalid rows and the carry."""
    got, want = case()
    same(got, want)


def test_admission_kernel_above_budget_keeps_the_scan(monkeypatch):
    """The one rule that picks the scan: a backlog whose tables exceed
    the kernel's VMEM budget runs the oracle, with the same result."""
    assert admit.fits(1024) and admit.fits(2048) and not admit.fits(2049)
    rk, wk = (jnp.asarray(a) for a in key_batch(5, 64))
    valid = jnp.ones(64, bool)

    def traced():
        fn = functools.partial(scheduler.ppcc_tick, keys=True)
        return str(jax.make_jaxpr(fn)(rk, wk, valid)), fn(rk, wk, valid)

    jaxpr, within = traced()
    assert "ppcc_admit_scan" in jaxpr
    monkeypatch.setattr(admit, "TABLE_BUDGET", 0)
    jaxpr, above = traced()
    assert "ppcc_admit_scan" not in jaxpr and "conflict_keys" in jaxpr
    same(within, above)
