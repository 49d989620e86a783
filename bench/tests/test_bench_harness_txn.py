"""The harness end to end on the CPU, execution surface, at a tiny size:
a ``txn`` cell it has never seen runs and is correct; planted faults in
the admission or the store make ``correct`` false; a stream or a log
that runs out fails the run; and the TPC-C generator's NURand draws
and mix shares are within sampling error of the spec's."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_test_util import BENCH, new_cell_root, run, run_cpu

TRAFFIC = {"kind": "tpcc_backlog", "backlog": 64, "stream_txns": 20000,
           "burn_in_ticks": 20, "check_ticks": 3}
LIMITS = {"admitted_mismatches": 0, "commit_rank_mismatches": 0,
          "order_violations": 0, "store_mismatches": 0}
SEED = 2**31 + 2003


def root_for(tmp_path, traffic=TRAFFIC):
    return new_cell_root(tmp_path, "tick-tpcc-no-pay", "tiny-tpcc",
                         "tiny-tpcc", traffic, LIMITS)


def test_new_txn_cell_runs_and_is_correct(monkeypatch, tmp_path):
    out = run_cpu(monkeypatch, root_for(tmp_path), "tiny-tpcc", SEED, 0.2)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0
    assert out["attempted"] >= 3 * 64 and out["attempted"] % 64 == 0
    assert out["metrics"]["sim_commits_per_s"]["value"] > 0
    assert out["metrics"]["setup_s"]["value"] > 0
    assert set(out["checks"]) == set(LIMITS)


def _fresh_tick(mp):
    """A jit of its own for ``scheduler.tick``, so that patched helpers
    are traced; the module's ``tick`` keeps its cache."""
    from repro.sched import scheduler
    mp.setattr(scheduler, "tick", jax.jit(
        functools.partial(scheduler.tick.__wrapped__),
        static_argnames=("policy", "order", "words", "return_carry",
                         "keys")))


def reads_after_writes(mp):
    """The store reads the tables after the tick's writes, not the
    snapshot before them (every transaction sees its own writes)."""
    from repro.sched import txstore
    orig = txstore.apply_keyed

    def faulty(store, rows, rank, logic):
        first, _, _ = orig(store, rows, rank, logic)
        return orig(store._replace(tables=first.tables), rows, rank, logic)
    mp.setattr(txstore, "apply_keyed", faulty)


def reverse_commit_order(mp):
    """Writes and inserts land in reverse commit order."""
    from repro.sched import txstore
    orig = txstore.apply_keyed

    def faulty(store, rows, rank, logic):
        back = jnp.where(rank >= 0, rank.shape[0] - 1 - rank, -1)
        return orig(store, rows, back, logic)
    mp.setattr(txstore, "apply_keyed", faulty)


def no_both_ways_test(mp):
    """PPCC without the rule's first test: a transaction that would
    both precede and follow admitted ones is admitted (two
    read-modify-writes of one district)."""
    from repro.kernels import admit
    from repro.sched import scheduler
    any_ = admit.any_kept
    mp.setattr(scheduler, "_prudent",
               lambda r_i, w_i, preceding, preceded:
               ~any_(r_i & preceding) & ~any_(w_i & preceded))
    _fresh_tick(mp)


def dropped_stock_update(mp):
    """Each NewOrder's first order line leaves its stock row as it was."""
    from repro.sched import tpcc
    orig = tpcc.logic

    def faulty(*args):
        writes, inserts, outputs = orig(*args)
        rows, new = writes["stock"]
        return (dict(writes, stock=(rows.at[:, 0].set(-1), new)), inserts,
                outputs)
    mp.setattr(tpcc, "logic", faulty)


@pytest.mark.parametrize("fault", [reads_after_writes, reverse_commit_order,
                                   no_both_ways_test, dropped_stock_update],
                         ids=lambda f: f.__name__)
def test_txn_fault_is_not_correct(monkeypatch, tmp_path, fault):
    """A window too short for more ticks than are compared: the check
    takes the loop's first tick and the window's first three."""
    fault(monkeypatch)
    out = run_cpu(monkeypatch, root_for(tmp_path), "tiny-tpcc", SEED,
                  1e-6)
    assert out["attempted"] == 3 * 64
    assert not out["correct"], out["checks"]
    assert out["failed"] > 0


def test_class_tests_never_decide_in_tpcc(monkeypatch, tmp_path):
    """PPCC without its two class tests admits the same TPC-C backlogs:
    every arc between admissible NewOrder and Payment pairs runs from a
    NewOrder (it reads W_TAX or a customer) to a Payment (it writes
    them), every other shared key is read-modify-written on both sides,
    so no path of two arcs can form and the rule's first test decides
    alone.  The tiny YCSB cell is where that fault shows."""
    from repro.kernels import admit
    from repro.sched import scheduler
    any_ = admit.any_kept
    monkeypatch.setattr(scheduler, "_prudent",
                        lambda r_i, w_i, preceding, preceded:
                        ~(any_(r_i) & any_(w_i)))
    _fresh_tick(monkeypatch)
    out = run_cpu(monkeypatch, root_for(tmp_path), "tiny-tpcc", SEED, 1e-6)
    assert out["correct"], out["checks"]


def test_stream_that_runs_out_fails_the_run(monkeypatch, tmp_path):
    short = dict(TRAFFIC, stream_txns=200)
    with pytest.raises(RuntimeError, match="ran out"):
        run_cpu(monkeypatch, root_for(tmp_path, short), "tiny-tpcc", SEED,
                30.0)


def test_log_that_runs_out_fails_the_run(monkeypatch, tmp_path):
    from repro.sched import tpcc
    monkeypatch.setattr(tpcc, "log_capacity",
                        lambda txns, share: {"order": 10_000,
                                             "new_order": 10_000,
                                             "order_line": 100_000,
                                             "history": 60})
    with pytest.raises(RuntimeError, match="append table ran out"):
        run_cpu(monkeypatch, root_for(tmp_path), "tiny-tpcc", SEED, 30.0)


# -- the generator --------------------------------------------------------

GEN = run.load_module(BENCH / "traffic" / "tpcc_backlog.py")
CONFIG = {"warehouses": 4, "districts_per_warehouse": 10,
          "customers_per_district": 3000, "items": 100_000,
          "new_order_share": 0.5}


def _within(count, total, p, sigmas=5.0):
    return abs(count - total * p) <= sigmas * np.sqrt(total * p * (1 - p))


@pytest.mark.parametrize("a,x,y", [(255, 0, 999), (1023, 1, 3000),
                                   (8191, 1, 200)])
def test_nurand_matches_its_distribution(a, x, y):
    """Every value's frequency against NURand's exact distribution, got
    by counting all (A + 1) x (y - x + 1) pairs of its two draws."""
    c = 123 % (a + 1)
    r1 = np.arange(a + 1)[:, None]
    r2 = np.arange(x, y + 1)[None, :]
    exact = np.bincount((((r1 | r2) + c) % (y - x + 1)).ravel(),
                        minlength=y - x + 1) / ((a + 1) * (y - x + 1))
    n = 400_000
    got = np.bincount(GEN.nurand(np.random.default_rng(5), a, x, y, c, n)
                      - x, minlength=y - x + 1)
    sd = np.sqrt(n * exact * (1 - exact)) + 1e-9
    assert (np.abs(got - n * exact) <= 5.5 * sd + 1).all()


def test_c_last_run_keeps_its_distance_from_load():
    rng = np.random.default_rng(6)
    for load in range(0, 256, 5):
        delta = abs(GEN.c_last_run(rng, load) - load)
        assert 65 <= delta <= 119 and delta not in (96, 112)


def test_stream_mix_shares():
    n = 100_000
    s = GEN.stream(CONFIG, {"stream_txns": n}, 2**31 + 9, c_load=100)
    no = s["new_order"].astype(bool)
    pay = ~no
    assert _within(no.sum(), n, 0.5)
    w, d = s["w"], s["d"]
    assert w.min() == 1 and w.max() == 4 and d.min() == 1 and d.max() == 10
    for k in range(1, 5):
        assert _within((w == k).sum(), n, 0.25)
    cnt = s["ol_cnt"][no]
    assert cnt.min() == 5 and cnt.max() == 15
    assert abs(cnt.mean() - 10) < 5 * np.sqrt(10 / no.sum())
    line = np.arange(15)[None, :] < cnt[:, None]
    items = s["i_id"][no]
    srt = np.sort(np.where(line, items, -np.arange(15)), 1)
    assert not (srt[:, 1:] == srt[:, :-1]).any()          # distinct items
    assert ((items >= 1) & (items <= 100_000))[line].all()
    qty = s["qty"][no][line]
    assert qty.min() == 1 and qty.max() == 10
    remote = (s["supply_w"][no] != w[no][:, None])[line]
    assert _within(remote.sum(), line.sum(), 0.01)
    remote = s["c_w"][pay] != w[pay]
    assert _within(remote.sum(), pay.sum(), 0.15)
    assert (s["c_d"][pay][~remote] == d[pay][~remote]).all()
    by_name = s["c_last"][pay] >= 0
    assert _within(by_name.sum(), pay.sum(), 0.6)
    assert (s["c"][pay][by_name] == 0).all()
    assert ((s["c"][pay][~by_name] >= 1)
            & (s["c"][pay][~by_name] <= 3000)).all()
    h = s["h_amount"][pay]
    assert h.min() >= 100 and h.max() <= 500_000
    assert abs(h.mean() - 250_050) < 5 * 144_337 / np.sqrt(pay.sum())
    assert (s["h_amount"][no] == 0).all() and (s["c_last"][no] < 0).all()
