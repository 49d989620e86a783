"""The keyed table store (``sched.txstore``) and TPC-C's NewOrder and
Payment on it (``sched.tpcc``), on the CPU at a tiny size, against
plain serial execution (``bench/reference/tpcc_tick.py``): tick by
tick through ``scheduler.tick``, blind writers resolved by commit
rank, a log that runs out, and the exact fixed-point pieces."""
import functools
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.sched import scheduler, tpcc, txstore

BENCH = Path(__file__).resolve().parents[1] / "bench"
sys.path.insert(0, str(BENCH))

from reference import tpcc_tick  # noqa: E402
from traffic import tpcc_backlog  # noqa: E402

CFG = json.loads((BENCH / "tests/data/tiny-tpcc.json").read_text())
SCALE = tpcc.Scale.of(CFG)
SEED = 2**31 + 2020
N = 64


@pytest.fixture(scope="module")
def world():
    """The tiny database and a stream whose by-name Payments are
    resolved, as the driver does."""
    tables, c_load = tpcc.populate(SCALE, SEED)
    stream = tpcc_backlog.stream(CFG, {"stream_txns": 3000}, SEED, c_load)
    name = stream.pop("c_last")
    by = name >= 0
    directory = tpcc.name_directory(tables["customer"], SCALE)
    stream["c"][by] = directory[stream["c_w"][by] - 1,
                                stream["c_d"][by] - 1, name[by]]
    return tables, stream


def test_keyed_store_equals_serial_execution(world):
    """Eight ticks of the loop: the program's admission equals the
    reference's, and after every tick its tables, appended rows and
    outputs equal those of the reference, which executes the admitted
    transactions one at a time in commit order on its own copy of the
    database, carried from tick to tick."""
    tables, stream = world
    store = txstore.new_store(tables, tpcc.LOG_COLUMNS,
                              tpcc.log_capacity(3000, 0.5))
    apply = jax.jit(functools.partial(tpcc.apply_tick, scale=SCALE))
    ref_tables = tables
    dev_stream = jax.device_put(stream)
    backlog = jax.tree.map(lambda a: a[:N], dev_stream)
    pos, kinds = N, set()
    for now in range(1, 9):
        read, write = tpcc.key_lists(backlog, SCALE)
        assert read.shape == (N, 33) and write.shape == (N, 16)
        res = scheduler.tick(read, write, jnp.ones(N, bool), keys=True)
        fill = {t: int(v) for t, v in store.fill.items()}
        store, out, counts = apply(store, backlog, res.admitted,
                                   res.commit_rank, now)
        txn = jax.device_get(backlog)
        ref = tpcc_tick.admit(txn)
        np.testing.assert_array_equal(np.asarray(res.admitted),
                                      ref["admitted"])
        np.testing.assert_array_equal(np.asarray(res.commit_rank),
                                      ref["commit_rank"])
        ref_tables, ref_logs, ref_out = tpcc_tick.execute(
            ref_tables, CFG, txn, ref["admitted"], ref["commit_rank"], now)
        logs = {t: {c: np.asarray(v)[fill[t]:int(store.fill[t])]
                    for c, v in cols.items()}
                for t, cols in store.logs.items()}
        assert tpcc_tick.mismatches(
            jax.device_get(store.tables), logs, jax.device_get(out),
            ref_tables, ref_logs, ref_out) == 0
        no = txn["new_order"].astype(bool) & ref["admitted"]
        assert list(np.asarray(counts)) == [
            ref["admitted"].sum(), no.sum(), (ref["admitted"] & ~no).sum(),
            txn["ol_cnt"][no].sum(), 0]
        kinds |= {bool(txn["new_order"][k]) for k in ref_out}
        backlog = tpcc_backlog.refill(backlog, res.admitted, dev_stream,
                                      pos)
        pos += int(counts[0])
    assert kinds == {True, False}


def _one_table_store(rows: int = 6):
    return txstore.new_store(
        {"t": {"v": jnp.arange(rows, dtype=jnp.int32),
               "s": jnp.zeros((rows, 3), jnp.uint8)}},
        {"log": {"x": ((), jnp.int32)}}, {"log": 8})


def test_blind_writers_resolve_by_commit_rank():
    """T0 and T1 write key 2 without reading it; T2 reads key 2 and
    writes key 4.  The tick admits all three, T2 first (it reads what
    both others write); both blind writes land, the later commit rank's
    value stays, and T2 saw the snapshot."""
    read = jnp.array([[-1], [-1], [2]], jnp.int32)
    write = jnp.array([[2], [2], [4]], jnp.int32)
    res = scheduler.tick(read, write, jnp.ones(3, bool), keys=True)
    assert np.asarray(res.admitted).all()
    assert list(np.asarray(res.commit_rank)) == [1, 2, 0]
    rows = {"t": jnp.array([[-1], [-1], [2]], jnp.int32)}

    def logic(snap):
        v = snap["t"]["v"][:, 0]
        new = jnp.array([100, 200, 0], jnp.int32).at[2].set(v[2] + 1)
        return ({"t": (write, {"v": new[:, None]})}, {},
                {"seen": v})
    store, out, over = txstore.apply_keyed(_one_table_store(), rows,
                                           res.commit_rank, logic)
    v = np.asarray(store.tables["t"]["v"])
    assert list(v) == [0, 1, 200, 3, 3, 5] and not bool(over)
    flipped = jnp.array([2, 1, 0], jnp.int32)
    store, _, _ = txstore.apply_keyed(_one_table_store(), rows, flipped,
                                      logic)
    assert list(np.asarray(store.tables["t"]["v"])) == [0, 1, 100, 3, 3, 5]
    # not admitted (-1): writes nothing
    store, _, _ = txstore.apply_keyed(_one_table_store(), rows,
                                      jnp.array([-1, -1, -1]), logic)
    assert list(np.asarray(store.tables["t"]["v"])) == list(range(6))


def test_appends_in_commit_order_and_a_full_log_is_reported():
    """Inserted rows land in commit order after the log's fill; a log
    that runs out drops the rows past its end (none wraps to the front)
    and says so."""
    store = _one_table_store()
    count = jnp.array([3, 2, 4], jnp.int32)
    new = {"x": jnp.array([[10, 11, 12, 0], [20, 21, 0, 0],
                           [30, 31, 32, 33]], jnp.int32)}
    logs, fill, over = txstore.append_rows(
        store.logs, store.fill, {"log": (count, new)},
        jnp.array([1, 0, -1], jnp.int32))
    assert int(fill["log"]) == 5 and not bool(over)
    assert list(np.asarray(logs["log"]["x"])) == [20, 21, 10, 11, 12,
                                                  0, 0, 0]
    logs, fill, over = txstore.append_rows(
        logs, fill, {"log": (count, new)}, jnp.array([0, -1, 1], jnp.int32))
    assert bool(over) and int(fill["log"]) == 12
    assert list(np.asarray(logs["log"]["x"])) == [20, 21, 10, 11, 12,
                                                  10, 11, 12]


def test_money_limbs_stay_exact_past_int32():
    """A warehouse's W_YTD passes 2^31 cents within a run: the two
    limbs follow Python's integers through every add and subtract."""
    rng = np.random.default_rng(7)
    h = rng.integers(100, 500_001, 20_000)
    sign = np.where(rng.random(20_000) < 0.2, -1, 1)
    want = 30_000_000 + np.cumsum(h * sign)
    x = jnp.asarray(h * sign, jnp.int32)

    def step(m, dx):
        m = tpcc.money_add(m, dx)
        return m, m
    _, ms = jax.lax.scan(step, jnp.asarray(tpcc.money(30_000_000)), x)
    ms = np.asarray(ms).astype(np.int64)
    assert ((0 <= ms[:, 1]) & (ms[:, 1] < tpcc.UNIT)).all()
    np.testing.assert_array_equal(ms[:, 0] * tpcc.UNIT + ms[:, 1], want)
    assert want.max() > 2**31


def test_scale_round_is_exact():
    rng = np.random.default_rng(8)
    v = rng.integers(0, 2_000_000, 100_000)
    m = rng.integers(0, 20_001, 100_000)
    got = np.asarray(tpcc.scale_round(jnp.asarray(v, jnp.int32),
                                      jnp.asarray(m, jnp.int32)))
    np.testing.assert_array_equal(got, (v * m + 5_000) // 10_000)


def test_name_directory_picks_the_middle_by_first_name(world):
    """Against the rule written plainly: the district's customers of
    that last name, sorted by (C_FIRST, C_ID), the one at ceil(n/2)."""
    tables, _ = world
    cu = tables["customer"]
    got = tpcc.name_directory(cu, SCALE)
    groups = {}
    for r in range(len(cu["c_id"])):
        key = (int(cu["c_w_id"][r]), int(cu["c_d_id"][r]),
               bytes(cu["c_last"][r]).rstrip(b"\0"))
        groups.setdefault(key, []).append(
            (bytes(cu["c_first"][r]).rstrip(b"\0"), int(cu["c_id"][r])))
    for num in range(SCALE.names):
        for w in range(1, SCALE.warehouses + 1):
            for d in range(1, SCALE.districts + 1):
                rows = sorted(groups[(w, d, tpcc.last_name(num))])
                assert got[w - 1, d - 1, num] == rows[(len(rows) + 1) // 2
                                                      - 1][1]


def test_key_ids_and_lists():
    """One id space with a block a table (4 warehouses: 620 044 ids);
    a NewOrder writes its district and stock rows and reads those and
    its warehouse, customer and items; a Payment reads and writes its
    warehouse, district and customer."""
    scale = tpcc.Scale(4)
    b = tpcc.bases(scale)
    assert b["stock"] + scale.rows()["stock"] == 620_044
    txn = {f: np.zeros((2, 15) if f in ("i_id", "supply_w", "qty")
                       else 2, np.int32) for f in tpcc.TXN_FIELDS}
    txn.update(new_order=np.array([1, 0]), w=np.array([2, 3]),
               d=np.array([4, 5]), c=np.array([6, 7]),
               c_w=np.array([2, 1]), c_d=np.array([4, 9]),
               ol_cnt=np.array([5, 0]))
    txn["i_id"][0, :5] = [1, 2, 3, 99_999, 100_000]
    txn["supply_w"][0, :5] = [2, 2, 4, 2, 1]
    read, write = (np.asarray(a) for a in tpcc.key_lists(txn, scale))
    w, d, c = b["warehouse"], b["district"], b["customer"]
    no_stock = [b["stock"] + (sw - 1) * 100_000 + i - 1 for i, sw in
                zip([1, 2, 3, 99_999, 100_000], [2, 2, 4, 2, 1])]
    assert list(read[0, :3]) == [w + 1, d + 13, c + (13 * 3000 + 5)]
    assert list(read[0, 3:8]) == [b["item"] + i - 1 for i in
                                  [1, 2, 3, 99_999, 100_000]]
    assert list(read[0, 18:23]) == no_stock
    assert (read[0, 8:18] < 0).all() and (read[0, 23:] < 0).all()
    assert list(write[0, :6]) == [d + 13] + no_stock
    assert (write[0, 6:] < 0).all()
    pay = [w + 2, d + 24, c + (8 * 3000 + 6)]
    assert list(read[1, :3]) == pay and (read[1, 3:] < 0).all()
    assert list(write[1, :3]) == pay and (write[1, 3:] < 0).all()
