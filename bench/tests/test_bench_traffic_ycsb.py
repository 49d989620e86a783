"""The YCSB-A traffic of the tick cells on the CPU: the stream drawn
from the seed (keys, updates, YCSB's zipfian and hash), the backlog's
refill, and the plain admission reference against the scheduler's
oracle path on the stream's backlogs."""
import json

import numpy as np
import pytest

from bench_test_util import BENCH

import run

YCSB = run.load_module(BENCH / "traffic" / "ycsb_backlog.py")
CFG20M = json.loads((BENCH / "configs" / "ycsb-a-calvin-20m.json")
                    .read_text())


def test_ycsb_stream_transactions():
    """16 distinct keys of the 20M-record table per transaction, about
    half updated; the same seed draws the same stream, another seed
    another."""
    tr = {"stream_txns": 4096}
    keys, upd = YCSB.stream(CFG20M, tr, 2**31 + 77)
    assert keys.shape == upd.shape == (4096, 16) and keys.dtype == np.int32
    assert ((keys >= 0) & (keys < 20_000_000)).all()
    assert all(len(set(row)) == 16 for row in keys.tolist())
    assert abs(upd.mean() - 0.5) < 0.01
    again, upd2 = YCSB.stream(CFG20M, tr, 2**31 + 77)
    assert (again == keys).all() and (upd2 == upd).all()
    other, _ = YCSB.stream(CFG20M, tr, 2**31 + 78)
    assert (other != keys).mean() > 0.9


def test_ycsb_updates_are_read_and_written():
    keys, upd = YCSB.stream(CFG20M, {"stream_txns": 256}, 5)
    read, write = (np.asarray(a) for a in YCSB.sets(keys, upd))
    assert (read == keys).all()
    assert (write[upd] == keys[upd]).all()
    assert (write[~upd] == YCSB.PAD).all()


def test_ycsb_zipf_top_ranks_match_zeta():
    """Gray's method draws ranks 0 and 1 with exactly 1 / zeta and
    0.5^theta / zeta; the scrambled hot key keeps its share."""
    rng = np.random.default_rng(2**31 + 3)
    n = 2_000_000
    r = YCSB.zipf_ranks(rng, n)
    for rank in (0, 1):
        p = (rank + 1) ** -YCSB.YCSB_THETA / YCSB.YCSB_ZETAN
        got = (r == rank).mean()
        assert abs(got - p) < 5 * np.sqrt(p / n), (rank, got, p)
    assert YCSB.zeta(2, 0.99) == pytest.approx(1 + 2 ** -0.99)
    keys = YCSB.scrambled_keys(np.random.default_rng(2), n, 20_000_000)
    hot = np.bincount(keys).max() / n
    assert abs(hot - 1 / YCSB.YCSB_ZETAN) < 0.002


def test_ycsb_fnvhash64_is_ycsbs():
    """The vectorised hash against a plain one in Python integers, as
    YCSB's ``Utils.fnvhash64`` computes it on Java longs."""
    def plain(v):
        h = 0xCBF29CE484222325
        for _ in range(8):
            h = ((h ^ (v & 0xFF)) * 1099511628211) & (2**64 - 1)
            v >>= 8
        h = h - 2**64 if h >= 2**63 else h
        return abs(h) if h != -2**63 else h
    xs = [0, 1, 255, 256, 10**10, 2**40 + 12345, 987654321]
    assert YCSB.fnvhash64(np.asarray(xs)).tolist() == [plain(x) for x in xs]


def test_ycsb_refill_keeps_oldest_first():
    """Admitted rows leave, the rest keep their order at the head and
    the stream's next rows fill the tail; past the stream is an error."""
    import jax.numpy as jnp
    n = 8
    keys = np.arange(40 * 2, dtype=np.int32).reshape(40, 2)
    upd = np.zeros((40, 2), bool)
    upd[:, 1] = True
    read, write = YCSB.sets(jnp.asarray(keys[:n]), jnp.asarray(upd[:n]))
    admitted = jnp.asarray([1, 0, 0, 1, 1, 0, 0, 0], bool)
    r2, w2 = YCSB.refill(read, write, admitted, jnp.asarray(keys),
                         jnp.asarray(upd), n)
    rows = [1, 2, 5, 6, 7, 8, 9, 10]
    assert np.asarray(r2).tolist() == keys[rows].tolist()
    assert np.asarray(w2)[:, 0].tolist() == [YCSB.PAD] * n
    assert np.asarray(w2)[:, 1].tolist() == keys[rows, 1].tolist()
    with pytest.raises(RuntimeError, match="ran out"):
        YCSB.refill(read, write, admitted, jnp.asarray(keys),
                    jnp.asarray(upd), 33)



@pytest.mark.parametrize("seed", [2**31 + 5, 11])
def test_tick_reference_equals_the_scheduler_oracle(seed):
    """The plain admission reference against the scheduler's
    ``use_kernel=False`` PPCC tick on YCSB-A backlogs of a closed loop:
    the same admitted rows and commit ranks, tick after tick, and no
    arc against the commit order."""
    import jax.numpy as jnp
    from repro.sched import scheduler
    from reference import ycsb_tick
    cfg = {"records": 300, "keys_per_txn": 6, "update_share": 0.5}
    n = 48
    keys, upd = YCSB.stream(cfg, {"stream_txns": 1000}, seed)
    keys, upd = jnp.asarray(keys), jnp.asarray(upd)
    read, write = YCSB.sets(keys[:n], upd[:n])
    pos, valid = n, jnp.ones(n, bool)
    for _ in range(4):
        got = scheduler.ppcc_tick(read, write, valid, use_kernel=False,
                                  keys=True)
        want = ycsb_tick.tick(read, write, np.ones(n, bool))
        adm = np.asarray(got.admitted)
        assert (adm == want["admitted"]).all()
        assert (np.asarray(got.commit_rank) == want["commit_rank"]).all()
        assert ycsb_tick.order_violations(want["raw"], adm,
                                          want["commit_rank"]) == 0
        assert 0 < adm.sum() < n
        read, write = YCSB.refill(read, write, got.admitted, keys, upd, pos)
        pos += int(adm.sum())


def test_tick_reference_counts_order_violations():
    """Two admitted transactions that read each other's writes cannot
    both commit first: the one arc against the order is counted."""
    from reference import ycsb_tick
    raw = np.array([[False, True], [True, False]])
    assert ycsb_tick.order_violations(raw, [True, True], [0, 1]) == 1
    assert ycsb_tick.order_violations(raw, [True, False], [0, -1]) == 0
    out = ycsb_tick.tick([[3, -1], [3, 4]], [[3, -1], [-1, -1]], [1, 1])
    assert out["admitted"].tolist() == [True, True]
    assert out["commit_rank"].tolist() == [1, 0]
