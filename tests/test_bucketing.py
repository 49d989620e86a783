"""Static-axis bucketing (DESIGN.md §2.4): a figure run inside the
grid's shape buckets must be BIT-identical to its native-shape run.

The load-bearing facts, each asserted here:

* samplers always draw at the op-bucket width (``EngCfg.ops_draw``) and
  slice, so the PRNG stream is independent of ``max_ops``;
* runtime bounds (``RtParams``) feed ``jax.random`` as traced scalars,
  which produces the same values as static bounds;
* pad item words stay zero (§1.1), pad op slots stay ``-1``, pad
  resource-pool entries stay ``free_at = INF`` (FCFS argmin never
  picks them) — so the padded computation is the native one;
* ``bitset.bucket`` is the one quantiser behind the slot, item-word
  and op axes;
* a multi-figure ``run_grid`` compiles exactly once and each figure's
  block equals its own per-figure fleet.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bitset, jaxsim, sweep
from repro.core.types import SimParams, grid_cover_params

# a d=100 / 8±4-op / 4-cpu figure shape ...
NATIVE = SimParams(db_size=100, txn_size_mean=8, txn_size_spread=4,
                   write_prob=0.2, num_cpus=4, num_disks=8, mpl=12,
                   horizon=2_000.0, seed=0)
# ... run inside the full-grid buckets: 500-item words, 16±4-op lists
# (op draws happen at the shared 20-op bucket either way), 16/32 pools
BUCKET = NATIVE.with_(db_size=500, txn_size_mean=16, txn_size_spread=4,
                      num_cpus=16, num_disks=32)


def test_bucket_quantiser():
    assert bitset.bucket(5, 32) == 32
    assert bitset.bucket(32, 32) == 32
    assert bitset.bucket(33, 32) == 64
    assert bitset.bucket(1, 20) == 20
    assert bitset.bucket(0, 20) == 20          # floor: one quantum
    with pytest.raises(ValueError):
        bitset.bucket(4, 0)
    # the item-word axis goes through the same quantiser
    assert bitset.n_words(100) == 4
    assert bitset.n_words(500) == 16


@pytest.mark.parametrize("protocol", ["ppcc", "2pl", "occ"])
def test_bucketed_run_bit_identical(protocol):
    """NATIVE's engine vs BUCKET's engine driven by NATIVE's RtParams:
    identical commits, aborts, iteration counts and final clock."""
    rt = jaxsim.rt_of(NATIVE)
    native = jaxsim.make_padded_engine(NATIVE, protocol, n_slots=16,
                                       pool=512)
    bucket = jaxsim.make_padded_engine(BUCKET, protocol, n_slots=16,
                                       pool=512)
    a = native(jnp.int32(0), jnp.int32(NATIVE.mpl))
    b = bucket(jnp.int32(0), jnp.int32(NATIVE.mpl), rt=rt)
    assert int(a.commits) > 0
    for f in ("commits", "aborts", "blocks", "ops_done", "iters"):
        assert int(getattr(a, f)) == int(getattr(b, f)), f
    assert float(a.now) == float(b.now)


def test_pad_axes_stay_inert():
    """After a bucketed run: item words past ceil(d/32) are zero, op
    slots past the live length bound are -1, and pool entries past the
    live cpu/disk counts still read free_at >= INF."""
    rt = jaxsim.rt_of(NATIVE)
    run = jaxsim.make_padded_engine(BUCKET, "ppcc", n_slots=16, pool=512)
    s = run(jnp.int32(1), jnp.int32(NATIVE.mpl), rt=rt)
    w_live = bitset.n_words(NATIVE.db_size)
    for bits in (s.pstate.read_set, s.pstate.write_set, s.dirty):
        assert not np.asarray(bits)[:, w_live:].any()
    assert (np.asarray(s.kinds)[:, int(rt.len_hi):] == -1).all()
    assert (np.asarray(s.cpu_free)[int(rt.cpus):] >= 1e29).all()
    assert (np.asarray(s.disk_free)[int(rt.disks):] >= 1e29).all()


def test_check_rt_rejects_bucket_overflow():
    """Values past their static buckets would silently corrupt (items
    into pad bits, pool entries that do not exist) — must raise."""
    rt = jaxsim.rt_of(NATIVE)
    with pytest.raises(ValueError):
        jaxsim.check_rt(NATIVE, rt._replace(d=jnp.int32(101)))
    with pytest.raises(ValueError):
        jaxsim.check_rt(NATIVE, rt._replace(len_hi=jnp.int32(13)))
    with pytest.raises(ValueError):
        jaxsim.check_rt(NATIVE, rt._replace(cpus=jnp.int32(5)))
    jaxsim.check_rt(BUCKET, rt)                # inside the buckets: fine


def test_workload_batch_op_bucket():
    """Host-side tensorisation at the op bucket: same draws, wider -1
    pad — slicing the bucketed batch recovers the native one."""
    from repro.core import workload

    k, i, n = workload.workload_batch(0, NATIVE, 6, max_ops=12)
    kb, ib, nb = workload.workload_batch(0, NATIVE, 6, max_ops=12,
                                         quantum=jaxsim.OP_QUANTUM)
    assert kb.shape == (6, 20) and k.shape == (6, 12)
    np.testing.assert_array_equal(n, nb)
    np.testing.assert_array_equal(k, kb[:, :12])
    np.testing.assert_array_equal(i, ib[:, :12])
    assert (kb[:, 12:] == -1).all()


def test_grid_cover_covers():
    cover = grid_cover_params()
    assert cover.db_size == 500
    assert cover.txn_size_mean + cover.txn_size_spread == 20
    assert cover.num_cpus == 16 and cover.num_disks == 32


def test_run_grid_one_executable_matches_per_figure_fleets():
    """Two figures of different native shape through ONE executable:
    traces stays 1 across figures AND across a re-run, and each
    figure's block is bit-identical to that figure's own fleet."""
    mpls, seeds, horizon = (4, 8), (0, 1), 600.0
    out, fleet = sweep.run_grid((6, 7), mpls, seeds, horizon,
                                max_iters=60)
    assert fleet.traces == 1
    out2, _ = sweep.run_grid((6, 7), mpls, seeds, horizon,
                             max_iters=60, fleet=fleet)
    assert fleet.traces == 1                   # re-run: no retrace
    for fig in (6, 7):
        ref, _f = sweep.run_fleet(fig, mpls, seeds, horizon,
                                  max_iters=60)
        for proto in sweep.PROTOCOLS:
            assert (out[fig][proto]["iters"] > 0).all()
            for metric in ref[proto]:
                np.testing.assert_array_equal(
                    out[fig][proto][metric], ref[proto][metric],
                    err_msg=f"fig{fig} {proto} {metric}")
                np.testing.assert_array_equal(
                    out2[fig][proto][metric], ref[proto][metric])


def test_scheduler_word_bucket_shares_executable():
    """tick(..., words=N) pads packed rows so different-d workloads
    share one jitted tick; results must match the unpadded tick."""
    from repro.sched import scheduler

    rng = np.random.default_rng(0)
    reads = jnp.asarray(rng.random((12, 40)) < 0.2)
    writes = jnp.asarray(rng.random((12, 40)) < 0.1)
    valid = jnp.ones(12, bool)
    for policy in ("ppcc", "2pl", "occ"):
        plain = scheduler.tick(reads, writes, valid, policy=policy)
        wide = scheduler.tick(reads, writes, valid, policy=policy,
                              words=16)
        np.testing.assert_array_equal(np.asarray(plain.admitted),
                                      np.asarray(wide.admitted))
        np.testing.assert_array_equal(np.asarray(plain.commit_rank),
                                      np.asarray(wide.commit_rank))
    with pytest.raises(ValueError):
        scheduler._as_bits(bitset.pack(reads), words=1)
