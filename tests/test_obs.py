"""Observability layer (DESIGN.md §8, ``repro.obs``).

* Zero-cost off: with ``EngCfg.telemetry`` off vs on, every non-obs
  engine state leaf must be BIT-IDENTICAL — the telemetry fold reads
  the step's masks but never feeds back into the simulation — for all
  three protocols, single-lane and fleet.
* Compile-once preserved: the fig7 fleet with telemetry on still
  traces exactly once across new MPL/seed values.
* Internal consistency: committed-transaction histograms sum to the
  commit counter; cause taxonomies partition the abort/block counters.
* Oracle parity: the pysim mirror's histograms equal a direct numpy
  recompute over its raw samples (shared bins), its cause support
  matches the protocol structure, and engine-vs-oracle percentiles
  agree statistically (different RNG streams — tolerance, not
  equality).
* Ring buffer: valid rows, monotone cumulative channels, and a
  Chrome-trace JSON export that Perfetto can open.
"""
import json

import jax
import numpy as np
import pytest

from repro.core import jaxsim, pysim, sweep
from repro.core.types import SimParams
from repro.obs import metrics as M
from repro.obs import trace as obs_trace

GRID = SimParams(db_size=100, txn_size_mean=8, write_prob=0.2, mpl=16,
                 horizon=2_000.0, seed=0)
PROTOCOLS = ("ppcc", "2pl", "occ")


def _final_state(protocol, telemetry, trace_every=0, **kw):
    run = jaxsim.make_padded_engine(GRID, protocol, n_slots=24,
                                    telemetry=telemetry,
                                    trace_every=trace_every, **kw)
    import jax.numpy as jnp
    return run(jnp.int32(0), jnp.int32(GRID.mpl))


# --------------------------------------------------------------------------
# zero-cost off / bit-identity
# --------------------------------------------------------------------------

@pytest.mark.parametrize("protocol", PROTOCOLS)
def test_telemetry_off_on_bit_identical_single_lane(protocol):
    """Swapping the telemetry flag must not change a single bit of the
    simulation state (compare every EngState leaf except ``tm``)."""
    off = _final_state(protocol, telemetry=False)
    on = _final_state(protocol, telemetry=True, trace_every=8)
    for a, b in zip(jax.tree.leaves(off._replace(tm=on.tm)),
                    jax.tree.leaves(on)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and the off-state telemetry leaves really are shape-0
    assert all(x.size == 0 for x in jax.tree.leaves(off.tm))


def test_telemetry_off_on_bit_identical_fleet():
    """Fleet metric arrays are unchanged by the flag, and the telemetry
    fleet still compiles exactly once across fresh MPL/seed values."""
    mpls, seeds = (5, 10, 16), (0, 1)
    off, _ = sweep.run_fleet(6, mpls, seeds, horizon=1_000.0)
    on, fleet = sweep.run_fleet(6, mpls, seeds, horizon=1_000.0,
                                telemetry=True, trace_every=8,
                                trace_len=64)
    for proto in PROTOCOLS:
        for k in off[proto]:
            np.testing.assert_array_equal(off[proto][k], on[proto][k])
        assert set(on[proto]["telemetry"]) == {
            "lat_hist", "wait_hist", "restart_hist", "abort_causes",
            "block_causes", "trace"}
        assert on[proto]["telemetry"]["lat_hist"].shape == (
            len(mpls), len(seeds), M.NBINS)
    assert fleet.traces == 1
    fleet((6, 11, 17), (2, 3))                       # new runtime values
    assert fleet.traces == 1


# --------------------------------------------------------------------------
# internal consistency: histograms/causes partition the counters
# --------------------------------------------------------------------------

@pytest.mark.parametrize("protocol", PROTOCOLS)
def test_engine_accumulators_partition_counters(protocol):
    s = _final_state(protocol, telemetry=True, trace_every=4)
    commits, aborts = int(s.commits), int(s.aborts)
    assert commits > 0
    tm = s.tm
    assert int(tm.lat_hist.sum()) == commits
    assert int(tm.wait_hist.sum()) == commits
    assert int(tm.restart_hist.sum()) == commits
    assert int(tm.abort_causes.sum()) == aborts
    # lock + rule block episodes partition the engine blocks counter
    assert int(tm.block_causes[0] + tm.block_causes[1]) == int(s.blocks)
    causes = dict(zip(M.ABORT_CAUSES, np.asarray(tm.abort_causes)))
    blocks = dict(zip(M.BLOCK_CAUSES, np.asarray(tm.block_causes)))
    if protocol == "2pl":
        # 2PL aborts only via block timeout; blocks only via locks
        assert causes["precedence"] == 0
        assert causes["validate_read"] + causes["validate_commit"] == 0
        assert blocks["rule"] == 0 and blocks["wc_lock"] == 0
    elif protocol == "occ":
        # OCC never blocks and aborts only through validation
        assert int(s.blocks) == 0 and sum(blocks.values()) == 0
        assert causes["block_timeout"] + causes["wc_timeout"] == 0
        assert causes["precedence"] == 0
    else:
        # PPCC has no validation phase
        assert causes["validate_read"] + causes["validate_commit"] == 0


@pytest.mark.parametrize("protocol", PROTOCOLS)
def test_pysim_telemetry_matches_raw_samples(protocol):
    """The oracle's histograms must equal a direct numpy recompute over
    its raw per-commit samples — same bins as the engine."""
    res = pysim.simulate(GRID.with_(horizon=5_000.0), protocol)
    tm = res.telemetry
    assert len(tm["latencies"]) == res.commits
    assert sum(tm["abort_causes"].values()) == res.aborts
    np.testing.assert_array_equal(
        tm["lat_hist"],
        np.bincount(M.value_bin(np.asarray(tm["latencies"])),
                    minlength=M.NBINS)[:M.NBINS])
    np.testing.assert_array_equal(
        tm["wait_hist"],
        np.bincount(M.value_bin(np.asarray(tm["waits"])),
                    minlength=M.NBINS)[:M.NBINS])
    assert int(tm["restart_hist"].sum()) == res.commits
    # mean latency from the raw samples matches SimResult's own account
    np.testing.assert_allclose(float(np.sum(tm["latencies"])),
                               res.sum_response_time, rtol=1e-9)
    if protocol == "occ":
        assert tm["block_causes"] == {c: 0 for c in M.BLOCK_CAUSES}
        assert tm["abort_causes"]["validate_read"] == res.aborts
    if protocol == "2pl":
        assert tm["abort_causes"]["block_timeout"] == res.aborts
        assert tm["block_causes"]["lock"] == res.blocks


@pytest.mark.parametrize("protocol", PROTOCOLS)
def test_engine_vs_oracle_latency_parity(protocol):
    """Engine and oracle percentiles agree statistically (different
    PRNG streams, same model) — the histogram-vs-oracle gate of the
    obs layer on a small fig6-like lane."""
    p = GRID.with_(horizon=5_000.0)
    s = _final_state_at(p, protocol)
    oracle = pysim.simulate(p, protocol)
    eng_p = M.percentiles(np.asarray(s.tm.lat_hist))
    ora_p = M.percentiles(oracle.telemetry["lat_hist"])
    assert int(s.tm.lat_hist.sum()) > 20 and oracle.commits > 20
    ratio = eng_p["p50"] / ora_p["p50"]
    assert 0.5 <= ratio <= 2.0, (eng_p, ora_p)
    # cause support agrees structurally: a cause the oracle cannot
    # produce must be absent from the engine too (and vice versa for
    # the validation split, which the engine alone refines)
    eng_c = dict(zip(M.ABORT_CAUSES, np.asarray(s.tm.abort_causes)))
    ora_c = oracle.telemetry["abort_causes"]
    for cause in ("precedence", "validate_read", "validate_commit"):
        if protocol != "ppcc" and cause == "precedence":
            assert eng_c[cause] == 0 and ora_c[cause] == 0
        if protocol != "occ" and cause.startswith("validate"):
            assert eng_c[cause] == 0 and ora_c[cause] == 0


def _final_state_at(p, protocol):
    import jax.numpy as jnp
    run = jaxsim.make_padded_engine(p, protocol, n_slots=24,
                                    telemetry=True, trace_every=8)
    return run(jnp.int32(0), jnp.int32(p.mpl))


# --------------------------------------------------------------------------
# host-side reductions
# --------------------------------------------------------------------------

def test_percentile_from_hist_exact_bins():
    hist = np.zeros(M.NBINS, int)
    hist[M.value_bin(10.0)] = 50
    hist[M.value_bin(1000.0)] = 49
    hist[M.value_bin(100_000.0)] = 1
    reps = M.bin_values()
    assert M.percentile_from_hist(hist, 0.5) == reps[M.value_bin(10.0)]
    assert M.percentile_from_hist(hist, 0.99) == \
        reps[M.value_bin(1000.0)]
    assert M.percentile_from_hist(hist, 0.999) == \
        reps[M.value_bin(100_000.0)]
    assert np.isnan(M.percentile_from_hist(np.zeros(M.NBINS), 0.5))
    labels = M.percentiles(hist)
    assert set(labels) == {"p50", "p99", "p999"}


def test_host_hist_matches_engine_binning():
    h = M.HostHist()
    vals = [0.5, 1.0, 7.0, 300.0, 2e6]
    for v in vals:
        h.add(v)
    assert h.count == len(vals)
    np.testing.assert_array_equal(
        h.hist, np.bincount(M.value_bin(np.asarray(vals)),
                            minlength=M.NBINS)[:M.NBINS])
    # out-of-range values clamp into the edge bins, never drop
    assert h.hist[0] >= 1 and h.hist[M.NBINS - 1] >= 1


def test_summarize_aggregates_lane_axes():
    s = _final_state("ppcc", telemetry=True)
    tm = {k: np.asarray(getattr(s.tm, k))[None, None]
          for k in ("lat_hist", "wait_hist", "restart_hist",
                    "abort_causes", "block_causes")}
    out = M.summarize(tm)
    assert out["commits"] == int(s.commits)
    assert sum(out["abort_causes"].values()) == int(s.aborts)
    assert out["commit_latency"]["p50"] > 0


# --------------------------------------------------------------------------
# ring buffer + Chrome-trace export
# --------------------------------------------------------------------------

def test_ring_buffer_rows_and_trace_export(tmp_path):
    s = _final_state("ppcc", telemetry=True, trace_every=4,
                     trace_len=64)
    rows = obs_trace.trace_rows(np.asarray(s.tm.trace))
    assert rows.shape[1] == len(M.TRACE_CHANNELS)
    assert len(rows) > 4
    now = rows[:, M.TRACE_CHANNELS.index("now")]
    assert (now >= 0).all() and (np.diff(now) >= 0).all()
    assert now[-1] > now[0]
    for ch in ("commits", "aborts"):
        c = rows[:, M.TRACE_CHANNELS.index(ch)]
        assert (np.diff(c) >= 0).all(), f"{ch} not cumulative"
    final_commits = rows[-1, M.TRACE_CHANNELS.index("commits")]
    assert 0 < final_commits <= int(s.commits)

    path = tmp_path / "trace.json"
    n = obs_trace.write_chrome_trace(path, {"ppcc": s.tm.trace},
                                     meta={"fig": "test"})
    doc = json.loads(path.read_text())
    events = doc["traceEvents"]
    counters = [e for e in events if e["ph"] == "C"]
    assert n == len(events)
    assert len(counters) == len(rows) * (len(M.TRACE_CHANNELS) - 1)
    assert all(e["ts"] >= 0 for e in counters)
    assert doc["otherData"] == {"fig": "test"}


def test_trace_disabled_keeps_zero_rows():
    s = _final_state("ppcc", telemetry=True, trace_every=0)
    assert np.asarray(s.tm.trace).shape[0] == 0
    assert len(obs_trace.trace_rows(np.asarray(s.tm.trace))) == 0
