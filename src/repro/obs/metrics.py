"""Telemetry accumulator layout + host-side reductions (DESIGN.md §8).

The compiled engine cannot append to lists: every statistic it keeps
must be a fixed-shape array folded with masked scatters.  This module
owns those shapes — log-spaced latency/wait histograms, a clipped
restart-count histogram, and the abort/block cause taxonomies — plus
the host-side reductions (percentile extraction, summaries) applied to
them after the run.

The module itself is numpy-only so the pure-Python oracle
(``repro.core.pysim``) can share the exact same bin edges and cause
names without importing JAX; the engine-side state container
(``Telemetry``) imports ``jax.numpy`` lazily inside
``init_telemetry``.

Histogram convention: ``NBINS`` bins over value ``v >= 0`` with
``bin = searchsorted(EDGES, v, side="right")`` — bin 0 holds
``v <= 1``, the last bin holds ``v > 1e6`` (beyond any paper horizon),
and interior edges are log-spaced so relative resolution is constant
(~24% per bin at 63 edges over 6 decades).  Percentiles extracted from
such a histogram are exact to bin resolution, and two accumulators
that share ``EDGES`` can be compared bin-for-bin.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Sequence

import numpy as np

# latency / wait-time histogram: log-spaced bins over simulated time
# units.  1.0 .. 1e6 covers every paper setting (mean response times
# are O(100..10k) units; horizons cap at 100k).
NBINS = 64
EDGES = np.geomspace(1.0, 1e6, NBINS - 1)

# restart-count histogram: bin r = min(restarts, RBINS - 1)
RBINS = 16

# Abort causes, one counter per cause (engine + oracle share the order):
#   block_timeout   — read-phase block expired (2PL deadlock resolution
#                     and PPCC Fig. 3 lock blocking both land here)
#   wc_timeout      — wait-to-commit lock acquisition timed out (PPCC)
#   precedence      — Fig. 3 circular-wait abort: the op touches an item
#                     locked by a wait-to-commit txn the requester
#                     already precedes (PPCC)
#   validate_read   — OCC backward validation failed at read-phase end
#   validate_commit — OCC commit-time re-validation failed (the engine's
#                     Kung-Robinson overlap-window close; the event-heap
#                     oracle validates only at read-done, so its counter
#                     is invariantly zero)
ABORT_CAUSES = ("block_timeout", "wc_timeout", "precedence",
                "validate_read", "validate_commit")

# Block-episode causes:
#   lock    — op hit an item exclusively locked by a wait-to-commit txn
#   rule    — the Prudent Precedence Rule refused the precedence
#   wc_lock — entered the wait-to-commit lock-wait state
# (lock + rule partition the engine's read-phase `blocks` counter;
# wc_lock episodes are counted separately.)
BLOCK_CAUSES = ("lock", "rule", "wc_lock")

# Ring-buffer channels, sampled every EngCfg.trace_every iterations:
#   now      — simulated time at the quantum (-1 marks an unused row)
#   ready    — cohort size (slots whose event falls in the quantum)
#   blocked  — slots in the read-phase blocked state (post-transition)
#   waiting  — all waiting slots (blocked + wc-lock + wc-prec)
#   commits/aborts — cumulative counters
#   selected — pairwise-independent admitted subset size
#   degree   — total conflict degree among ready ops (ppcc fused path;
#              0 where the engine variant does not compute degrees)
TRACE_CHANNELS = ("now", "ready", "blocked", "waiting", "commits",
                  "aborts", "selected", "degree")

INF = 1e30


class Telemetry(NamedTuple):
    """In-loop telemetry state carried by ``jaxsim.EngState``.

    Per-slot stamps (f32/int32[n]) plus fixed-shape histograms; every
    leaf is shape-0 when ``EngCfg.telemetry`` is off, so the pytree
    structure — and therefore the compiled executable — is unchanged
    by the flag (DESIGN.md §8).
    """

    first_start: Any    # f32[n] first begin time of the slot's live txn
    wait_from: Any      # f32[n] current wait-episode start (INF: none)
    wait_acc: Any       # f32[n] accumulated wait of the live txn
    restarts: Any       # int32[n] restart count of the live txn
    lat_hist: Any       # int32[NBINS] commit latency (te - first_start)
    wait_hist: Any      # int32[NBINS] accumulated wait of committed txns
    restart_hist: Any   # int32[RBINS] restart count of committed txns
    abort_causes: Any   # int32[len(ABORT_CAUSES)]
    block_causes: Any   # int32[len(BLOCK_CAUSES)]
    trace: Any          # f32[trace_len, len(TRACE_CHANNELS)] ring buffer


def init_telemetry(n: int, trace_len: int = 0) -> Telemetry:
    """Fresh engine telemetry state; ``n = 0`` when telemetry is off
    (all-empty leaves keep the EngState tree structure constant)."""
    import jax.numpy as jnp
    nb = NBINS if n else 0
    rb = RBINS if n else 0
    nc = len(ABORT_CAUSES) if n else 0
    nbk = len(BLOCK_CAUSES) if n else 0
    trace = jnp.zeros((trace_len if n else 0, len(TRACE_CHANNELS)),
                      jnp.float32)
    if trace.shape[0]:
        trace = trace.at[:, 0].set(-1.0)      # `now` < 0 marks unused rows
    return Telemetry(
        first_start=jnp.zeros(n, jnp.float32),
        wait_from=jnp.full(n, jnp.float32(INF)),
        wait_acc=jnp.zeros(n, jnp.float32),
        restarts=jnp.zeros(n, jnp.int32),
        lat_hist=jnp.zeros(nb, jnp.int32),
        wait_hist=jnp.zeros(nb, jnp.int32),
        restart_hist=jnp.zeros(rb, jnp.int32),
        abort_causes=jnp.zeros(nc, jnp.int32),
        block_causes=jnp.zeros(nbk, jnp.int32),
        trace=trace)


# --------------------------------------------------------------------------
# host-side reductions
# --------------------------------------------------------------------------

def value_bin(v) -> np.ndarray:
    """Histogram bin of value(s) ``v`` — the shared binning rule."""
    return np.searchsorted(EDGES, v, side="right")


def bin_values() -> np.ndarray:
    """Representative value per bin: the geometric bin center (edge
    value at the extremes).  Percentiles report these."""
    rep = np.empty(NBINS)
    rep[0] = EDGES[0]
    rep[1:-1] = np.sqrt(EDGES[:-1] * EDGES[1:])
    rep[-1] = EDGES[-1]
    return rep


def percentile_from_hist(hist, q: float) -> float:
    """q-quantile (0 < q <= 1) of a histogram over the shared EDGES:
    the representative value of the first bin whose cumulative count
    reaches q — exact to bin resolution, and identical for any two
    histograms with equal counts."""
    hist = np.asarray(hist)
    total = int(hist.sum())
    if total == 0:
        return float("nan")
    idx = int(np.searchsorted(np.cumsum(hist), q * total))
    return float(bin_values()[min(idx, NBINS - 1)])


def percentiles(hist, qs: Sequence[float] = (0.5, 0.99, 0.999)) -> dict:
    # 0.5 -> p50, 0.99 -> p99, 0.999 -> p999
    def label(q):
        digits = f"{q:g}"[2:]
        return "p" + (digits + "0" if len(digits) == 1 else digits)

    return {label(q): percentile_from_hist(hist, q) for q in qs}


class HostHist:
    """Host-side accumulator over the SAME bins as the engine — used by
    the pysim oracle and the serving example so their histograms are
    bin-for-bin comparable with the compiled engine's."""

    def __init__(self):
        self.hist = np.zeros(NBINS, np.int64)

    def add(self, v: float) -> None:
        self.hist[int(value_bin(v))] += 1

    def percentiles(self, qs=(0.5, 0.99, 0.999)) -> dict:
        return percentiles(self.hist, qs)

    @property
    def count(self) -> int:
        return int(self.hist.sum())


def summarize(tm: dict) -> dict:
    """Summarize one telemetry block (``lat_hist``/``wait_hist``/
    ``restart_hist``/``abort_causes``/``block_causes`` arrays; leading
    lane axes are summed, so fleet blocks aggregate cleanly)."""
    def flat(key, width):
        return np.asarray(tm[key]).reshape(-1, width).sum(axis=0)

    lat = flat("lat_hist", NBINS)
    wait = flat("wait_hist", NBINS)
    restarts = flat("restart_hist", RBINS)
    causes = flat("abort_causes", len(ABORT_CAUSES))
    blocks = flat("block_causes", len(BLOCK_CAUSES))
    n_commit = int(lat.sum())
    return {
        "commits": n_commit,
        "commit_latency": percentiles(lat),
        "wait_time": percentiles(wait),
        "restarts_mean": (float((restarts
                                 * np.arange(RBINS)).sum() / n_commit)
                          if n_commit else float("nan")),
        "abort_causes": {c: int(v) for c, v in zip(ABORT_CAUSES, causes)},
        "block_causes": {c: int(v) for c, v in zip(BLOCK_CAUSES, blocks)},
    }
