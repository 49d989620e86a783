"""The trace reduction: on hand-made planes with known answers, and on
a small trace recorded on a TPU v5e (three admission ticks of
``scheduler.tick`` at 256 pending rows, checked in under ``data/``)."""
from types import SimpleNamespace as NS

import pytest

from bench_test_util import DATA

import trace_reduce as TR


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def planes():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.traced_window", 0, 1000),
        ev("bench.tick_call", 0, 500),
        ev("bench.refill", 600, 300),
        ev("other", 0, 1000)])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_tick", 100, 400)]),
        NS(name="XLA Ops", events=[
            ev("while", 100, 300), ev("conflict_fused_kernel", 120, 50),
            ev("fusion.1", 200, 100), ev("fusion.1", 450, 50),
            ev("copy", 950, 200)])])
    return [host, dev]


def test_reduce_planes_known_answers():
    r = TR.reduce_planes(planes(), {"conflict": "conflict_fused"})
    # busy: [100, 400) + [450, 500) + [950, 1000) clipped to the window
    assert r["busy_s"] == pytest.approx(400e-9)
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["kernels"]["conflict"] == {"count": 1,
                                        "seconds": pytest.approx(50e-9)}
    top = dict(r["top_ops"])
    assert top["while"] == pytest.approx(150e-9)      # self time
    assert top["fusion.1"] == pytest.approx(150e-9)
    gaps = r["idle_gaps"]
    assert gaps[0] == ["bench.refill", pytest.approx(450e-9)]
    assert gaps[1] == ["bench.tick_call", pytest.approx(100e-9)]


def test_union_and_self_times():
    assert TR.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    st = TR.self_times([(0, 10, "a"), (2, 4, "b"), (4, 6, "b")])
    assert st == {"a": 6, "b": 4}


def test_window_span_is_required():
    p = planes()
    p[0].lines[0].events = p[0].lines[0].events[1:]
    with pytest.raises(ValueError):
        TR.reduce_planes(p, {})


def test_recorded_tpu_trace():
    path = DATA / "tiny_tick.xplane.pb"
    r = TR.reduce(path, {"conflict": "conflict_fused"})
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["devices"] == 1
    assert r["kernels"]["conflict"]["count"] >= 1
    assert r["kernels"]["conflict"]["seconds"] > 0
    assert len(r["top_ops"]) == 10
    labels = {g[0] for g in r["idle_gaps"]}
    assert labels <= {"bench.traced_window", "bench.tick_call",
                      "bench.refill"}
    assert labels & {"bench.tick_call", "bench.refill"}
