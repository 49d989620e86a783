"""Device time per named phase (``bench/scopes.py``): the scope map of a
small hand-written HLO module, the per-phase reduction on hand-made
planes with known answers, and both on a short trace of the tiny sim
cell recorded on a TPU v5e with its scope map (``data/``)."""
import gzip
import json
from types import SimpleNamespace as NS

import pytest

from bench_test_util import DATA

import scopes as S
import trace_reduce as TR

HLO = """HloModule jit_fleet_fn, is_scheduled=true

%add (a: s32[], b: s32[]) -> s32[] {
  %a = s32[] parameter(0)
  %b = s32[] parameter(1)
  ROOT %add.1 = s32[] add(%a, %b), metadata={op_name="reduce_sum"}
}

%fused_computation.1 (p: s32[4]) -> s32[4] {
  %p = s32[4] parameter(0)
  %neg.1 = s32[4] negate(%p), metadata={op_name="jit(fleet_fn)/fleet.ppcc/vmap()/while/body/jit(<unknown>)/cohort.classify/neg"}
  ROOT %abs.1 = s32[4] abs(%neg.1), metadata={op_name="jit(fleet_fn)/fleet.ppcc/vmap()/while/body/jit(<unknown>)/cohort.step/abs"}
}

%fused_computation.2 (p: s32[4]) -> s32[] {
  %p.1 = s32[4] parameter(0)
  %c = s32[] constant(0)
  ROOT %reduce.1 = s32[] reduce(%p.1, %c), dimensions={0}, to_apply=%add, metadata={op_name="jit(fleet_fn)/fleet.ppcc/vmap()/while/body/jit(<unknown>)/cohort.reserve/reduce_sum"}
}

%cond.1 (s: (s32[4])) -> pred[] {
  %s = (s32[4]) parameter(0)
  %g = s32[4] get-tuple-element(%s), index=0
  ROOT %lt.1 = pred[] fusion(%g), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(fleet_fn)/fleet.ppcc/vmap()/while/body_pred/lt"}
}

%body.1 (s.1: (s32[4])) -> (s32[4]) {
  %s.1 = (s32[4]) parameter(0)
  %g.1 = s32[4] get-tuple-element(%s.1), index=0
  %fusion.7 = s32[4] fusion(%g.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(fleet_fn)/fleet.ppcc/vmap()/while/body/jit(<unknown>)/cohort.step/abs"}
  %copy.3 = s32[4] copy(%fusion.7)
  ROOT %tuple.2 = (s32[4]) tuple(%copy.3)
}

ENTRY %main.9 (seed_l.1: s32[4]) -> (s32[4]) {
  %seed_l.1 = s32[4] parameter(0)
  %init.5 = s32[4] negate(%seed_l.1), metadata={op_name="jit(fleet_fn)/fleet.ppcc/vmap(fleet.init)/jit(fleet_fn)/fleet.2pl/vmap(fleet.init)/neg"}
  %tuple.1 = (s32[4]) tuple(%init.5)
  %while.4 = (s32[4]) while(%tuple.1), condition=%cond.1, body=%body.1, metadata={op_name="jit(fleet_fn)/fleet.ppcc/vmap()/while"}
  ROOT %tuple.3 = (s32[4]) tuple(%while.4)
}
"""


def test_scope_map_of_a_module():
    m = S.scope_map(HLO)
    ops = m["ops"]
    assert m["module"] == "jit_fleet_fn"
    # fused and reducer computations hold no device operations
    assert not {"neg.1", "abs.1", "reduce.1", "add.1"} & set(ops)
    # a fusion takes its root's op_name and lists every fused scope
    assert ops["fusion.7"]["scope"] == "cohort.step"
    assert ops["fusion.7"]["scopes"] == ["cohort.classify", "cohort.step"]
    assert ops["fusion.7"]["region"] == "loop"
    assert ops["fusion.7"]["fleets"] == ["fleet.ppcc"]
    # instructions with no metadata take their computation's region
    assert ops["copy.3"]["region"] == "loop" and ops["copy.3"]["scope"] == ""
    assert ops["lt.1"]["region"] == "cond"
    assert ops["lt.1"]["scopes"] == ["cohort.reserve"]
    assert ops["while.4"]["region"] == "loop"
    # set-up work the compiler merged across protocols belongs to each
    assert ops["init.5"]["region"] == "init"
    assert ops["init.5"]["fleets"] == ["fleet.2pl", "fleet.ppcc"]
    assert ops["tuple.3"]["region"] == ""


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


SMAP = {"module": "jit_fleet_fn", "ops": {
    "cond.1": {"fleets": ["fleet.ppcc"], "region": "cond", "scope": "",
               "scopes": []},
    "cond.2": {"fleets": ["fleet.ppcc"], "region": "cond", "scope": "",
               "scopes": []},
    "fusion.1": {"fleets": ["fleet.ppcc"], "region": "loop",
                 "scope": "cohort.classify", "scopes": ["cohort.classify"]},
    "fusion.2": {"fleets": ["fleet.ppcc"], "region": "loop",
                 "scope": "cohort.step",
                 "scopes": ["cohort.step", "cohort.transitions"]},
    "copy.1": {"fleets": ["fleet.ppcc"], "region": "loop", "scope": "",
               "scopes": []},
    "init.1": {"fleets": ["fleet.2pl", "fleet.ppcc"], "region": "init",
               "scope": "", "scopes": []},
    "fusion.9": {"fleets": ["fleet.2pl"], "region": "loop",
                 "scope": "cohort.step", "scopes": ["cohort.step"]},
}}


def planes():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.traced_window", 0, 1000),
        ev("bench.grid_call", 0, 1000),
        ev("fleet.check", 0, 140),
        ev("fleet.dispatch", 140, 10)])])
    ops = [ev("max.1", 20, 20),              # another module's op
           ev("init.1", 150, 100)]
    for k, t in enumerate((250, 370, 490)):   # three iterations
        ops += [ev("cond.1", t, 5), ev("cond.2", t + 5, 5),
                ev("fusion.1", t + 10, 40)]
        if k < 2:
            ops += [ev("fusion.2", t + 50, 60), ev("copy.1", t + 110, 10)]
    ops += [ev("unknown.7", 900, 50),         # not in the map
            ev("fusion.9", 950, 20)]          # another protocol's loop
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_max(1)", 20, 20),
                                       ev("jit_fleet_fn(7)", 150, 850)]),
        NS(name="XLA Ops", events=ops)])
    return [host, dev]


def test_reduce_planes_known_answers():
    r = S.reduce_planes(planes(), SMAP)
    ph = r["phases_s"]
    assert ph["cohort.classify"] == pytest.approx(120e-9)
    assert ph["cohort.step"] == pytest.approx(120e-9)
    assert ph["cohort.reserve"] == 0.0
    # the condition and the copies fall under no phase
    assert r["other_s"] == pytest.approx(50e-9)
    # phases and other add up to the loop's self time exactly
    assert sum(ph.values()) + r["other_s"] == r["loop_s"]
    assert r["loop_s"] == pytest.approx(290e-9)
    assert r["iters"] == 3                    # the loop runs on: no -1
    assert r["loop_ops"] == 13             # both condition ops count
    # the set-up is not loop time, and all of it lies in the window
    assert r["init_s"] == pytest.approx(100e-9)
    assert r["init_whole"] is True
    assert r["mixed_share"] == pytest.approx(120 / 290)
    # max.1 ran outside the fleet's module, unknown.7 is not in the map
    assert r["unmapped_share"] == pytest.approx(70 / 480)
    gaps = dict((round(d * 1e9), lab) for lab, d in r["idle_gaps"])
    assert gaps[20] == "fleet.check"          # before the first op
    assert gaps[110] == "fleet.check"         # innermost covering span
    assert gaps[360] == "bench.grid_call"
    m = S.metrics(r)
    assert m["cohort.ms_per_iter"] == pytest.approx(290e-9 / 3 * 1e3)
    assert m["cohort.ops_per_iter"] == pytest.approx(13 / 3)
    assert m["fleet.init_ms"] == pytest.approx(100e-6)
    parts = [m[f"{p}.ms_per_iter"] for p in S.PHASES]
    assert sum(parts) + m["cohort.other.ms_per_iter"] == \
        pytest.approx(m["cohort.ms_per_iter"], rel=1e-12)


def test_a_loop_that_ended_counts_its_last_condition_once():
    p = planes()
    ops = p[1].lines[1].events
    ops[:] = [e for e in ops if not (e.name == "fusion.1"
                                     and e.start_ns == 500)]
    r = S.reduce_planes(p, SMAP)
    assert r["iters"] == 2


def test_no_iteration_reads_none():
    p = planes()
    ops = p[1].lines[1].events
    ops[:] = [e for e in ops if e.name in ("max.1", "init.1")]
    m = S.metrics(S.reduce_planes(p, SMAP))
    assert m["cohort.ms_per_iter"] is None
    assert m["cohort.step.ms_per_iter"] is None
    assert m["fleet.init_ms"] is None     # no loop op: end not seen


def test_gap_labels_without_fleet_spans_are_the_reducers():
    p = planes()
    p[0].lines[0].events = [e for e in p[0].lines[0].events
                            if not e.name.startswith("fleet.")]
    r = S.reduce_planes(p, SMAP)
    base = TR.reduce_planes(p, {})
    assert r["idle_gaps"] == base["idle_gaps"]


def recorded_sim():
    """The tiny sim cell of ``test_bench_harness_sim.py`` (its
    ``new_cell_root`` with ``data/tiny-sim.json`` at horizon 100), traced
    on a TPU v5e by ``scopes.measure("tiny-grid", 2**31 + 977, 0.5,
    keep, root)``: the trace, the compiled HLO text (source locations
    removed) and its scope map."""
    import lzma
    from jax.profiler import ProfileData
    raw = lzma.open(DATA / "tiny_sim.xplane.pb.xz").read()
    planes = list(ProfileData.from_serialized_xspace(raw).planes)
    with gzip.open(DATA / "tiny_sim.hlo.txt.gz", "rt") as f:
        text = f.read()
    with gzip.open(DATA / "tiny_sim.scope_map.json.gz", "rt") as f:
        smap = json.load(f)
    return planes, text, smap


def test_recorded_sim_trace_maps_and_counts_iterations():
    planes, text, smap = recorded_sim()
    assert S.scope_map(text) == smap
    r = S.reduce_planes(planes, smap)
    assert r["unmapped_share"] <= 0.01
    base = TR.reduce_planes(planes, {"megastep": "megastep"})
    assert r["iters"] == base["kernels"]["megastep"]["count"] > 0
    assert sum(r["phases_s"].values()) + r["other_s"] == r["loop_s"] > 0
    assert r["init_whole"] and r["init_s"] > 0
    # every phase the ppcc loop runs on the chip has device time there
    for p in S.PHASES:
        if p != "cohort.leave_begin":
            assert r["phases_s"][p] > 0, p
    # the host spans of run_lanes name the gaps they cover
    assert {"fleet.check", "fleet.dispatch"} & \
        {lab for lab, _ in r["idle_gaps"]}


@pytest.mark.parametrize("name", ["tiny_tick.xplane.pb",
                                  "tiny_sim.xplane.pb.xz"])
def test_reducer_reads_the_recorded_traces_as_before(name):
    """``trace_reduce`` gives what it gave before the scopes existed."""
    golden = json.loads((DATA / "reduce_golden.json").read_text())[name]
    if name.endswith(".xz"):
        r = TR.reduce_planes(recorded_sim()[0], {"megastep": "megastep"})
    else:
        r = TR.reduce(DATA / name, {"conflict": "conflict_fused"})
    assert json.loads(json.dumps(r)) == golden
