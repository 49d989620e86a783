"""Device time per named phase of the simulator's fleet loop, read from
a profiler trace.

    python3 bench/scopes.py --workload <cell> --seed <n> \
        [--trace-seconds 2] [--keep <dir>]

The program names its parts: ``jax.named_scope`` puts ``fleet.<proto>``
around each protocol's lanes, ``fleet.init`` around their set-up and the
seven ``cohort.*`` phases inside the cohort body (``jaxsim._cohort_body``);
``sweep.Fleet.run_lanes`` adds the host spans ``fleet.check`` and
``fleet.dispatch``.  A trace's device events carry only an instruction's
name and text, so the scopes reach the trace through a *scope map* built
from the compiled HLO text of the executable the trace ran:

* ``scope_map(text)`` -- each instruction that can run as a device
  operation -> its ``op_name`` (a fusion takes its root's), the
  protocols whose scope it is under, its region (the outer loop of a
  ``fleet.<proto>`` program, that loop's condition, the lanes' set-up
  ``init``, or none), its innermost ``cohort.*`` scope, and for a fusion
  every ``cohort.*`` scope among the instructions fused into it (more
  than one: a *mixed* fusion);
* ``reduce_planes(planes, smap)`` -- for the ``fleet.ppcc`` loop inside
  the traced window: self time per phase and under no phase (loop
  condition, the vmapped loop's select of finished lanes, copies), its
  op events and iterations (events of one op of the loop condition),
  the set-up's time (its ops that run before the loop's first op; work
  the compiler merged for all protocols counts for each), the share of
  loop time in mixed fusions, the share of device time not in the map,
  and the idle gaps labelled by the innermost ``bench.*`` or ``fleet.*``
  span;
* ``metrics(entry)`` -- the per-iteration numbers, ``None`` where the
  window holds no full iteration.

The command runs a cell as ``bench/run.py --trace 1`` does (set-up, then
a window whose first grid call is traced for ``--trace-seconds``), then
builds the map after the window and prints one JSON line.  ``--keep``
saves the trace, its map and the compiled HLO text.  ``bench/run.py``
does not call this module yet; PERF.md §7 lists what wiring it in takes.
"""
from __future__ import annotations

import argparse
import collections
import gzip
import json
import re
import shutil
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

HERE = Path(__file__).resolve().parent          # bench/
sys.path.insert(0, str(HERE))

import run  # noqa: E402  (sets up sys.path for the program too)
import trace_reduce as TR  # noqa: E402

PROTO = "fleet.ppcc"
PHASES = ("cohort.classify", "cohort.relations", "cohort.step",
          "cohort.leave_begin", "cohort.refill", "cohort.reserve",
          "cohort.transitions")
HOST_SPANS = ("bench.", "fleet.")

SCOPE = re.compile(r"(?<![\w.])((?:cohort|fleet)\.[a-z0-9_]+)")
_COMP = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{$")
_INST = re.compile(r"^\s+(ROOT )?%([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALL = re.compile(r"\b(calls|condition|body|to_apply|true_computation|"
                   r"false_computation)=%([\w.\-]+)")
_CALLS = re.compile(r"\b(calls|branch_computations|called_computations)="
                    r"\{([^}]*)\}")


# ---------------------------------------------------------------- map --

def parse_hlo(text: str):
    """(module name, computations) of an HLO module's text; each
    computation is ``{"insts": [(name, opcode, op_name, calls)],
    "root": name}``, ``calls`` a list of (attribute, computation)."""
    module, comps, cur = "", {}, None
    for line in text.split("\n"):
        if line.startswith("HloModule "):
            module = line.split()[1].rstrip(",")
            continue
        m = _COMP.match(line)
        if m:
            cur = comps.setdefault(m.group(1), {"insts": [], "root": None})
            continue
        if line.startswith("}"):
            cur = None
            continue
        m = _INST.match(line) if cur is not None else None
        if not m:
            continue
        rest = m.group(3)
        op = _OPCODE.search(rest)
        nm = _OP_NAME.search(rest)
        calls = list(_CALL.findall(rest))
        for attr, group in _CALLS.findall(rest):
            calls += [(attr, c.strip().lstrip("%"))
                      for c in group.split(",") if c.strip()]
        cur["insts"].append((m.group(2), op.group(1) if op else "",
                             nm.group(1) if nm else "", calls))
        if m.group(1):
            cur["root"] = m.group(2)
    return module, comps


def scopes_of(op_name: str) -> List[str]:
    """The ``cohort.*`` and ``fleet.*`` scopes on an ``op_name``'s path,
    outermost first (``vmap(fleet.init)`` counts as ``fleet.init``)."""
    return SCOPE.findall(op_name)


def phase_of(op_name: str) -> str:
    """The innermost ``cohort.*`` scope of an ``op_name``, or ''."""
    inner = [s for s in scopes_of(op_name) if s.startswith("cohort.")]
    return inner[-1] if inner else ""


def _fleets(scopes: List[str]) -> List[str]:
    return sorted({s for s in scopes
                   if s.startswith("fleet.") and s != "fleet.init"})


def _reach(comps, start) -> set:
    """Computations reachable from ``start`` through any call."""
    seen, todo = set(), [c for c in start if c]
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        todo += [cc for inst in comps[c]["insts"] for _, cc in inst[3]]
    return seen


def scope_map(text: str) -> dict:
    """The scope map of a compiled module's HLO text: ``{"module",
    "ops": {instruction: {"op_name", "fleets", "region", "scope",
    "scopes"}}}`` for every instruction outside fused and reducer
    computations (the ones that can run as device operations)."""
    module, comps = parse_hlo(text)
    insts = [(c, *i) for c, comp in comps.items() for i in comp["insts"]]
    inner = {cc for _, _, op, _, calls in insts for attr, cc in calls
             if (attr == "calls" and op == "fusion")
             or (attr == "to_apply" and op != "call")}
    # the outer loop of each protocol's program: a while under
    # fleet.<proto>, outside the set-up and the cohort body
    cands = []
    for cname, name, op, op_name, calls in insts:
        sc = scopes_of(op_name)
        if op == "while" and _fleets(sc) and "fleet.init" not in sc and \
                not any(s.startswith("cohort.") for s in sc):
            parts = dict(calls)
            cands.append((_fleets(sc), cname, name, parts.get("condition"),
                          parts.get("body")))
    bodies = [_reach(comps, [c[4]]) for c in cands]
    region: Dict[str, tuple] = {}        # computation -> (fleets, region)
    loop_insts: Dict[str, list] = {}     # the outer while instructions
    for k, (fleets, cname, name, cond, body) in enumerate(cands):
        if any(cname in r for j, r in enumerate(bodies) if j != k):
            continue                     # nested in another candidate
        loop_insts[name] = fleets
        for c in _reach(comps, [body, cond]):
            region[c] = (fleets, "loop")
        for c in _reach(comps, [cond]):
            region[c] = (fleets, "cond")
    # the set-up: everything an instruction under fleet.init calls (the
    # compiler may merge the protocols' identical set-up work: then the
    # op_name lists each of them)
    setup: Dict[str, set] = {}
    for cname, name, op, op_name, calls in insts:
        sc = scopes_of(op_name)
        if cname not in region and "fleet.init" in sc:
            for c in _reach(comps, [cc for _, cc in calls]):
                setup.setdefault(c, set()).update(_fleets(sc))
    for c, fleets in setup.items():
        region.setdefault(c, (sorted(fleets), "init"))
    ops = {}
    for cname, name, op, op_name, calls in insts:
        if cname in inner:
            continue
        sc = scopes_of(op_name)
        fleets, reg = region.get(cname, ([], ""))
        if name in loop_insts:
            fleets, reg = loop_insts[name], "loop"
        elif reg == "init":
            fleets = sorted(set(fleets) | set(_fleets(sc)))
        elif not reg:
            fleets = _fleets(sc)
            reg = "init" if "fleet.init" in sc else ""
        root_name, fused = op_name, set()
        called = [cc for attr, cc in calls if attr == "calls"]
        if op == "fusion" and called and called[0] in comps:
            for c in _reach(comps, called):
                fused |= {phase_of(i[2]) for i in comps[c]["insts"]} - {""}
            body = comps[called[0]]
            root_name = next((i[2] for i in body["insts"]
                              if i[0] == body["root"] and i[2]), op_name)
        ops[name] = {"op_name": root_name, "fleets": list(fleets),
                     "region": reg, "scope": phase_of(root_name),
                     "scopes": sorted(fused)}
    return {"module": module, "ops": ops}


# ---------------------------------------------------------- reduction --

def host_spans(planes) -> list:
    """(start, end, name) of the host spans ``bench.*`` and ``fleet.*``."""
    return [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
            for pl in planes if pl.name.startswith("/host:")
            for ln in pl.lines for ev in ln.events
            if ev.name.startswith(HOST_SPANS)]


def module_runs(planes, device: str, module: str) -> list:
    """(start, end) of each run of ``module`` on ``device``."""
    return [(ev.start_ns, ev.start_ns + ev.duration_ns)
            for pl in planes if pl.name == device
            for ln in pl.lines if ln.name == "XLA Modules"
            for ev in ln.events if ev.name.split("(", 1)[0] == module]


def self_times_each(events) -> List[float]:
    """Self time of each of possibly nested (start, end, name) events,
    in their order (``trace_reduce.self_times`` sums them by name)."""
    out = [0.0] * len(events)
    stack: List[list] = []          # [end, index, child_time]

    def pop():
        end, i, child = stack.pop()
        dur = events[i][1] - events[i][0]
        out[i] = dur - child
        if stack:
            stack[-1][2] += dur

    for i in sorted(range(len(events)),
                    key=lambda k: (events[k][0], -events[k][1])):
        while stack and stack[-1][0] <= events[i][0]:
            pop()
        stack.append([events[i][1], i, 0.0])
    while stack:
        pop()
    return out


def reduce_planes(planes, smap: dict) -> dict:
    """The PPCC loop's device time inside the traced window, per phase,
    on the first device (see the module's docstring)."""
    planes = list(planes)
    spans = host_spans(planes)
    wins = [(s, e) for s, e, n in spans if n == TR.WINDOW_SPAN]
    if not wins:
        raise ValueError(f"trace has no {TR.WINDOW_SPAN} span")
    lo, hi = wins[0]
    devs = TR.device_ops(planes)
    if not devs:
        raise ValueError("trace has no device operations")
    device = sorted(devs)[0]
    evs = [(max(s, lo), min(e, hi), n) for s, e, n in devs[device]
           if e > lo and s < hi]
    runs = module_runs(planes, device, smap["module"])
    inside = [ev for ev in evs if any(a <= ev[0] < b for a, b in runs)]
    own = self_times_each(inside)
    total = sum(TR.self_times(evs).values())
    ops = smap["ops"]
    region = [ops[n]["region"] if n in ops and PROTO in ops[n]["fleets"]
              else "" for _, _, n in inside]
    in_loop = [r in ("loop", "cond") for r in region]
    first_loop = min((ev[0] for ev, x in zip(inside, in_loop) if x),
                     default=None)
    phases = {p: 0.0 for p in PHASES}
    other = mixed = init = mapped = 0.0
    per_name: Dict[str, float] = collections.defaultdict(float)
    count: Dict[str, int] = collections.Counter()
    init_end = None
    for (s, e, name), t, reg in zip(inside, own, region):
        if name not in ops:
            continue
        mapped += t
        o = ops[name]
        if reg in ("loop", "cond"):
            per_name[name] += t
            count[name] += 1
            if o["scope"] in phases:
                phases[o["scope"]] += t
            else:
                other += t
            if len(o["scopes"]) > 1:
                mixed += t
        elif reg == "init" and (first_loop is None or s < first_loop):
            # the set-up up to the loop: what runs later belongs to the
            # set-up of protocols scheduled after it
            init += t
            init_end = e if init_end is None else max(init_end, e)
    # iterations: events of one op of the loop condition (the one seen
    # most); the last one ends the loop unless a body op follows it
    cond = sorted({n for (_, _, n), r in zip(inside, region)
                   if r == "cond"})
    tick = max(cond, key=lambda n: count[n], default=None)
    conds = sorted(s for s, _, n in inside if n == tick)
    last_body = max((ev[0] for ev, r in zip(inside, region)
                     if r == "loop"), default=None)
    iters = len(conds)
    if conds and (last_body is None or last_body < conds[-1]):
        iters -= 1
    n_ops = sum(count.values())
    init_whole = bool(init_end is not None and first_loop is not None
                      and any(lo <= a for a, _ in runs))
    loop = sum(phases.values()) + other
    busy = TR.union([(s, e) for s, e, _ in evs])
    gaps, prev = [], lo
    for s, e in busy:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    if hi > prev:
        gaps.append((prev, hi))
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    top = sorted(per_name.items(), key=lambda kv: kv[1], reverse=True)[:10]
    return {
        "window_s": (hi - lo) * 1e-9,
        "phases_s": {p: t * 1e-9 for p, t in phases.items()},
        "other_s": other * 1e-9,
        "loop_s": loop * 1e-9,
        "loop_ops": n_ops,
        "iters": iters,
        "init_s": init * 1e-9,
        "init_whole": init_whole,
        "mixed_share": mixed / loop if loop else None,
        "unmapped_share": 1.0 - mapped / total if total else None,
        "top_loop_ops": [[n, t * 1e-9, ops[n]["scope"], ops[n]["scopes"]]
                         for n, t in top],
        "idle_gaps": [[TR.label_gap(g, spans), (g[1] - g[0]) * 1e-9]
                      for g in gaps[:10]],
    }


def metrics(r: dict) -> Dict[str, Optional[float]]:
    """Per-iteration numbers of a ``reduce_planes`` entry: the loop's
    device time and op events, each phase's time, the time under no
    phase; and the set-up's time when the window holds all of it."""
    full = r["iters"] >= 1

    def per_iter(x, scale=1e3):
        return x / r["iters"] * scale if full else None

    out = {"cohort.ms_per_iter": per_iter(r["loop_s"]),
           "cohort.ops_per_iter": per_iter(r["loop_ops"], 1)}
    for p in PHASES:
        out[f"{p}.ms_per_iter"] = per_iter(r["phases_s"][p])
    out["cohort.other.ms_per_iter"] = per_iter(r["other_s"])
    out["fleet.init_ms"] = r["init_s"] * 1e3 if r["init_whole"] else None
    return out


# ------------------------------------------------------------ command --

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--trace-seconds", type=float, default=None,
                    help="how long the first grid call is traced "
                         "(default: the driver's own)")
    ap.add_argument("--keep", type=Path, default=None,
                    help="save the trace, its scope map and the compiled "
                         "HLO text here")
    args = ap.parse_args(argv)
    try:
        out = measure(args.workload, args.seed, args.trace_seconds,
                      args.keep)
    except run.BenchError as e:
        print(f"scopes: {e}", file=sys.stderr)
        return 1
    print(json.dumps(out), flush=True)
    return 0


def measure(name: str, seed: int, trace_seconds: Optional[float] = None,
            keep: Optional[Path] = None, root: Path = run.ROOT) -> dict:
    """Set up cell ``name``, trace the first grid call of a one-call
    window, map and reduce the trace; the times of the map and the
    reductions are part of the result."""
    cell = run.Cell(name, root)
    import jax
    dev = run.device_info(jax)
    if dev["platform"] != "tpu":
        raise run.BenchError(f"no TPU: JAX's first device is "
                             f"{dev['platform']}")
    run.enable_cache(jax)
    drv = cell.driver()
    if trace_seconds is not None:
        drv.TRACE_SECONDS = trace_seconds
    ctx = drv.setup(cell, cell.generator(), seed,
                    lambda n: run.span(jax, n))
    tracer = run.Tracer(jax, True, root / ".bench_trace" / f"{name}.scopes")
    drv.window(ctx, 0.0, tracer)
    t0 = time.perf_counter()
    text = ctx.fleet._jit.lower(drv.lane_seed_vector(ctx, 0), ctx.mpl_l,
                                ctx.rt_l).compile().as_text()
    smap = scope_map(text)
    t1 = time.perf_counter()
    files = sorted(tracer.dir.rglob("*.xplane.pb"))
    if not files:
        raise run.BenchError(f"no trace written under {tracer.dir}")
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(str(files[-1])).planes)
    t2 = time.perf_counter()
    base = TR.reduce_planes(planes, drv.KERNELS)
    t3 = time.perf_counter()
    r = reduce_planes(planes, smap)
    t4 = time.perf_counter()
    if keep is not None:
        keep.mkdir(parents=True, exist_ok=True)
        shutil.copy(files[-1], keep / "trace.xplane.pb")
        with gzip.open(keep / "scope_map.json.gz", "wt") as f:
            json.dump(smap, f)
        with gzip.open(keep / "hlo.txt.gz", "wt") as f:
            f.write(text)
    shutil.rmtree(tracer.dir, ignore_errors=True)
    return {
        "metrics": metrics(r),
        "scopes": r,
        "kernels": base["kernels"],
        "base": {k: base[k] for k in ("busy_s", "window_s", "idle_gaps")},
        "top_ops": [[n, t, *[smap["ops"].get(n, {}).get(k) for k in
                             ("region", "fleets", "scope", "scopes")]]
                    for n, t in base["top_ops"]],
        "seconds": {"scope_map": t1 - t0, "load_trace": t2 - t1,
                    "reduce": t3 - t2, "reduce_scopes": t4 - t3},
        "device": dev,
    }


if __name__ == "__main__":
    sys.exit(main())
