"""The named scopes of the fleet program (``fleet.<protocol>``,
``fleet.init``, ``cohort.*``) on the CPU: they reach the compiled HLO's
``op_name`` metadata of the PPCC loop, and nothing else — the program
compiled without them is the same text once metadata is stripped.

On the CPU the relations phase holds the megakernel's jnp twin
(``compute_relations`` and ``relations_inputs``); the megakernel that
holds it on a TPU is not compiled here."""
import contextlib
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.core import jaxsim, sweep
from repro.core.types import SimParams

CONFIG = Path(__file__).resolve().parents[1] / "bench/tests/data/tiny-sim.json"
PHASES = {"cohort.classify", "cohort.relations", "cohort.step",
          "cohort.leave_begin", "cohort.refill", "cohort.reserve",
          "cohort.transitions"}
COMP = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{$")
INST = re.compile(r"^\s+(?:ROOT )?%([\w.\-]+) = .*?\s([a-z][a-z0-9\-]*)\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')
CALLS = re.compile(r"\b(calls|condition|body|to_apply)=%([\w.\-]+)")


def compiled_text() -> str:
    cfg = json.loads(CONFIG.read_text())
    p = SimParams(**cfg["table1"], **cfg["figures"]["6"], mpl=20,
                  horizon=200.0)
    fleet = sweep.Fleet(p, protocols=("ppcc",))
    rt = jax.tree.map(lambda x: jnp.broadcast_to(x, (2,)), jaxsim.rt_of(p))
    seeds = jnp.zeros(2, jnp.int32)
    return fleet._jit.lower(seeds, seeds + 20, rt).compile().as_text()


def strip(text: str) -> str:
    """The program without metadata and the header's stack-frame
    tables (which name source files and lines)."""
    out, table = [], False
    for line in text.split("\n"):
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            table = True
        elif table and not line:
            table = False
        elif not table:
            out.append(line)
    return re.sub(r", metadata=\{[^}]*\}", "", "\n".join(out))


@pytest.fixture(scope="module")
def texts():
    with_scopes = compiled_text()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        without = compiled_text()
    return with_scopes, without


def computations(text: str) -> dict:
    comps, cur = {}, None
    for line in text.split("\n"):
        m = COMP.match(line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            m = INST.match(line)
            if m:
                nm = OP_NAME.search(line)
                cur.append((m.group(1), m.group(2), nm.group(1) if nm else "",
                            CALLS.findall(line)))
    return comps


def test_scopes_change_only_metadata(texts):
    with_scopes, without = texts
    assert "cohort.step" in with_scopes and "cohort.step" not in without
    assert strip(with_scopes) == strip(without)


def test_ppcc_loop_runs_under_its_scopes(texts):
    """Every instruction that runs as a device operation in the PPCC
    loop (outside fused and reducer computations, whose instructions
    may carry a name relative to the op they belong to) is under
    ``fleet.ppcc/``, and the loop holds all seven phases."""
    comps = computations(texts[0])
    loops = [calls for insts in comps.values()
             for _, op, op_name, calls in insts
             if op == "while" and op_name.endswith("fleet.ppcc/vmap()/while")]
    assert len(loops) == 1
    parts = dict(loops[0])
    seen, todo, phases = set(), [parts["body"], parts["condition"]], set()
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.add(c)
        for name, op, op_name, calls in comps[c]:
            for attr, cc in calls:
                if attr in ("body", "condition") or \
                        (attr == "calls" and op != "fusion"):
                    todo.append(cc)
                elif op == "fusion":
                    phases |= {p for _, _, n, _ in comps[cc]
                               for p in re.findall(r"cohort\.[a-z_]+", n)}
            if op_name:
                assert op_name.startswith("jit(fleet_fn)/fleet.ppcc/"), \
                    (name, op_name)
                phases |= set(re.findall(r"cohort\.[a-z_]+", op_name))
    assert phases == PHASES


TICK_SCOPES = {"tick.conflict", "tick.scan", "tick.commit_order"}


def tick_text() -> str:
    """The keyed PPCC tick at 32 rows of 4-key lists, compiled."""
    from repro.sched import scheduler
    keys = jax.ShapeDtypeStruct((32, 4), jnp.int32)
    valid = jax.ShapeDtypeStruct((32,), jnp.bool_)
    fn = jax.jit(lambda r, w, v: scheduler.ppcc_tick(r, w, v, keys=True))
    return fn.lower(keys, keys, valid).compile().as_text()


def test_tick_scopes_change_only_metadata():
    """``ppcc_tick``'s three parts run under ``tick.conflict``,
    ``tick.scan`` (the admission scan's kernel, interpreted on the CPU)
    and ``tick.commit_order``; without the scopes the program is the
    same."""
    with_scopes = tick_text()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        without = tick_text()
    assert strip(with_scopes) == strip(without)
    names = set(OP_NAME.findall(with_scopes))
    seen = {s for s in TICK_SCOPES if any(f"/{s}/" in n for n in names)}
    assert seen == TICK_SCOPES
    assert any("tick.scan/jit(ppcc_admit_scan)/ppcc_admit_scan/" in n
               for n in names)
    assert not any(s in without for s in TICK_SCOPES)
