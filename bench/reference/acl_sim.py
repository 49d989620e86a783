"""Plain event-driven simulator of the paper's Table 1 model.

The reference that decides ``correct`` for the simulator cells: a
closed system at a constant multiprogramming level (MPL) with FCFS CPU
and disk pools (after Agrawal, Carey and Livny), under three
concurrency-control protocols:

* ``ppcc`` -- Prudent Precedence (the paper, Section 2): a reader may
  precede a writer of the same item unless that would make a preceded
  transaction preceding or a preceding one preceded; the read phase
  ends with wait-to-commit (exclusive locks on the write set, then a
  wait for every predecessor to leave);
* ``2pl`` -- strict two-phase locking with shared/exclusive locks;
* ``occ`` -- Kung-Robinson backward validation at the end of the read
  phase.

A blocked transaction aborts after ``block_timeout``; an aborted one
restarts its own operations after a random delay; a committed one is
replaced at once by a fresh transaction in the same slot.

It imports nothing of the system under test.  Same model, its own
random streams and its own tie-breaking: it agrees with the compiled
engine in distribution, not event for event.
"""
from __future__ import annotations

import dataclasses
import heapq
import itertools
from collections import deque
from typing import Dict, List, Set, Tuple

import numpy as np

PROCEED, BLOCK, ABORT = "proceed", "block", "abort"
READ, WRITE = 0, 1


@dataclasses.dataclass(frozen=True)
class Params:
    """One lane of the model: Table 1 settings plus MPL, horizon, seed."""
    db_size: int
    txn_size_mean: int
    txn_size_spread: int
    write_prob: float
    num_cpus: int
    num_disks: int
    cpu_burst_mean: float
    cpu_burst_spread: float
    io_time_mean: float
    io_time_spread: float
    block_timeout: float
    restart_delay_mean: float
    mpl: int
    horizon: float
    seed: int


# --------------------------------------------------------------------------
# workload
# --------------------------------------------------------------------------

def sample_ops(rng: np.random.Generator, p: Params) -> List[Tuple[int, int]]:
    """One transaction: (kind, item) ops.  Length uniform on
    [max(2, mean - spread), mean + spread]; each op is, with probability
    ``write_prob``, a write of an item read before and not yet written
    (a read when there is none), otherwise a read of an unread item."""
    lo = max(2, p.txn_size_mean - p.txn_size_spread)
    hi = p.txn_size_mean + p.txn_size_spread
    length = int(rng.integers(lo, hi + 1))
    ops: List[Tuple[int, int]] = []
    read_items: List[int] = []
    written: set = set()
    for _ in range(length):
        want_write = rng.random() < p.write_prob
        avail = [x for x in read_items if x not in written]
        if want_write and avail:
            item = avail[int(rng.integers(len(avail)))]
            written.add(item)
            ops.append((WRITE, item))
        else:
            for _ in range(64):
                item = int(rng.integers(p.db_size))
                if item not in read_items:
                    break
            read_items.append(item)
            ops.append((READ, item))
    return ops


class Txn:
    """One slot's transaction; ``epoch`` invalidates stale events."""

    __slots__ = ("slot", "ops", "ip", "read_set", "write_set", "state",
                 "epoch", "block_epoch", "start_ts", "preceding",
                 "preceded", "pred", "succ", "flush_left", "timeout_epoch")

    def __init__(self, slot: int, ops, now: float):
        self.slot = slot
        self.ops = ops
        self.epoch = 0
        self.reset(now)

    def reset(self, now: float) -> None:
        self.ip = 0
        self.read_set: Set[int] = set()
        self.write_set: Set[int] = set()
        self.state = "start"
        self.epoch += 1
        self.block_epoch = 0
        self.timeout_epoch = -1
        self.start_ts = now
        self.preceding = False
        self.preceded = False
        self.pred: Set["Txn"] = set()
        self.succ: Set["Txn"] = set()
        self.flush_left = 0


class Pool:
    """FCFS multi-server resource (CPUs or disks)."""

    def __init__(self, n: int):
        self.free = n
        self.queue: deque = deque()

    def request(self, sim: "Sim", t: Txn, dur: float, tag: str) -> None:
        if self.free > 0:
            self.free -= 1
            sim.schedule(sim.now + dur, tag, t)
        else:
            self.queue.append((t, t.epoch, dur, tag))

    def release(self, sim: "Sim") -> None:
        self.free += 1
        while self.queue:
            t, epoch, dur, tag = self.queue.popleft()
            if t.epoch != epoch:
                continue
            self.free -= 1
            sim.schedule(sim.now + dur, tag, t)
            break


# --------------------------------------------------------------------------
# protocols
# --------------------------------------------------------------------------

class Protocol:
    def __init__(self, sim: "Sim"):
        self.sim = sim

    def try_op(self, t: Txn, kind: int, x: int) -> str:
        raise NotImplementedError

    def on_read_done(self, t: Txn) -> str:
        """'flush', 'wait' (parked by the protocol) or 'validate_fail'."""
        raise NotImplementedError

    def on_leave(self, t: Txn) -> None:
        """Called when ``t`` commits or aborts."""
        raise NotImplementedError


class PPCC(Protocol):
    def __init__(self, sim: "Sim"):
        super().__init__(sim)
        self.readers: Dict[int, Set[Txn]] = {}
        self.writers: Dict[int, Set[Txn]] = {}
        self.locks: Dict[int, Txn] = {}
        self.lock_wait: List[Txn] = []
        self.prec_wait: List[Txn] = []

    @staticmethod
    def _arc(a: Txn, b: Txn) -> None:        # a precedes b
        a.succ.add(b)
        b.pred.add(a)
        a.preceding = True
        b.preceded = True

    def try_op(self, t: Txn, kind: int, x: int) -> str:
        owner = self.locks.get(x)
        if owner is not None and owner is not t:
            return ABORT if owner in t.succ else BLOCK
        if kind == READ:
            new = [j for j in self.writers.get(x, ())
                   if j is not t and j not in t.succ]
            if new:
                if t.preceded or any(j.preceding for j in new):
                    return BLOCK
                for j in new:
                    self._arc(t, j)
            t.read_set.add(x)
            self.readers.setdefault(x, set()).add(t)
            return PROCEED
        new = [j for j in self.readers.get(x, ())
               if j is not t and j not in t.pred]
        if new:
            if t.preceding or any(j.preceded for j in new):
                return BLOCK
            for j in new:
                self._arc(j, t)
        t.write_set.add(x)
        self.writers.setdefault(x, set()).add(t)
        return PROCEED

    def on_read_done(self, t: Txn) -> str:
        return self._try_locks(t)

    def _free_for(self, t: Txn) -> bool:
        return all(self.locks.get(x) in (None, t) for x in t.write_set)

    def _try_locks(self, t: Txn) -> str:
        if self._free_for(t):
            for x in t.write_set:
                self.locks[x] = t
            return self._try_commit(t)
        if t not in self.lock_wait:
            self.lock_wait.append(t)
        t.state = "lock_wait"
        return "wait"

    def _try_commit(self, t: Txn) -> str:
        if t.pred:
            if t not in self.prec_wait:
                self.prec_wait.append(t)
            t.state = "prec_wait"
            return "wait"
        if t in self.prec_wait:
            self.prec_wait.remove(t)
        return "flush"

    def on_leave(self, t: Txn) -> None:
        for x in t.read_set:
            self.readers.get(x, set()).discard(t)
        for x in t.write_set:
            self.writers.get(x, set()).discard(t)
            if self.locks.get(x) is t:
                del self.locks[x]
        for j in t.succ:
            j.pred.discard(t)
        for j in t.pred:
            j.succ.discard(t)
        t.succ.clear()
        t.pred.clear()
        for q in (self.lock_wait, self.prec_wait):
            if t in q:
                q.remove(t)
        # wake: lock waiters first (FCFS), then cleared predecessors,
        # then blocked read-phase transactions
        for w in list(self.lock_wait):
            if w.state != "lock_wait":
                self.lock_wait.remove(w)
            elif self._free_for(w):
                self.lock_wait.remove(w)
                if self._try_locks(w) == "flush":
                    self.sim.start_flush(w)
        for w in list(self.prec_wait):
            if w.state != "prec_wait":
                self.prec_wait.remove(w)
            elif not w.pred:
                self.prec_wait.remove(w)
                self.sim.start_flush(w)
        self.sim.retry_blocked()


class TwoPL(Protocol):
    def __init__(self, sim: "Sim"):
        super().__init__(sim)
        self.shared: Dict[int, Set[Txn]] = {}
        self.exclusive: Dict[int, Txn] = {}

    def try_op(self, t: Txn, kind: int, x: int) -> str:
        xh = self.exclusive.get(x)
        if xh is not None and xh is not t:
            return BLOCK
        if kind == READ:
            self.shared.setdefault(x, set()).add(t)
            t.read_set.add(x)
            return PROCEED
        if any(j is not t for j in self.shared.get(x, ())):
            return BLOCK
        self.exclusive[x] = t
        t.write_set.add(x)
        return PROCEED

    def on_read_done(self, t: Txn) -> str:
        return "flush"

    def on_leave(self, t: Txn) -> None:
        for x in t.read_set:
            self.shared.get(x, set()).discard(t)
        for x in t.write_set:
            if self.exclusive.get(x) is t:
                del self.exclusive[x]
        self.sim.retry_blocked()


class OCC(Protocol):
    """Backward validation against every transaction that validated
    earlier and had not finished its flush when this one started."""

    def __init__(self, sim: "Sim"):
        super().__init__(sim)
        self.log: List[list] = []               # [write set, end time]
        self.entry: Dict[int, list] = {}

    def try_op(self, t: Txn, kind: int, x: int) -> str:
        (t.write_set if kind == WRITE else t.read_set).add(x)
        return PROCEED

    def on_read_done(self, t: Txn) -> str:
        for wset, end in self.log:
            if end is not None and end <= t.start_ts:
                continue
            if wset & t.read_set:
                return "validate_fail"
        if t.write_set:
            e = [set(t.write_set), None]
            self.log.append(e)
            self.entry[t.slot] = e
        return "flush"

    def on_leave(self, t: Txn) -> None:
        if t.state != "committed":
            return              # aborts happen before validation logs
        e = self.entry.pop(t.slot, None)
        if e is not None:
            e[1] = self.sim.now
        oldest = min((x.start_ts for x in self.sim.txns),
                     default=self.sim.now)
        self.log = [e for e in self.log if e[1] is None or e[1] > oldest]


PROTOCOLS = {"ppcc": PPCC, "2pl": TwoPL, "occ": OCC}


# --------------------------------------------------------------------------
# engine
# --------------------------------------------------------------------------

class Sim:
    """Closed-loop event-heap engine around one protocol."""

    def __init__(self, p: Params, protocol):
        self.p = p
        self.rng = np.random.default_rng(p.seed)
        self.now = 0.0
        self.heap: list = []
        self.seq = itertools.count()
        self.cpu = Pool(p.num_cpus)
        self.disk = Pool(p.num_disks)
        self.proto = (PROTOCOLS[protocol] if isinstance(protocol, str)
                      else protocol)(self)
        self.commits = self.aborts = self.blocks = 0
        self.blocked: deque = deque()
        self.in_retry = self.retry_again = False
        self.txns: List[Txn] = []
        for slot in range(p.mpl):
            t = Txn(slot, sample_ops(self.rng, p), 0.0)
            self.txns.append(t)
            self.begin(t)

    def schedule(self, when: float, tag: str, t: Txn) -> None:
        heapq.heappush(self.heap, (when, next(self.seq), tag, t, t.epoch))

    def cpu_burst(self) -> float:
        p = self.p
        return float(self.rng.uniform(p.cpu_burst_mean - p.cpu_burst_spread,
                                      p.cpu_burst_mean + p.cpu_burst_spread))

    def io_time(self) -> float:
        p = self.p
        return float(self.rng.uniform(p.io_time_mean - p.io_time_spread,
                                      p.io_time_mean + p.io_time_spread))

    def begin(self, t: Txn) -> None:
        t.state = "read"
        self.next_op(t)

    def next_op(self, t: Txn) -> None:
        if t.ip >= len(t.ops):
            self.read_done(t)
        else:
            self.cpu.request(self, t, self.cpu_burst(), "cpu")

    def run(self) -> "Sim":
        while self.heap:
            when, _, tag, t, epoch = heapq.heappop(self.heap)
            if when > self.p.horizon:
                break
            self.now = when
            if t.epoch != epoch:
                # a stale event of an aborted incarnation still frees
                # the server it held
                if tag == "cpu":
                    self.cpu.release(self)
                elif tag in ("disk", "flush_io"):
                    self.disk.release(self)
                continue
            getattr(self, "ev_" + tag)(t)
        return self

    def ev_cpu(self, t: Txn) -> None:
        self.cpu.release(self)
        self.attempt(t, retry=False)

    def attempt(self, t: Txn, retry: bool) -> None:
        kind, x = t.ops[t.ip]
        verdict = self.proto.try_op(t, kind, x)
        if verdict == PROCEED:
            if retry:
                t.block_epoch += 1            # cancels the pending timeout
            t.ip += 1
            if kind == READ:
                t.state = "disk"
                self.disk.request(self, t, self.io_time(), "disk")
            else:
                t.state = "read"
                self.next_op(t)
        elif verdict == BLOCK:
            if retry:
                self.blocked.append(t)        # its timeout keeps running
            else:
                self.block(t)
        else:
            self.abort(t)

    def ev_disk(self, t: Txn) -> None:
        self.disk.release(self)
        self.next_op(t)

    def block(self, t: Txn) -> None:
        t.state = "blocked"
        t.block_epoch += 1
        self.blocks += 1
        self.blocked.append(t)
        self.schedule(self.now + self.p.block_timeout, "timeout", t)
        t.timeout_epoch = t.block_epoch

    def ev_timeout(self, t: Txn) -> None:
        if t.state in ("blocked", "lock_wait") and \
                t.timeout_epoch == t.block_epoch:
            self.abort(t)

    def retry_blocked(self) -> None:
        """Re-attempt every blocked read-phase transaction; a re-entrant
        call (an abort during a retry) becomes another pass."""
        if self.in_retry:
            self.retry_again = True
            return
        self.in_retry = True
        try:
            self.retry_again = True
            while self.retry_again:
                self.retry_again = False
                for _ in range(len(self.blocked)):
                    if not self.blocked:
                        break
                    t = self.blocked.popleft()
                    if t.state == "blocked":
                        self.attempt(t, retry=True)
        finally:
            self.in_retry = False

    def read_done(self, t: Txn) -> None:
        t.state = "wc"
        out = self.proto.on_read_done(t)
        if out == "flush":
            self.start_flush(t)
        elif out == "validate_fail":
            self.abort(t)
        elif t.state == "lock_wait":
            t.block_epoch += 1
            self.schedule(self.now + self.p.block_timeout, "timeout", t)
            t.timeout_epoch = t.block_epoch
        else:                                 # waits for predecessors
            t.block_epoch += 1

    def start_flush(self, t: Txn) -> None:
        t.state = "flush"
        t.block_epoch += 1
        t.flush_left = len(t.write_set)
        if t.flush_left == 0:
            self.commit(t)
        else:
            self.disk.request(self, t, self.io_time(), "flush_io")

    def ev_flush_io(self, t: Txn) -> None:
        self.disk.release(self)
        t.flush_left -= 1
        if t.flush_left > 0:
            self.disk.request(self, t, self.io_time(), "flush_io")
        else:
            self.commit(t)

    def commit(self, t: Txn) -> None:
        t.state = "committed"
        self.commits += 1
        self.proto.on_leave(t)
        t.ops = sample_ops(self.rng, self.p)
        t.reset(self.now)
        self.begin(t)

    def abort(self, t: Txn) -> None:
        t.state = "aborted"
        self.aborts += 1
        self.proto.on_leave(t)
        ops = t.ops
        t.reset(self.now)
        t.ops = ops
        m = self.p.restart_delay_mean
        self.schedule(self.now + float(self.rng.uniform(0.5 * m, 1.5 * m)),
                      "restart", t)

    def ev_restart(self, t: Txn) -> None:
        self.begin(t)


def simulate(p: Params, protocol) -> Tuple[int, int, int]:
    """(commits, aborts, blocks) of one lane within ``p.horizon``."""
    s = Sim(p, protocol).run()
    return s.commits, s.aborts, s.blocks
