"""The harness end to end on the CPU, admission surface, at a tiny
size: a tick cell it has never seen runs and is correct; planted faults
in the program's admission make ``correct`` false; a stream that runs
out fails the run."""
import jax
import pytest

from bench_test_util import new_cell_root, run_cpu

TRAFFIC = {"kind": "ycsb_backlog", "backlog": 64, "stream_txns": 40000,
           "burn_in_ticks": 20, "check_ticks": 3}
LIMITS = {"admitted_mismatches": 0, "commit_rank_mismatches": 0,
          "order_violations": 0}
SEED = 2**31 + 1601


def root_for(tmp_path, traffic=TRAFFIC):
    return new_cell_root(tmp_path, "tick-ycsb-a-n1024", "tiny-tick",
                         "tiny-tick", traffic, LIMITS)


def test_new_tick_cell_runs_and_is_correct(monkeypatch, tmp_path):
    out = run_cpu(monkeypatch, root_for(tmp_path), "tiny-tick", SEED, 0.2)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0
    assert out["attempted"] >= 3 * 64 and out["attempted"] % 64 == 0
    assert out["metrics"]["sim_commits_per_s"]["value"] > 0
    assert out["metrics"]["setup_s"]["value"] > 0
    assert set(out["checks"]) == set(LIMITS)


def _fresh_tick(mp):
    """Swap in a jit of its own for ``scheduler.tick``, so that the
    patched helpers are traced; the module's ``tick`` keeps its cache."""
    import functools

    from repro.sched import scheduler
    mp.setattr(scheduler, "tick", jax.jit(
        functools.partial(scheduler.tick.__wrapped__),
        static_argnames=("policy", "order", "words", "return_carry",
                         "keys")))


def no_class_tests(mp):
    """PPCC without the rule's two class tests: only 'not preceding and
    preceded at once' is left."""
    from repro.sched import scheduler
    mp.setattr(scheduler, "_prudent",
               lambda r_i, w_i, preceding, preceded:
               ~(r_i.any() & w_i.any()))
    _fresh_tick(mp)


def ww_dropped(mp):
    """The conflict pass loses the pairs that overlap in their writes:
    two updates of one key no longer conflict."""
    from repro.sched import scheduler
    orig = scheduler._conflict_matrices

    def dropped(*a, **kw):
        out = orig(*a, **kw)
        return (out[0] & ~out[1],) + tuple(out[1:])
    mp.setattr(scheduler, "_conflict_matrices", dropped)
    _fresh_tick(mp)


@pytest.mark.parametrize("fault", [no_class_tests, ww_dropped],
                         ids=lambda f: f.__name__)
def test_tick_fault_is_not_correct(monkeypatch, tmp_path, fault):
    """A window too short for more ticks than are compared: the check
    takes the loop's first tick and the window's first three, the same
    on every run."""
    fault(monkeypatch)
    out = run_cpu(monkeypatch, root_for(tmp_path), "tiny-tick", SEED,
                  1e-6)
    assert out["attempted"] == 3 * 64
    assert not out["correct"], out["checks"]
    assert out["failed"] > 0


def test_stream_that_runs_out_fails_the_run(monkeypatch, tmp_path):
    short = dict(TRAFFIC, stream_txns=200)
    with pytest.raises(RuntimeError, match="ran out"):
        run_cpu(monkeypatch, root_for(tmp_path, short), "tiny-tick", SEED,
                30.0)
