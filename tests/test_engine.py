"""Engine mechanics: buffer donation, chunk pipelining, and batched
early-exit.

The sweep engine's perf refinements are all required to be *invisible* in
the results:

* state buffers are donated into the run jits (``donate_argnums``) — every
  donated leaf must find an output to alias into, so jax must emit no
  "donated buffer was not usable" warnings, and the donated init state must
  actually be consumed;
* the depth-2 chunk pipeline (``submit``/``collect`` overlap) is pure
  dispatch reordering — bitwise identical rows with the toggle on or off;
* the batched while loop exits when every lane is done *or permanently
  stalled* (the shared :func:`repro.core.phases.run_gate`), instead of
  spinning a deadlocked lane to the ``max_steps`` horizon — and the rows a
  stalled lane produces are bitwise identical across executors.
"""

import collections
import contextlib
import time
import warnings

import jax
import numpy as np
import pytest

from repro.core import executors, taskgraph
from repro.core.scheduler import (SimConfig, _init_cached, _run_cached,
                                  graph_arrays, run_schedule)
from repro.core.spec import RuntimeSpec
from repro.core.state import make_case, make_params
from repro.core.sweep import CaseSpec, run_cases

CFG = SimConfig(n_workers=8, n_zones=2, max_steps=60_000)


@pytest.fixture(scope="module")
def graph():
    return taskgraph.fib(8)


def _specs(n=6):
    return [CaseSpec(spec="na_ws", n_workers=CFG.n_workers,
                     n_zones=CFG.n_zones, t_interval=10, p_local=0.8,
                     seed=s) for s in range(n)]


def _rows(res):
    return (np.asarray(res.time_ns), np.asarray(res.steps),
            np.asarray(res.completed))


# ---------------------------------------------------------------- donation

def test_no_unusable_donation_warnings(graph):
    """Satellite acceptance: donating SimState through the serial, vmap,
    and sharded run jits must not trip jax's "donated buffer was not
    usable" warning — every donated leaf aliases an output."""
    with warnings.catch_warnings():
        warnings.filterwarnings("error", message=".*[Dd]onat.*")
        for strategy in ("serial", "batched", "sharded"):
            res = run_cases(graph, _specs(), cfg=CFG, strategy=strategy)
            assert res.completed.all(), strategy
        run_schedule(graph, spec="na_ws", cfg=CFG)


def test_donated_init_state_is_consumed(graph):
    """The run jit really takes ownership: after ``_run_cached`` the
    freshly-initialized state's buffers are deleted (aliased into the loop
    carry, not copied)."""
    g = graph_arrays(graph)
    case = make_case(RuntimeSpec(balance="na_ws"), CFG.n_workers,
                     CFG.n_workers // CFG.n_zones, 0, 0.0,
                     make_params(t_interval=10, p_local=0.8))
    st0 = jax.block_until_ready(_init_cached(CFG, 4, g, case))
    st = jax.block_until_ready(_run_cached(CFG, 4, g, case, st0))
    assert int(st.n_done) == graph.n_tasks
    assert all(leaf.is_deleted()
               for leaf in jax.tree_util.tree_leaves(st0))


# --------------------------------------------------------------- pipeline

def test_pipeline_toggle_is_bitwise_invisible(graph):
    """The submit/collect overlap is pure dispatch reordering."""
    on = run_cases(graph, _specs(), cfg=CFG, pipeline=True)
    off = run_cases(graph, _specs(), cfg=CFG, pipeline=False)
    for a, b in zip(_rows(on), _rows(off)):
        assert np.array_equal(a, b)


def test_next_chunk_is_stacked_while_the_running_chunk_runs(monkeypatch):
    """Stacking chunk k+1 puts nothing in the device queue behind chunk k,
    so the host's time in ``repro.stack`` stays a small part of its time
    waiting for the device.  (Built one device scalar at a time, stacking
    waited for the running chunk to end, and the device idled while the
    host stacked.)"""
    spent = collections.Counter()

    @contextlib.contextmanager
    def timed(name, **args):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            spent[name] += time.perf_counter() - t0

    g = taskgraph.fib(16)
    specs = [CaseSpec(spec=b, n_workers=CFG.n_workers, n_zones=CFG.n_zones,
                      seed=s) for b in ("na_rp", "na_ws") for s in range(2)]
    run_cases(g, specs, cfg=CFG, strategy="batched")      # compile
    monkeypatch.setattr(executors, "span", timed)
    res = run_cases(g, specs, cfg=CFG, strategy="batched")
    assert res.completed.all()
    assert spent["stack"] < 0.25 * spent["wait"], dict(spent)


def test_engine_stats_accounting(graph):
    """ENGINE_STATS counts every dispatch/chunk/simulated step of a sweep
    (the numbers --profile prints)."""
    executors.reset_engine_stats()
    res = run_cases(graph, _specs(), cfg=CFG, strategy="batched")
    stats = executors.ENGINE_STATS
    assert stats["chunks"] >= 1
    assert stats["dispatches"] >= stats["chunks"]
    assert stats["sim_steps"] == int(np.asarray(res.steps).sum()) > 0


# ------------------------------------------------------------- early exit

def _deadlocked_graph():
    """A graph that permanently stalls: task 2 needs 2 join notifications
    but only one task ever notifies it, so after tasks 0/1 finish no worker
    can ever acquire work again.  Built directly (``validate()`` would
    reject it — that is the point: the engine must *detect* the stall, not
    assume well-formed inputs)."""
    return taskgraph.TaskGraph(
        name="deadlock",
        dur=np.array([10, 10, 10], np.int64),
        first_child=np.array([1, 0, 0], np.int32),
        n_children=np.array([1, 0, 0], np.int32),
        notify=np.array([-1, 2, -1], np.int32),
        join_dep=np.array([0, 0, 2], np.int32),
    )


def test_stalled_run_exits_early():
    """Satellite acceptance: a deadlocked simulation stops as soon as the
    system is workless (run_gate), orders of magnitude before the
    ``max_steps`` horizon, and reports incomplete."""
    r = run_schedule(_deadlocked_graph(), spec="na_ws", cfg=CFG)
    assert not r.completed
    assert r.counters["exec"] == 2           # tasks 0 and 1 ran; 2 never
    assert r.steps < 100 < CFG.max_steps     # not spun to the horizon


def test_stalled_rows_bitwise_across_executors(graph):
    """A chunk mixing completing and deadlocked lanes exits when the last
    lane dies, with identical per-row results under every executor — the
    step's internal ``running`` gate freezes dead lanes at the same step
    everywhere."""
    graphs = [graph, _deadlocked_graph()]
    specs = [CaseSpec(spec="na_ws", n_workers=CFG.n_workers,
                      n_zones=CFG.n_zones, t_interval=10, p_local=0.8,
                      seed=s, graph=gi) for s in range(2) for gi in (0, 1)]
    ref = None
    for strategy in ("serial", "batched", "sharded"):
        res = run_cases(graphs, specs, cfg=CFG, strategy=strategy)
        comp = np.asarray(res.completed)
        assert comp.tolist() == [True, False, True, False], strategy
        assert (np.asarray(res.steps) < 100).all(), strategy
        if ref is None:
            ref = res
            continue
        for a, b in zip(_rows(res), _rows(ref)):
            assert np.array_equal(a, b), strategy
