"""Padded-lane inertness, phase by phase.

The batching contract says worker lanes ``>= case.n_workers`` are inert —
but the composed-step tests only prove it for a whole step.  Here every
*individual* phase function is checked: from a nontrivial mid-run state,
applying one phase must leave the padded lanes' stack entries, queue
heads/tails/buffers, counters, clocks, DLB state, and messaging cells
bitwise unchanged, for random lattice points and worker counts.

(The per-lane RNG stream is deliberately *not* asserted inert: the thief
retry loop advances ``xorshift`` lane-uniformly — cheaper than masking —
and padded lanes never act on the stream, so it carries no state.)
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import arrivals as arrivals_mod
from repro.core import dlb, phases, taskgraph
from repro.core.backends import get_backend
from repro.core.scheduler import SimConfig, graph_arrays
from repro.core.spec import LATTICE, RuntimeSpec
from repro.core.state import init_state, make_case, make_params

CFG = SimConfig(n_workers=8, n_zones=2, max_steps=60_000)
W = CFG.n_workers

GRAPH = taskgraph.fib(8)
GARR = graph_arrays(GRAPH)


def _padded_views(st, n_w):
    """Every per-lane field of SimState a phase must leave untouched for
    lanes >= n_w (rows *and* producer columns for the (W, W[, Q]) queue
    arrays; the global locked-queue scalars are shared, not per-lane)."""
    return dict(
        s_task=st.s_task[n_w:], s_cnt=st.s_cnt[n_w:], s_top=st.s_top[n_w:],
        xq_head_rows=st.xq.head[n_w:], xq_head_cols=st.xq.head[:, n_w:],
        xq_tail_rows=st.xq.tail[n_w:], xq_tail_cols=st.xq.tail[:, n_w:],
        xq_buf_rows=st.xq.buf[n_w:], xq_buf_cols=st.xq.buf[:, n_w:],
        xq_ts_rows=st.xq.ts[n_w:], xq_ts_cols=st.xq.ts[:, n_w:],
        ctr=st.ctr[n_w:], clock=st.clock[n_w:], idle=st.idle[n_w:],
        rr=st.rr[n_w:], deq_rr=st.deq_rr[n_w:],
        rp_tgt=st.rp.tgt[n_w:], rp_left=st.rp.left[n_w:],
        cells_round=st.cells.round[n_w:],
        cells_req_round=st.cells.req_round[n_w:],
        cells_req_tid=st.cells.req_tid[n_w:],
    )


def _assert_inert(before, after, n_w, label):
    a = _padded_views(before, n_w)
    b = _padded_views(after, n_w)
    for k in a:
        assert np.array_equal(np.asarray(a[k]), np.asarray(b[k])), \
            (label, k)


@jax.jit
def _advance(case, st, k_steps):
    """k composed reference steps, compiled once for every (case, k) — the
    traced case keeps one compilation across lattice points and worker
    counts, which is what makes the hypothesis sweep affordable."""
    step = get_backend("reference").build_step(
        W, CFG.stack_cap, CFG.costs, GARR, case, CFG.max_steps,
        dlb.victim_tables(W, case))
    return jax.lax.while_loop(lambda c: c[0] < k_steps,
                              lambda c: (c[0] + 1, step(c[1])),
                              (jnp.int32(0), st))[1]


def check_phases_padded_inert(spec: RuntimeSpec, n_workers: int, seed: int,
                              k_steps: int, topology=None, arrivals=None):
    """Shared checker: advance ``k_steps`` composed steps, then apply each
    phase once and assert the padded lanes never move.  ``topology`` runs
    the same check on a hierarchical machine (tests/test_topology.py
    sweeps it over random socket counts); ``arrivals`` runs it open-system
    — the spawn release gate and its clock sleep must be just as inert on
    padded lanes as the closed path."""
    if topology is not None:
        zone = topology.zone_size_for(n_workers)
    else:
        zone = max(n_workers // 2, 1)
    arr = arrivals_mod.resolve(arrivals)
    release = None if arr is None else \
        arrivals_mod.release_times(arr, GRAPH.n_tasks, seed)
    case = make_case(spec, n_workers, zone, seed=seed,
                     params=make_params(n_victim=2, n_steal=4, t_interval=5,
                                        p_local=0.7), topology=topology,
                     release_ns=release)
    st = init_state(GARR, W, CFG.stack_cap, CFG.queue_cap, 4, case.seed)
    st = _advance(case, st, jnp.int32(k_steps))
    running = (st.n_done < GARR.n_tasks) & (st.step_i < CFG.max_steps) \
        & ~st.overflow
    kw = dict(case=case, costs=CFG.costs)
    label = (spec.slug, n_workers, seed, k_steps)

    st1 = phases.adopt_phase(st, running, **kw)
    _assert_inert(st, st1, n_workers, (*label, "adopt"))
    st2 = phases.spawn_phase(st1, running, g=GARR, **kw)
    _assert_inert(st1, st2, n_workers, (*label, "spawn"))
    st3, task, ts, found = phases.dequeue_phase(st2, running, g=GARR, **kw)
    _assert_inert(st2, st3, n_workers, (*label, "dequeue"))
    # padded lanes never find work either
    assert not np.asarray(found)[n_workers:].any(), label
    st4 = phases.thief_phase(st3, found, running,
                             tables=dlb.victim_tables(W, case), **kw)
    _assert_inert(st3, st4, n_workers, (*label, "thief"))
    st5 = phases.victim_phase(st4, found, g=GARR, **kw)
    _assert_inert(st4, st5, n_workers, (*label, "victim"))
    st6 = phases.exec_phase(st5, task, ts, found, g=GARR, **kw)
    _assert_inert(st5, st6, n_workers, (*label, "exec"))


#: deterministic corner sample: every queue flavor, both DLB policies, odd
#: worker counts, a 1-worker degenerate — runs without hypothesis installed
DETERMINISTIC = [
    (RuntimeSpec(), 5, 0, 6),
    (RuntimeSpec("locked_global", "centralized_count", "static_rr"), 3, 1, 6),
    (RuntimeSpec(balance="na_ws"), 6, 2, 9),
    (RuntimeSpec(balance="na_rp"), 5, 3, 9),
    (RuntimeSpec("locked_global", "tree", "na_ws"), 4, 0, 7),
    (RuntimeSpec("xqueue", "centralized_count", "na_rp"), 7, 1, 8),
    (RuntimeSpec(), 1, 0, 4),
]


@pytest.mark.parametrize("spec,n_w,seed,k", DETERMINISTIC,
                         ids=lambda v: str(getattr(v, "slug", v)))
def test_padded_lanes_inert_deterministic(spec, n_w, seed, k):
    check_phases_padded_inert(spec, n_w, seed, k)


#: one open-system process per kind — runs without hypothesis installed
ARRIVAL_SAMPLES = ("poisson:2", "lognormal:2:1.5", "bursty:2:4:0.5")


@pytest.mark.parametrize("arrivals", ARRIVAL_SAMPLES)
def test_padded_lanes_inert_under_arrivals(arrivals):
    """Satellite acceptance: padded lanes stay inert when the spawn phase
    gates injection on release stamps (both DLB policies, odd workers)."""
    check_phases_padded_inert(RuntimeSpec(balance="na_ws"), 5, 3, 8,
                              arrivals=arrivals)
    check_phases_padded_inert(RuntimeSpec(balance="na_rp"), 6, 1, 8,
                              arrivals=arrivals)


try:
    from hypothesis import given, settings, strategies as hst
    HAVE_HYPOTHESIS = True
except ImportError:     # the deterministic sample above still runs
    HAVE_HYPOTHESIS = False


if HAVE_HYPOTHESIS:

    @settings(max_examples=12, deadline=None)
    @given(spec=hst.sampled_from(LATTICE),
           n_workers=hst.integers(min_value=1, max_value=W - 1),
           seed=hst.integers(min_value=0, max_value=2**16),
           k_steps=hst.integers(min_value=1, max_value=10))
    def test_padded_lanes_inert_random(spec, n_workers, seed, k_steps):
        """Satellite acceptance: for random lattice points and worker
        counts, padded lanes are provably inert across every individual
        phase function."""
        check_phases_padded_inert(spec, n_workers, seed, k_steps)

    @settings(max_examples=8, deadline=None)
    @given(spec=hst.sampled_from(LATTICE),
           n_workers=hst.integers(min_value=1, max_value=W - 1),
           seed=hst.integers(min_value=0, max_value=2**16),
           k_steps=hst.integers(min_value=1, max_value=10),
           arrivals=hst.sampled_from(ARRIVAL_SAMPLES))
    def test_padded_lanes_inert_random_arrivals(spec, n_workers, seed,
                                                k_steps, arrivals):
        """The same inertness claim on the open-system path, for random
        lattice points, worker counts, and arrival kinds."""
        check_phases_padded_inert(spec, n_workers, seed, k_steps,
                                  arrivals=arrivals)
