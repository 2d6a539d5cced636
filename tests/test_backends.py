"""Step-backend contract: ``pallas`` (per-phase kernels) and
``pallas_fused`` (the whole-step megakernel) — both interpret mode on CPU —
are bitwise identical to ``reference``: per individual phase, end-to-end
through every executor on all 12 lattice points (closed and open-system
arrivals), and at the cache-key layer (backends share cache entries because
results are backend-independent)."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dlb, phases, taskgraph
from repro.core.backends import BACKENDS, get_backend, resolve_name
from repro.core.cache import ResultCache, case_key, graph_digest
from repro.core.phases import REFERENCE_OPS
from repro.core.scheduler import CTR_NAMES, SimConfig, graph_arrays
from repro.core.spec import LATTICE
from repro.core.state import init_state, make_case, make_params
from repro.core.sweep import CaseSpec, run_cases

CFG = SimConfig(n_workers=8, n_zones=2, max_steps=60_000)


@pytest.fixture(scope="module")
def graph():
    return taskgraph.fib(8)


@pytest.fixture(scope="module")
def pallas_ops():
    return get_backend("pallas").step_ops()


def _assert_trees_equal(a, b, label):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb), label
    for x, y in zip(la, lb):
        assert np.array_equal(np.asarray(x), np.asarray(y)), label


#: every phase jitted once per (phase, ops) — the traced case/state reuse
#: that one compilation across all 12 lattice points (and demonstrates the
#: phases' individual jittability, which is the decomposition's point)
@functools.lru_cache(maxsize=None)
def _jitted(phase_name):
    return jax.jit(getattr(phases, phase_name),
                   static_argnames=("costs", "ops"))


@jax.jit
def _mid_run_state(g, case, k):
    """A nontrivial state: k composed reference steps from init."""
    st = init_state(g, CFG.n_workers, CFG.stack_cap, CFG.queue_cap, 4,
                    case.seed)
    step = get_backend("reference").build_step(
        CFG.n_workers, CFG.stack_cap, CFG.costs, g, case, CFG.max_steps,
        dlb.victim_tables(CFG.n_workers, case))
    return jax.lax.while_loop(lambda c: c[0] < k,
                              lambda c: (c[0] + 1, step(c[1])),
                              (jnp.int32(0), st))[1]


@pytest.mark.parametrize("spec", LATTICE, ids=lambda s: s.slug)
def test_each_phase_bitwise_per_backend(graph, pallas_ops, spec):
    """Acceptance criterion: every individual phase function produces a
    bitwise-identical state under the pallas kernel set, on every lattice
    point, from a nontrivial mid-run state."""
    g = graph_arrays(graph)
    case = make_case(spec, CFG.n_workers, CFG.n_workers // CFG.n_zones,
                     seed=3, params=make_params(t_interval=10, p_local=0.8))
    for k in (4, 11):
        st = _mid_run_state(g, case, jnp.int32(k))
        running = (st.n_done < g.n_tasks) & (st.step_i < CFG.max_steps) \
            & ~st.overflow
        kw = dict(case=case, costs=CFG.costs)

        def both(name, *args, **extra):
            fn = _jitted(name)
            r = fn(*args, **kw, **extra, ops=REFERENCE_OPS)
            p = fn(*args, **kw, **extra, ops=pallas_ops)
            _assert_trees_equal(r, p, (spec.slug, k, name))
            return r

        st = both("adopt_phase", st, running)
        st = both("spawn_phase", st, running, g=g)
        st, task, ts, found = both("dequeue_phase", st, running, g=g)
        st = both("thief_phase", st, found, running,
                  tables=dlb.victim_tables(CFG.n_workers, case))
        st = both("victim_phase", st, found, g=g)
        both("exec_phase", st, task, ts, found, g=g)


def test_backends_bitwise_end_to_end_all_executors(graph):
    """Acceptance criterion: both backends produce identical makespans,
    step counts, and §V counters on all 12 lattice points under the
    serial, vmap, and sharded executors."""
    specs = [CaseSpec(spec=s, n_workers=CFG.n_workers, n_zones=CFG.n_zones,
                      t_interval=10, p_local=0.8) for s in LATTICE]
    ref = None
    for backend in sorted(BACKENDS):
        for strategy in ("serial", "batched", "sharded"):
            res = run_cases(graph, specs, cfg=CFG, strategy=strategy,
                            backend=backend)
            assert res.completed.all(), (backend, strategy)
            if ref is None:
                ref = res
                continue
            label = (backend, strategy)
            assert (res.time_ns == ref.time_ns).all(), label
            assert (res.steps == ref.steps).all(), label
            for n in CTR_NAMES:
                assert (res.counters[n] == ref.counters[n]).all(), \
                    (*label, n)
    assert (ref.counters["exec"] == graph.n_tasks).all()


def test_backends_bitwise_open_system(graph):
    """Satellite acceptance: open-system (streaming) cases — every lattice
    point under Poisson arrivals plus long-tail/bursty spot checks — agree
    bitwise across both backends and all three executors, SLO arrays
    (p50/p90/p99 latency, throughput) included."""
    specs = [CaseSpec(spec=s, n_workers=CFG.n_workers, n_zones=CFG.n_zones,
                      t_interval=10, p_local=0.8, arrivals="poisson:2")
             for s in LATTICE]
    specs += [CaseSpec(spec="na_ws", n_workers=CFG.n_workers,
                       n_zones=CFG.n_zones, t_interval=10, p_local=0.8,
                       arrivals=a)
              for a in ("lognormal:2:1.5", "bursty:2:4:0.5")]
    ref = None
    for backend in sorted(BACKENDS):
        for strategy in ("serial", "batched", "sharded"):
            res = run_cases(graph, specs, cfg=CFG, strategy=strategy,
                            backend=backend)
            assert res.completed.all(), (backend, strategy)
            if ref is None:
                ref = res
                continue
            label = (backend, strategy)
            assert (res.time_ns == ref.time_ns).all(), label
            assert (res.steps == ref.steps).all(), label
            for n in CTR_NAMES:
                assert (res.counters[n] == ref.counters[n]).all(), \
                    (*label, n)
            for n in ("p50_ns", "p90_ns", "p99_ns", "throughput"):
                assert (getattr(res, n) == getattr(ref, n)).all(), \
                    (*label, n)
    assert (ref.counters["exec"] == graph.n_tasks).all()
    # open-system latency tails are real (released later than t=0)
    assert (ref.p99_ns > 0).all() and (ref.throughput > 0).all()


def test_backend_excluded_from_cache_keys(graph, tmp_path):
    """Backends are bitwise-equal by contract, so cases simulated under one
    backend are valid cache hits under any other — the key must not depend
    on ``cfg.backend``, and a pallas warm run must hit a reference-written
    store (and vice versa)."""
    s = CaseSpec(spec="na_ws", n_workers=8, n_zones=2)
    gd = graph_digest(graph)
    keys = {case_key(gd, s, dataclasses.replace(CFG, backend=b))
            for b in (None, "reference", "pallas", "pallas_fused")}
    assert len(keys) == 1

    c = ResultCache(str(tmp_path))
    cold = run_cases(graph, [s], cfg=CFG, cache=c, backend="reference")
    assert cold.cache_hits == 0
    for warm_backend in ("pallas", "pallas_fused"):
        warm = run_cases(graph, [s], cfg=CFG, cache=c, backend=warm_backend)
        assert warm.cache_hits == 1, warm_backend
        assert (warm.time_ns == cold.time_ns).all(), warm_backend


def test_backend_selection_threads_through(monkeypatch):
    """SimConfig.backend / the env var / the run_cases override resolve
    consistently, and unknown names fail loudly."""
    monkeypatch.delenv("REPRO_STEP_BACKEND", raising=False)
    assert resolve_name(None) == "reference"
    assert resolve_name("pallas") == "pallas"
    monkeypatch.setenv("REPRO_STEP_BACKEND", "pallas")
    assert resolve_name(None) == "pallas"
    assert resolve_name("reference") == "reference"   # explicit beats env
    with pytest.raises(AssertionError):
        resolve_name("no-such-backend")


def test_backend_registry_matches_run_py():
    """benchmarks/run.py spells the backend names out (to stay jax-free);
    they must match the canonical registry."""
    from conftest import load_bench_run
    bench_run = load_bench_run()
    assert set(bench_run.BACKEND_VALUES) == set(BACKENDS)
    assert "step_backends" in bench_run.SUITES
