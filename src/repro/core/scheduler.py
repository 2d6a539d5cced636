"""Vectorized lock-less task scheduler simulator (the paper's runtime, in JAX).

Executes the paper's algorithms *literally* — same queue topology (per-pair
SPSC buffers), same message cells (Alg. 1/2), same DLB policies (Alg. 3/4),
same counters (§V) — over host-built task DAGs, with per-worker virtual
clocks charged by the cost model.  Makespan is causal through queue
timestamps: popping a task advances the consumer clock to at least the
producer-side timestamp.

The simulator is three explicit layers (this module is the thin run loop on
top, kept as the historical import surface):

* :mod:`repro.core.state`    — SimState / SweepCase / GraphArrays pytrees,
  SimConfig, and the initializers (every name is re-exported here).
* :mod:`repro.core.phases`   — each per-step phase (push, dequeue, thief,
  victim, execute) as a pure, individually-jittable ``(state, case, …) ->
  state`` function with a documented read/write footprint.
* :mod:`repro.core.backends` — ``StepBackend`` composes the phases into the
  step body over a pluggable kernel set: ``reference`` (pure jnp, pinned
  bitwise to tests/golden_modes.json) or ``pallas`` (Pallas kernels for the
  hot queue phases, interpret mode off-TPU) — bitwise identical by
  contract.

A runtime configuration is a point on the queue × barrier × balance lattice
(:class:`repro.core.spec.RuntimeSpec`):

  queue    locked_global — single global priority queue + global task lock
           (everything serializes on the lock; malloc in the critical path)
           vs xqueue — per-pair SPSC lock-less queues (§II-B)
  barrier  centralized_count — centralized barrier + a globally-shared
           *atomic* task count (contended per create/finish; under the
           locked_global queue the count update rides the already-held task
           lock, so only xqueue runtimes pay it separately)
           vs tree — distributed tree barrier, no global count at all
  balance  static_rr — static round-robin placement only
           vs na_rp — NUMA-aware Redirect Push  (Alg. 3)
           vs na_ws — NUMA-aware Work Stealing  (Alg. 4)

The paper's five-rung ablation ladder (gomp / xgomp / xgomptb / na_rp /
na_ws) is the canned subset ``spec.MODE_SPECS`` of that lattice and
reproduces the pre-decomposition results bitwise
(tests/test_golden_modes.py).

One simulator step = one scheduling point per worker: a worker either pushes
pending spawned tasks (up to K_SPAWN), or tries to dequeue-and-execute one
task; idle workers run the thief protocol.  All phases are vectorized over
workers; lock-less "owner writes only" discipline holds per phase by
construction (see xqueue.py).

Batching (the sweep engine's contract): the entire simulator state is a flat
pytree of fixed-shape arrays, and every per-configuration knob — the three
spec axis ids, the active worker count, the NUMA zone size, the RNG seed,
the memory-bound fraction, and the DLB parameters — is a *traced* scalar
carried in ``SweepCase``.  Axis selection is pure mask arithmetic
(``jnp.where`` over the axis ids), never Python ``if``, so
``step``/``_run_jit`` are safely ``jax.vmap``-able over a leading batch axis
of cases (see sweep.py).  Worker counts below the padded width ``W`` leave
the extra lanes provably inert: padded workers never hold stack entries, are
masked out of every dequeue / thief mask, and all round-robin / victim
arithmetic is modulo the traced ``n_workers`` (tests/test_phases.py proves
lane inertness for every individual phase).
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np

from repro.core import arrivals as arrivals_mod
from repro.core import backends as backends_mod
from repro.core import barrier as barrier_mod
from repro.core import dlb
from repro.core import phases as phases_mod
from repro.core import topology as topology_mod
from repro.core.spec import MODE_SPECS, RuntimeSpec, resolve_spec
from repro.core.state import (CTR, CTR_NAMES, K_SPAWN, NC, NV_CAP,  # noqa: F401
                              WS_CAP, GraphArrays, Params, SimConfig,
                              SimState, SweepCase, graph_arrays, init_state,
                              make_case, make_params)
from repro.core.taskgraph import TaskGraph

#: legacy five-rung ladder names (see repro.core.spec for the lattice)
MODES = tuple(MODE_SPECS)
MODE_ID = {m: i for i, m in enumerate(MODES)}

# historical alias for the pre-decomposition private API (the state
# moved to state.py)
_init_state = init_state


@dataclasses.dataclass
class SimResult:
    name: str
    mode: str                 # legacy ladder name when on-ladder, else slug
    n_workers: int
    completed: bool
    time_ns: int
    steps: int
    counters: dict            # summed over workers
    per_worker_busy: np.ndarray
    per_worker_clock: np.ndarray
    per_worker_exec: np.ndarray
    spec: RuntimeSpec | None = None   # the lattice point that produced this
    arrivals: str = "closed"          # arrival-process label (see arrivals)
    slo: dict | None = None           # arrivals.slo_metrics record

    @property
    def throughput_tasks_per_s(self) -> float:
        return self.counters["exec"] / max(self.time_ns, 1) * 1e9

    @property
    def latency_p99_ns(self) -> int:
        """Nearest-rank p99 of per-task (completion − release) latency."""
        return int(self.slo["p99_ns"]) if self.slo else -1

    @property
    def sustained_tasks_per_s(self) -> float:
        """Completions over the busy span (open-system throughput)."""
        return float(self.slo["throughput_tasks_per_s"]) if self.slo else 0.0


def _init_jit(cfg: SimConfig, gq_cap: int, g: GraphArrays,
              case: SweepCase) -> SimState:
    """Fresh state for one case — split out of the run so the run's jit can
    *donate* the state argument (the init's output buffers become the run's
    scratch, not a second live copy)."""
    return init_state(g, cfg.n_workers, cfg.stack_cap, cfg.queue_cap,
                      gq_cap, case.seed)


_init_cached = jax.jit(_init_jit, static_argnums=(0, 1))


def _run_jit(cfg: SimConfig, gq_cap: int, g: GraphArrays,
             case: SweepCase, st0: SimState) -> SimState:
    """Run one fully-traced simulation to completion.  ``cfg`` and ``gq_cap``
    are static (they fix array shapes — and ``cfg.backend`` the step
    kernels); ``g``, ``case`` and the initial state are traced pytrees, so
    this function vmaps over a leading batch axis of all three.  The while
    cond is the shared :func:`~repro.core.phases.run_gate` — identical to
    the step body's internal ``running`` gate, so completion, the step
    horizon, overflow, *and* a permanently stalled (workless) simulation
    all stop the loop at the same step.  The case's victim-weight tables
    are built once here, before the loop."""
    tables = dlb.victim_tables(cfg.n_workers, case)
    step = backends_mod.get_backend(cfg.backend).build_step(
        cfg.n_workers, cfg.stack_cap, cfg.costs, g, case, cfg.max_steps,
        tables)

    def cond(st):
        return phases_mod.run_gate(st, g, cfg.max_steps)

    return jax.lax.while_loop(cond, step, st0)


#: ``st0`` is donated: the caller hands over the freshly-initialized state
#: buffers and must not touch them again (SerialExecutor / run_schedule
#: re-init per case anyway), letting XLA alias them into the loop carry
#: instead of round-tripping a second full copy of SimState
_run_cached = jax.jit(_run_jit, static_argnums=(0, 1), donate_argnums=(4,))


def run_schedule(graph: TaskGraph, mode: str | RuntimeSpec | None = None,
                 params: Params | None = None, cfg: SimConfig | None = None,
                 seed: int = 0, *, spec: RuntimeSpec | str | None = None,
                 topology=None, arrivals=None) -> SimResult:
    """Simulate scheduling ``graph`` under one runtime configuration.

    ``spec`` is the canonical way to name the configuration (a
    :class:`RuntimeSpec` lattice point); the legacy string ``mode=`` still
    works but emits a ``DeprecationWarning``.  Default is the SLB baseline
    (XQueue + tree barrier + static round-robin, the old ``"xgomptb"``).
    ``topology`` names the simulated machine (a
    :class:`~repro.core.topology.MachineTopology` or preset name; ``None``
    = the flat ``cfg.n_zones`` machine, bitwise-identical to the
    pre-topology engine).  ``cfg.backend`` picks the step backend
    (``reference`` / ``pallas``, bitwise identical).  ``arrivals`` runs
    the open-system mode (an :class:`~repro.core.arrivals.ArrivalProcess`
    or string spec; ``None`` = closed system, bitwise identical to the
    pre-arrival engine).  Returns makespan + the paper's §V counters, plus
    the per-task SLO record (p50/p90/p99 latency, sustained throughput).
    """
    rspec = resolve_spec(spec, mode, where="run_schedule")
    topo = topology_mod.resolve(topology)
    arr = arrivals_mod.resolve(arrivals)
    cfg = cfg or SimConfig()
    # resolve the backend (None -> env -> reference) *before* the jit
    # dispatch so the compiled-function cache keys on the concrete name
    cfg = dataclasses.replace(
        cfg, backend=backends_mod.resolve_name(cfg.backend))
    params = params or make_params()
    gq_cap = graph.n_tasks + 2 if rspec.queue == "locked_global" else 4
    W = cfg.n_workers
    zone_size = (topo.zone_size_for(W) if topo is not None
                 else max(W // cfg.n_zones, 1))
    release = (None if arr is None
               else arrivals_mod.release_times(arr, graph.n_tasks, seed))
    case = make_case(rspec, W, zone_size, seed,
                     round(float(graph.mem_bound), 3), params,
                     topology=topo, release_ns=release)
    garr = graph_arrays(graph)
    st0 = _init_cached(cfg, gq_cap, garr, case)
    st = jax.block_until_ready(_run_cached(cfg, gq_cap, garr, case, st0))

    episode = barrier_mod.episode_for(rspec.barrier, W, cfg.costs, topo)
    ctr = np.asarray(st.ctr)
    counters = {n: int(ctr[:, i].sum()) for i, n in enumerate(CTR_NAMES)}
    counters["atomic_ops"] += int(episode.atomic_ops)
    time_ns = int(np.asarray(st.clock).max()) + int(episode.time_ns)
    rel_host = (np.zeros(graph.n_tasks, np.int64) if release is None
                else release)
    slo = arrivals_mod.slo_metrics(np.asarray(st.done_ns), rel_host,
                                   graph.n_tasks)
    return SimResult(
        name=graph.name, mode=rspec.label, n_workers=W,
        completed=bool(st.n_done == graph.n_tasks) and not bool(st.overflow),
        time_ns=time_ns, steps=int(st.step_i), counters=counters,
        per_worker_busy=ctr[:, CTR["busy_ns"]].copy(),
        per_worker_clock=np.asarray(st.clock).copy(),
        per_worker_exec=ctr[:, CTR["exec"]].copy(),
        spec=rspec, arrivals=arrivals_mod.label(arr), slo=slo,
    )
