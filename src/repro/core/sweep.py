"""The experiment service: batched scheduler-ablation sweeps, layered.

The paper's headline results are ablation *grids* — runtime spec × worker
count × task granularity × DLB parameters (Figs. 4-11, Tables I-IV) — and
the simulator's per-configuration cost is dominated by dispatch overhead on
tiny arrays, not by useful work.  Runtime configurations are
:class:`~repro.core.spec.RuntimeSpec` lattice points (queue × barrier ×
balance); this module is the thin orchestration on top of three explicit
layers:

* **plan** (`repro.core.plan`) — case list → ``SweepPlan``: shared paddings
  (worker lanes, task counts, locked-queue capacity) and (spec,
  graph)-grouped chunks.  Pure host-side; unit-tested without running the
  simulator.
* **cache** (`repro.core.cache`) — a content-addressed on-disk result store
  consulted *per case* before anything executes: re-running overlapping
  grids skips both compilation and execution, and only cache misses are
  planned at all.
* **executors** (`repro.core.executors`) — ``serial`` / ``vmap`` /
  ``sharded`` ways of running a planned chunk, bitwise identical by
  contract; ``strategy="auto"`` shards the batch axis over ``jax.devices()``
  whenever more than one device is visible.

Two entry points:

* ``run_cases(graphs, specs)`` — arbitrary flat list of ``CaseSpec``
  configurations (what the benchmark suites use: per-app best parameters,
  mixed spec ladders, ...).
* ``run_grid(graphs, queues=..., barriers=..., balancers=...,
  n_workers=..., seeds=..., ...)`` — cartesian product sugar over the spec
  lattice that labels the result with ``grid_axes`` and reshapes
  makespans/counters to the grid shape (legacy ``modes=`` is shimmed with a
  ``DeprecationWarning``).

Correctness contract (asserted by tests/test_sweep.py): a batched run is
bitwise identical to running each configuration alone through the same
engine under any executor, a single-configuration engine run matches
``run_schedule``, and a cache hit reproduces the executed result exactly.
"""

from __future__ import annotations

import dataclasses
import itertools
import time
import warnings
from typing import Dict, List, Optional, Sequence

import numpy as np

from repro.core import arrivals as arrivals_mod
from repro.core import backends as backends_mod
from repro.core import barrier as barrier_mod
from repro.core import cache as cache_mod
from repro.core import executors as executors_mod
from repro.core import topology as topology_mod
from repro.core.executors import (STRATEGIES, ExecContext, select_executor,
                                  span)
from repro.core.plan import CaseSpec, build_plan
from repro.core.scheduler import CTR_NAMES, SimConfig, graph_arrays
from repro.core.spec import AXES, RuntimeSpec, spec_product
from repro.core.taskgraph import TaskGraph

__all__ = ["CaseSpec", "SweepResult", "run_cases", "run_grid"]

#: numbers the process's ``run_cases`` calls, for their spans' ``call``
_CALLS = itertools.count(1)


@dataclasses.dataclass
class SweepResult:
    """Structured result of a batched sweep.

    ``time_ns``/``counters``/``completed``/``steps`` are flat per-case arrays
    in ``specs`` order.  When produced by ``run_grid``, ``grid_axes`` names
    the cartesian axes and ``makespans`` / ``counter(name)`` reshape to the
    grid shape ``tuple(len(v) for v in grid_axes.values())``.

    The SLO arrays (``p50_ns``/``p90_ns``/``p99_ns``/``throughput``) carry
    per-task latency percentiles and sustained throughput — populated for
    open- *and* closed-system cases alike (a closed case's "latency" is the
    completion clock, release 0), ``NaN`` only when a case was served from
    a cache entry written before the streaming fields existed.
    """
    specs: List[CaseSpec]
    graph_names: List[str]
    time_ns: np.ndarray               # (B,) int64
    counters: Dict[str, np.ndarray]   # name -> (B,) int64
    completed: np.ndarray             # (B,) bool
    steps: np.ndarray                 # (B,) int64
    wall_s: float = 0.0               # engine wall-clock for this sweep
    cache_hits: int = 0               # cases served from the result cache
    grid_axes: Optional[Dict[str, tuple]] = None
    p50_ns: Optional[np.ndarray] = None        # (B,) float64 (NaN = unknown)
    p90_ns: Optional[np.ndarray] = None
    p99_ns: Optional[np.ndarray] = None
    throughput: Optional[np.ndarray] = None    # (B,) tasks/s over busy span

    def _grid(self, a: np.ndarray) -> np.ndarray:
        if self.grid_axes is None:
            return a
        return a.reshape(tuple(len(v) for v in self.grid_axes.values()))

    @property
    def makespans(self) -> np.ndarray:
        return self._grid(self.time_ns)

    def counter(self, name: str) -> np.ndarray:
        return self._grid(self.counters[name])

    def slo(self, name: str) -> np.ndarray:
        """Grid-shaped view of one SLO array (``p50_ns``/``p90_ns``/
        ``p99_ns``/``throughput``)."""
        return self._grid(getattr(self, name))

    def row(self, i: int) -> dict:
        """One case as a flat dict (benchmark emission helper)."""
        s = self.specs[i]
        return dict(
            app=self.graph_names[s.graph], mode=s.mode,
            queue=s.spec.queue, barrier=s.spec.barrier,
            balance=s.spec.balance,
            topology=topology_mod.label(s.topology),
            arrivals=arrivals_mod.label(s.arrivals),
            n_workers=s.n_workers, seed=s.seed, n_victim=s.n_victim,
            n_steal=s.n_steal, t_interval=s.t_interval, p_local=s.p_local,
            p_local_node=s.p_local_node,
            time_ns=int(self.time_ns[i]), completed=bool(self.completed[i]),
            p50_ns=float(self.p50_ns[i]), p90_ns=float(self.p90_ns[i]),
            p99_ns=float(self.p99_ns[i]),
            throughput_tasks_per_s=float(self.throughput[i]),
            counters={k: int(v[i]) for k, v in self.counters.items()})


def run_cases(graphs: Sequence[TaskGraph] | TaskGraph,
              specs: Sequence[CaseSpec], cfg: SimConfig | None = None,
              chunk_size: int = 64, strategy: str = "auto",
              cache=None, backend: str | None = None,
              pipeline: bool = True) -> SweepResult:
    """Run every ``CaseSpec`` through the experiment service.

    The result cache (``cache=True`` for the default on-disk store, or a
    ``ResultCache`` instance) is consulted per case first; only misses are
    planned, padded, and executed.  Graphs are padded to a common task
    count, worker lanes to the maximum ``n_workers`` among the misses.
    Per-case results return in the original ``specs`` order and are bitwise
    independent of grouping, padding, caching, and execution strategy.

    ``strategy``: ``"serial"`` / ``"vmap"`` (alias ``"batched"``) /
    ``"sharded"`` force one executor; ``"auto"`` shards over
    ``jax.devices()`` when more than one is visible, else vmaps uniform
    chunks and serializes heterogeneous DLB-knob chunks on CPU (see
    repro.core.executors).

    ``backend`` picks the step backend (``reference`` / ``pallas`` /
    ``pallas_fused``; see repro.core.backends), overriding ``cfg.backend``.
    Backends are bitwise identical by contract, so results — and the cache
    keys below — are backend-independent: a case simulated under one
    backend is a valid cache hit under any other.

    ``pipeline`` (default on) overlaps chunk *k+1*'s host-side work —
    stacking, state init, dispatch, and chunk *k*'s post-processing (SLO
    reduction, cache writes) — with chunk *k*'s device execution, via the
    executors' non-blocking ``submit`` / blocking ``collect`` split.  Pure
    dispatch reordering: results are bitwise independent of the toggle
    (tests/test_engine.py asserts it); ``pipeline=False`` exists for A/B
    timing (benchmarks/step_backends.py) and debugging.
    """
    if isinstance(graphs, TaskGraph):
        graphs = [graphs]
    graphs = list(graphs)
    specs = list(specs)
    assert specs, "empty sweep"
    assert all(0 <= s.graph < len(graphs) for s in specs)
    assert strategy in STRATEGIES, (strategy, STRATEGIES)
    cfg = cfg or SimConfig()
    # resolve the backend once, host-side (None -> env -> reference), so
    # every jit dispatch below keys on the concrete name
    cfg = dataclasses.replace(cfg, backend=backends_mod.resolve_name(
        backend if backend is not None else cfg.backend))

    call = next(_CALLS)
    with span("run_cases", call=call, rows=len(specs)) as root:
        result, n_chunks = _run_cases(graphs, specs, cfg, chunk_size,
                                      strategy, cache, pipeline, call)
        root.set_metadata(chunks=n_chunks)
    return result


def _run_cases(graphs: List[TaskGraph], specs: List[CaseSpec],
               cfg: SimConfig, chunk_size: int, strategy: str, cache,
               pipeline: bool, call: int) -> tuple[SweepResult, int]:
    """The body of :func:`run_cases`, under its root span; also returns the
    number of chunks it ran."""
    t0 = time.perf_counter()
    B = len(specs)
    clock_max = np.zeros(B, np.int64)
    ctr_sum = np.zeros((B, len(CTR_NAMES)), np.int64)
    n_done = np.zeros(B, np.int64)
    overflow = np.zeros(B, bool)
    step_i = np.zeros(B, np.int64)
    slo_arr = {n: np.full(B, np.nan) for n in arrivals_mod.SLO_FIELDS}

    def fill_slo(i: int, rec: Optional[dict]) -> None:
        if rec:
            for n in arrivals_mod.SLO_FIELDS:
                slo_arr[n][i] = float(rec[n])

    def release_for(s: CaseSpec) -> np.ndarray:
        g = graphs[s.graph]
        if s.arrivals is None:
            return np.zeros(g.n_tasks, np.int64)
        return arrivals_mod.release_times(s.arrivals, g.n_tasks, s.seed)

    store = cache_mod.resolve(cache)
    keys: List[Optional[str]] = [None] * B
    miss = list(range(B))
    hits = 0
    if store is not None:
        digests = [cache_mod.graph_digest(g) for g in graphs]
        miss = []
        for i, s in enumerate(specs):
            keys[i] = cache_mod.case_key(digests[s.graph], s, cfg)
            rec = store.get(keys[i], required_counters=CTR_NAMES)
            if rec is None:
                miss.append(i)
                continue
            hits += 1
            clock_max[i] = int(rec["clock_max"])
            ctr_sum[i] = [int(rec["counters"][n]) for n in CTR_NAMES]
            n_done[i] = int(rec["n_done"])
            overflow[i] = bool(rec["overflow"])
            step_i[i] = int(rec["step_i"])
            # entries written before the streaming mode carry no SLO
            # record — still valid hits (closed keys never changed), the
            # SLO arrays just stay NaN for them
            fill_slo(i, rec.get("slo"))

    n_chunks = 0
    if miss:
        miss_specs = [specs[i] for i in miss]
        with span("plan", call=call):
            plan = build_plan(graphs, miss_specs, chunk_size=chunk_size)
            run_cfg = dataclasses.replace(cfg, n_workers=plan.w_pad)
            ctx = ExecContext(
                cfg=run_cfg, gq_cap=plan.gq_cap, graphs=graphs,
                garr=[graph_arrays(g, plan.t_pad, xp=np) for g in graphs],
                release_len=(plan.t_pad
                             if any(s.arrivals is not None for s in miss_specs)
                             else 1),
                call=call)
        n_chunks = len(plan.chunks)

        def postprocess(chunk, raw) -> None:
            executors_mod.ENGINE_STATS["sim_steps"] += int(raw.step_i.sum())
            for j, mi in enumerate(chunk.indices):
                i = miss[mi]
                s = specs[i]
                clock_max[i] = int(raw.clock[j].max())
                ctr_sum[i] = raw.ctr[j].sum(axis=0)
                n_done[i] = int(raw.n_done[j])
                overflow[i] = bool(raw.overflow[j])
                step_i[i] = int(raw.step_i[j])
                slo = arrivals_mod.slo_metrics(
                    raw.done_ns[j], release_for(s),
                    graphs[s.graph].n_tasks)
                fill_slo(i, slo)
                if store is not None:
                    # app stamp = the graph's family name ("moe(E64,..)"
                    # → "moe"); metadata only — keys stay app-blind by
                    # design (identically-shaped graphs share entries), so
                    # warm caches stay warm across this stamp's arrival
                    store.put(keys[i], dict(
                        clock_max=int(clock_max[i]),
                        counters={n: int(ctr_sum[i][k])
                                  for k, n in enumerate(CTR_NAMES)},
                        n_done=int(n_done[i]), overflow=bool(overflow[i]),
                        step_i=int(step_i[i]), slo=slo,
                        topology=topology_mod.label(s.topology),
                        arrivals=arrivals_mod.label(s.arrivals),
                        app=graphs[s.graph].name.split("(")[0]))

        # depth-2 software pipeline: chunk k+1 is stacked/inited/dispatched
        # (all host-side or async) before chunk k's results are collected,
        # so the host's next-chunk work and post-processing overlap the
        # device's current-chunk execution.  Dispatch reordering only —
        # per-case results are bitwise identical either way.
        def collect(ex, handle, chunk) -> None:
            raw = ex.collect(handle)
            with span("postprocess", **ex.chunk_args(ctx, chunk)):
                postprocess(chunk, raw)

        pending = None  # (executor, handle, chunk) in flight
        for chunk in plan.chunks:
            ex = select_executor(strategy, chunk)
            handle = ex.submit(ctx, miss_specs, chunk)
            if not pipeline:
                collect(ex, handle, chunk)
                continue
            if pending is not None:
                collect(*pending)
            pending = (ex, handle, chunk)
        if pending is not None:
            collect(*pending)

    with span("finish", call=call):
        # barrier episode per case (host-side: the barrier axis, W, and the
        # machine topology are known per spec, matching run_schedule's
        # accounting bit-for-bit; a non-flat topology lays the tree barrier
        # out along the socket hierarchy — see barrier.tree_episode_topo)
        ep_t = np.zeros(B, np.int64)
        ep_a = np.zeros(B, np.int64)
        for i, s in enumerate(specs):
            ep = barrier_mod.episode_for(s.spec.barrier, s.n_workers, cfg.costs,
                                         s.topology)
            ep_t[i] = int(ep.time_ns)
            ep_a[i] = int(ep.atomic_ops)

        time_ns = clock_max + ep_t
        counters = {n: ctr_sum[:, i].copy() for i, n in enumerate(CTR_NAMES)}
        counters["atomic_ops"] = counters["atomic_ops"] + ep_a
        completed = np.array(
            [n_done[i] == graphs[s.graph].n_tasks and not overflow[i]
             for i, s in enumerate(specs)])
    return SweepResult(
        specs=specs, graph_names=[g.name for g in graphs],
        time_ns=time_ns, counters=counters, completed=completed,
        steps=step_i, wall_s=time.perf_counter() - t0, cache_hits=hits,
        p50_ns=slo_arr["p50_ns"], p90_ns=slo_arr["p90_ns"],
        p99_ns=slo_arr["p99_ns"],
        throughput=slo_arr["throughput_tasks_per_s"]), n_chunks


def run_grid(graphs: Sequence[TaskGraph] | TaskGraph,
             modes: Sequence[str | RuntimeSpec] | None = None,
             n_workers: Sequence[int] = (32,),
             seeds: Sequence[int] = (0,),
             n_victim: Sequence[int] = (4,),
             n_steal: Sequence[int] = (8,),
             t_interval: Sequence[int] = (100,),
             p_local: Sequence[float] = (1.0,),
             n_zones: int | None = None,
             cfg: SimConfig | None = None,
             chunk_size: int = 64, strategy: str = "auto",
             cache=None, backend: str | None = None,
             pipeline: bool = True, *,
             queues: Sequence[str] | None = None,
             barriers: Sequence[str] | None = None,
             balancers: Sequence[str] | None = None,
             topologies: Sequence = (None,),
             bandwidths: Sequence = (None,),
             arrivals: Sequence = (None,),
             p_local_node: Sequence[float] = (0.75,)) -> SweepResult:
    """Cartesian sweep over the spec lattice × machine × workers × seeds ×
    DLB knobs.

    The runtime axes are named per :mod:`repro.core.spec`:
    ``queues`` × ``barriers`` × ``balancers`` (each defaulting to the SLB
    baseline's value), e.g. the full 12-point ablation lattice is::

        run_grid(graphs, queues=spec.QUEUES, barriers=spec.BARRIERS,
                 balancers=spec.BALANCERS)

    ``topologies`` makes the simulated machine a grid axis like every other
    knob: entries are :class:`~repro.core.topology.MachineTopology`
    instances, preset names (``"uds"`` / ``"dual_socket_24"`` /
    ``"quad_socket_48"``), or ``None`` for the historical flat machine
    (axis label ``"flat"``), e.g.::

        run_grid(graphs, balancers=spec.BALANCERS,
                 topologies=(None, "dual_socket_24", "quad_socket_48"))

    ``bandwidths`` rescales each topology's inter-node links (bytes/ns):
    ``None`` keeps the preset's native matrix (axis label ``"native"``); an
    integer ``b`` maps every entry to ``topo.with_bandwidth(b)``, e.g. a
    bandwidth-starvation curve on the rack preset::

        run_grid(graphs, balancers=("na_ws",),
                 topologies=("rack_4x2x24",), bandwidths=(None, 16, 4, 1))

    ``p_local_node`` sweeps the cluster victim policy's second stratum (the
    probability a *remote* steal attempt stays on the thief's node); it only
    steers cluster machines — single-node and flat entries ignore it.

    ``arrivals`` sweeps the open-system arrival process the same way:
    entries are :class:`~repro.core.arrivals.ArrivalProcess` instances,
    string specs (``"poisson:2"`` / ``"lognormal:2:1.5"`` /
    ``"bursty:2:8:0.25"``), or ``None`` for the historical closed system
    (axis label ``"closed"``), e.g. a throughput-vs-offered-load curve::

        run_grid(graphs, balancers=spec.BALANCERS,
                 arrivals=("poisson:0.5", "poisson:2", "poisson:8"))

    The legacy ``modes=`` argument (a non-cartesian list of ladder names)
    still works — string entries emit a ``DeprecationWarning`` and the grid
    keeps its historical ``mode`` axis; ``RuntimeSpec`` entries are accepted
    silently (the escape hatch for non-cartesian spec lists).

    Returns a ``SweepResult`` whose ``grid_axes`` names every axis (in
    declaration order) and whose ``makespans``/``counter(name)`` reshape to
    the grid.
    """
    if isinstance(graphs, TaskGraph):
        graphs = [graphs]
    graphs = list(graphs)
    cfg = cfg or SimConfig()
    zones = cfg.n_zones if n_zones is None else n_zones

    lattice_args = (queues, barriers, balancers)
    if modes is not None and any(a is not None for a in lattice_args):
        raise TypeError("pass either the deprecated modes= or the "
                        "queues=/barriers=/balancers= lattice to run_grid, "
                        "not both")
    if modes is not None:
        if any(isinstance(m, str) for m in modes):
            warnings.warn(
                "modes= in run_grid is deprecated; pass queues=/barriers=/"
                "balancers= (see repro.core.spec.MODE_SPECS for the "
                "mode→spec mapping)", DeprecationWarning, stacklevel=2)
        spec_list = tuple(RuntimeSpec.coerce(m) for m in modes)
        spec_axes = dict(mode=tuple(
            m if isinstance(m, str) else m.label for m in modes))
    else:
        # unset axes default to the SLB baseline's value on that axis;
        # an explicitly-passed empty axis is an error, not a default
        baseline = RuntimeSpec()
        lattice = {}
        for name, vals in zip(("queue", "barrier", "balance"),
                              lattice_args):
            if vals is None:
                lattice[name] = (getattr(baseline, name),)
                continue
            vals = tuple(vals)
            assert vals, f"empty {name} axis in run_grid"
            assert all(v in AXES[name] for v in vals), (name, vals)
            lattice[name] = vals
        spec_list = spec_product(lattice["queue"], lattice["barrier"],
                                 lattice["balance"])
        spec_axes = lattice
    topo_list = tuple(topology_mod.resolve(t) for t in topologies)
    assert topo_list, "empty topology axis in run_grid"
    bw_list = tuple(bandwidths)
    assert bw_list, "empty bandwidth axis in run_grid"
    assert all(b is None for b in bw_list) \
        or all(t is not None for t in topo_list), \
        "bandwidths= rescales machine topologies; the flat machine has none"
    arr_list = tuple(arrivals_mod.resolve(a) for a in arrivals)
    assert arr_list, "empty arrivals axis in run_grid"

    def with_bw(t, b):
        return t if b is None else t.with_bandwidth(b)

    axes = dict(app=tuple(g.name for g in graphs), **spec_axes,
                topology=tuple(topology_mod.label(t) for t in topo_list),
                bandwidth=tuple("native" if b is None else int(b)
                                for b in bw_list),
                arrivals=tuple(arrivals_mod.label(a) for a in arr_list),
                n_workers=tuple(n_workers), seed=tuple(seeds),
                n_victim=tuple(n_victim), n_steal=tuple(n_steal),
                t_interval=tuple(t_interval), p_local=tuple(p_local),
                p_local_node=tuple(p_local_node))
    specs = [
        CaseSpec(spec=sp, n_workers=w, n_zones=zones, seed=sd, n_victim=nv,
                 n_steal=ns, t_interval=ti, p_local=pl, graph=gi,
                 topology=with_bw(tp, bw), arrivals=ar, p_local_node=pn)
        for gi in range(len(graphs)) for sp in spec_list
        for tp in topo_list for bw in bw_list for ar in arr_list
        for w in n_workers for sd in seeds for nv in n_victim
        for ns in n_steal for ti in t_interval for pl in p_local
        for pn in p_local_node
    ]
    res = run_cases(graphs, specs, cfg=cfg, chunk_size=chunk_size,
                    strategy=strategy, cache=cache, backend=backend,
                    pipeline=pipeline)
    res.grid_axes = axes
    return res
