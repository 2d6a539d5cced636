"""State layer of the simulator: pytrees, traced configuration, and init.

Everything the scheduler step reads or writes lives here as flat,
fixed-shape pytrees — :class:`SimState` (the whole simulator state),
:class:`SweepCase` (one fully-traced configuration), :class:`GraphArrays`
(the device-side task graph) — plus the static :class:`SimConfig` and the
initializers that build them.  The phase functions in
:mod:`repro.core.phases` are pure ``(state, case, …) -> state`` maps over
these types; :mod:`repro.core.backends` composes them into a step body.

Batching contract (see sweep.py): every per-configuration knob is a traced
scalar carried in ``SweepCase``, every array in ``SimState`` has a static
shape fixed by ``SimConfig``, so a batch of configurations is just these
pytrees with a leading axis — ``jax.vmap``-able by construction.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dlb, messaging, xqueue
from repro.core import topology as topology_mod
from repro.core.costs import DEFAULT_COSTS, CostModel
from repro.core.spec import MODE_SPECS, RuntimeSpec
from repro.core.taskgraph import TaskGraph
from repro.core.topology import MachineTopology, TopoArrays

# counters (paper §V, plus the cluster tier's locality/traffic pair —
# identically zero on flat and single-node machines)
CTR_NAMES = (
    "exec", "self", "local", "remote",            # task locality at execution
    "static_push", "imm_exec",                     # push outcomes
    "req_sent", "req_handled", "req_has_steal",    # messaging protocol
    "stolen", "stolen_local", "stolen_remote",     # migrated tasks (WS + RP)
    "src_empty", "tgt_full",                       # failed steals
    "atomic_ops", "busy_ns",
    "stolen_xnode",                                # steals crossing a node
    "xnode_bytes",                                 # bytes over the bottleneck
)
NC = len(CTR_NAMES)
CTR = {n: i for i, n in enumerate(CTR_NAMES)}

K_SPAWN = 2     # pushes per worker per scheduling point
WS_CAP = 32     # static bound on Alg. 4's per-round transfer loop
NV_CAP = 24     # static bound on requests per thief retry (paper max N_victim)


class Params(NamedTuple):
    """Dynamic DLB configuration (§IV-E) — sweepable without recompilation.

    ``p_local_node`` is the cluster tier's second stratum: when a victim
    draw goes remote (prob ``1 - p_local``), it stays inside the thief's
    *node* with probability ``p_local_node`` and crosses the inter-node
    fabric otherwise.  Only read when the topology is a cluster — flat and
    single-node machines never consult it (bitwise contract).
    """
    n_victim: jax.Array
    n_steal: jax.Array
    t_interval: jax.Array  # in scheduling points
    p_local: jax.Array
    p_local_node: jax.Array


def make_params(n_victim=4, n_steal=8, t_interval=100, p_local=1.0,
                p_local_node=0.75, xp=jnp) -> Params:
    return Params(xp.int32(n_victim), xp.int32(n_steal),
                  xp.int32(t_interval), xp.float32(p_local),
                  xp.float32(p_local_node))


class SweepCase(NamedTuple):
    """One fully-traced simulator configuration.

    Every field is a scalar array, so a batch of cases is just this pytree
    with a leading axis — ``jax.vmap`` over it runs a whole spec × workers ×
    seeds × DLB-knob grid in one compiled call.  The three axis ids carry a
    :class:`~repro.core.spec.RuntimeSpec` point-by-point (queue_id indexes
    ``spec.QUEUES``, etc.), so one compiled call can mix lattice points.
    """
    queue_id: jax.Array    # int32 index into spec.QUEUES
    barrier_id: jax.Array  # int32 index into spec.BARRIERS
    balance_id: jax.Array  # int32 index into spec.BALANCERS
    n_workers: jax.Array   # int32 active workers (≤ the padded static width)
    zone_size: jax.Array   # int32 workers per NUMA zone / socket
    seed: jax.Array        # int32 PRNG seed
    mem_bound: jax.Array   # float32 memory-bound fraction of task runtime
    params: Params
    topo: TopoArrays       # machine topology (flat degenerate by default)
    closed: jax.Array      # bool scalar — closed system (no arrival gating)
    release_ns: jax.Array  # (R,) int32 per-task release stamps (open system)


def make_case(spec: RuntimeSpec | str | int, n_workers: int, zone_size: int,
              seed: int = 0, mem_bound: float = 0.0,
              params: Params | None = None,
              topology: MachineTopology | str | None = None,
              release_ns=None, closed: bool | None = None,
              xp=jnp) -> SweepCase:
    """Lift a runtime configuration to traced scalars.

    ``spec`` accepts a :class:`RuntimeSpec`, a legacy mode name or spec
    slug, or a legacy integer mode id (silently — the deprecation for mode
    strings fires at the public entry points, not in this plumbing).
    ``topology`` accepts a :class:`~repro.core.topology.MachineTopology`
    or preset name; ``None`` is the flat degenerate machine (the legacy
    two-level zone model, bitwise identical to the pre-topology engine).
    Callers passing a topology are expected to pass the matching
    ``zone_size`` (``topology.zone_size_for(n_workers)``).

    ``release_ns`` is the open-system per-task release vector (int ns; see
    :mod:`repro.core.arrivals`); ``None`` is the closed system, where the
    ``closed`` flag routes :func:`~repro.core.phases.spawn_phase` through
    arithmetic bitwise identical to the pre-arrival engine.  ``closed``
    may be forced ``True`` alongside a (zero) vector so closed and open
    cases stack with uniform shapes inside one vmapped chunk.

    ``xp`` is the array module of the leaves.  ``numpy`` keeps the case
    on the host, so that the executors stack a chunk's cases there and
    send them in one transfer: each ``jax.numpy`` scalar is a computation
    of its own on the device, queued behind whatever chunk is running.
    """
    if isinstance(spec, int):
        spec = MODE_SPECS[tuple(MODE_SPECS)[spec]]
    else:
        spec = RuntimeSpec.coerce(spec)
    topo = topology_mod.resolve(topology)
    if closed is None:
        closed = release_ns is None
    release = (xp.zeros((1,), xp.int32) if release_ns is None
               else xp.asarray(np.asarray(release_ns, np.int32)))
    return SweepCase(
        queue_id=xp.int32(spec.queue_id),
        barrier_id=xp.int32(spec.barrier_id),
        balance_id=xp.int32(spec.balance_id),
        n_workers=xp.int32(n_workers),
        zone_size=xp.int32(zone_size), seed=xp.int32(seed),
        mem_bound=xp.float32(mem_bound),
        params=params if params is not None else make_params(xp=xp),
        topo=(topology_mod.degenerate_arrays(xp) if topo is None
              else topo.arrays(xp)),
        closed=xp.asarray(bool(closed)),
        release_ns=release)


class GraphArrays(NamedTuple):
    """Device-side task graph (see taskgraph.py for the encoding).

    ``n_tasks`` is traced so graphs padded to a common length batch together:
    padding tasks are never spawned, never notified, and termination compares
    ``n_done`` against the *true* task count.
    """
    dur: jax.Array
    first_child: jax.Array
    n_children: jax.Array
    notify: jax.Array
    join_dep: jax.Array
    n_tasks: jax.Array    # int32 scalar — true (unpadded) task count
    payload: jax.Array    # (T,) int32 task payload in bytes (cluster D/B)


def graph_arrays(graph: TaskGraph, pad_to: int | None = None,
                 xp=jnp) -> GraphArrays:
    """Lift a host TaskGraph to device arrays, optionally padded to a common
    length with inert tasks (dur 0, no children, no notify target).  With
    ``xp=numpy`` the arrays stay on the host (see :func:`make_case`)."""
    T = graph.n_tasks
    P = max(pad_to or T, T)

    def pad(a, fill):
        a = np.asarray(a, np.int32)
        if P == T:
            return xp.asarray(a)
        out = np.full(P, fill, np.int32)
        out[:T] = a
        return xp.asarray(out)

    payload = (np.zeros(T, np.int32) if graph.payload is None
               else graph.payload)
    return GraphArrays(
        dur=pad(graph.dur, 0), first_child=pad(graph.first_child, 0),
        n_children=pad(graph.n_children, 0), notify=pad(graph.notify, -1),
        join_dep=pad(graph.join_dep, 0), n_tasks=xp.int32(T),
        payload=pad(payload, 0))


class SimState(NamedTuple):
    xq: xqueue.XQ
    cells: messaging.Cells
    rp: dlb.RPState
    # GOMP-mode single global queue
    g_buf: jax.Array
    g_ts: jax.Array
    g_head: jax.Array
    g_tail: jax.Array
    # per-worker spawn stacks of contiguous task-id ranges
    s_task: jax.Array   # (W, S) next task id of the range
    s_cnt: jax.Array    # (W, S) remaining count
    s_top: jax.Array    # (W,)
    # task-graph dynamic state
    join_cnt: jax.Array
    done: jax.Array
    done_ns: jax.Array  # (T,) int32 completion clock per task (-1 = never)
    creator: jax.Array
    # worker state
    clock: jax.Array
    rr: jax.Array
    deq_rr: jax.Array
    idle: jax.Array
    rng: jax.Array
    ctr: jax.Array      # (W, NC) int32
    n_done: jax.Array
    overflow: jax.Array
    step_i: jax.Array
    #: (W,) int32 — bytes each worker pushed over the inter-node bottleneck
    #: *this step*; summed and charged as link occupancy at step end, then
    #: reset (see phases.step_pipeline).  Always zero on non-cluster cases.
    nlink_bytes: jax.Array


@dataclasses.dataclass(frozen=True)
class SimConfig:
    """Static simulator configuration — fixes every array shape (and hence
    the compiled program).  ``backend`` names the step backend composing the
    phase pipeline (see :mod:`repro.core.backends`); ``None`` resolves to
    the ``REPRO_STEP_BACKEND`` environment variable, default ``reference``.
    Backends are bitwise-identical by contract, so the result cache key
    deliberately ignores this field (tests/test_backends.py asserts both)."""
    n_workers: int = 64
    n_zones: int = 8
    queue_cap: int = 16
    stack_cap: int = 512
    max_steps: int = 200_000
    costs: CostModel = DEFAULT_COSTS
    backend: Optional[str] = None


def init_state(g: GraphArrays, W: int, S: int, q_cap: int, gq_cap: int,
               seed: jax.Array) -> SimState:
    """Fresh simulator state: empty queues/cells/stacks, per-lane RNG
    streams derived from ``seed``, and the root task seeded onto worker 0's
    spawn stack as a 1-length range."""
    T = g.dur.shape[0]
    seed32 = jnp.asarray(seed).astype(jnp.uint32)
    st = SimState(
        xq=xqueue.make(W, q_cap),
        cells=messaging.make(W),
        rp=dlb.rp_make(W),
        g_buf=jnp.full((gq_cap,), -1, jnp.int32),
        g_ts=jnp.zeros((gq_cap,), jnp.int32),
        g_head=jnp.int32(0), g_tail=jnp.int32(0),
        s_task=jnp.zeros((W, S), jnp.int32),
        s_cnt=jnp.zeros((W, S), jnp.int32),
        s_top=jnp.zeros((W,), jnp.int32),
        join_cnt=g.join_dep,
        done=jnp.zeros((T,), bool),
        done_ns=jnp.full((T,), -1, jnp.int32),
        creator=jnp.zeros((T,), jnp.int32),
        clock=jnp.zeros((W,), jnp.int32),
        rr=jnp.arange(W, dtype=jnp.int32),      # round-robin starts at master
        deq_rr=jnp.zeros((W,), jnp.int32),
        idle=jnp.zeros((W,), jnp.int32),
        rng=(jnp.arange(W, dtype=jnp.uint32) * jnp.uint32(2654435761)
             + (seed32 * jnp.uint32(40503) + jnp.uint32(1))),
        ctr=jnp.zeros((W, NC), jnp.int32),
        n_done=jnp.int32(0),
        overflow=jnp.asarray(False),
        step_i=jnp.int32(0),
        nlink_bytes=jnp.zeros((W,), jnp.int32),
    )
    return st._replace(
        s_task=st.s_task.at[0, 0].set(0),
        s_cnt=st.s_cnt.at[0, 0].set(1),
        s_top=st.s_top.at[0].set(1),
    )
