"""Executor layer of the experiment service: how a planned chunk runs.

Every executor consumes one :class:`~repro.core.plan.ChunkPlan` against the
shared :class:`ExecContext` (padded graphs + padded ``SimConfig``) and
returns the same per-case raw arrays — bitwise identical across executors,
which is the whole point (tests/test_sweep.py asserts it).  The step body
itself comes from the backend named by ``cfg.backend`` (resolved by
``run_cases``; see repro.core.backends) — orthogonal to the executor axis,
and also bitwise-neutral by contract:

* ``serial``  — one jitted dispatch per case; all cases share one compiled
  shape thanks to the plan's common paddings.  Wins for heterogeneous
  DLB-knob chunks on single-device CPU hosts, where a vmapped chunk is
  straggler-bound (it steps until its slowest member finishes).
* ``vmap``    — today's batched path: stack the chunk, pad it to the plan's
  power-of-two size with *inert* cases, and run one compiled
  ``vmap``-of-steps while loop.
* ``sharded`` — ``shard_map`` of the same batched body over the batch axis
  and ``jax.devices()``: each device drives its own while loop over its
  slice (no collectives, so a device whose slice finishes early stops
  stepping).  Chunks pad up to a device-count multiple; padding lanes are
  inert cases that terminate before their first step.

Inert padding: a padding lane replays the chunk's first case against a
zero-task graph, so the step function's ``running`` gate is false from
step 0 — padding costs (almost) nothing and is dropped on the way out.

Engine mechanics shared by all executors: the initial state is built by a
separate jitted init and *donated* to the run (``donate_argnums`` — XLA
aliases the init buffers into the while-loop carry instead of holding a
dead copy; the sharded path inits through ``shard_map`` so the donated
shardings match), the batched while cond threads a per-lane alive mask
(the vmapped :func:`~repro.core.phases.run_gate`) so a chunk exits as soon
as every lane is finished or stalled, and every executor splits into a
non-blocking ``submit`` + blocking ``collect`` so the sweep layer can
overlap chunk *k+1*'s host-side work with chunk *k*'s device execution.

``strategy="auto"`` picks ``sharded`` whenever more than one device is
visible (e.g. ``XLA_FLAGS=--xla_force_host_platform_device_count=8``, or a
real accelerator mesh), otherwise ``vmap`` with a ``serial`` fallback for
heterogeneous DLB chunks on CPU (measured: docs/BENCHMARKS.md).
"""

from __future__ import annotations

import abc
import dataclasses
import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.core import arrivals as arrivals_mod
from repro.core import backends as backends_mod
from repro.core import dlb
from repro.core import phases as phases_mod
from repro.core.plan import CaseSpec, ChunkPlan
from repro.core.scheduler import (NC, GraphArrays, SimConfig, SweepCase,
                                  _init_cached, _run_cached, init_state,
                                  make_case, make_params)
from repro.core.taskgraph import TaskGraph

#: process-wide engine counters (``benchmarks/run.py --profile`` reads
#: them): ``dispatches`` counts device dispatches (one per serial case /
#: one per batched chunk), ``chunks`` the chunks submitted, ``sim_steps``
#: the simulated scheduling points executed (accumulated by the sweep
#: layer).  ``loop_iterations`` counts the device loops' iterations: each
#: device loops over its slice of a chunk's padded lanes until the slice's
#: slowest lane stops, so a slice runs its largest step count.
#: ``lane_slots`` is Σ padded lanes × iterations over the slices, so
#: ``sim_steps / lane_slots`` is the share of lane-steps that advance a
#: real case.  Reset with :func:`reset_engine_stats`.
ENGINE_STATS = {"dispatches": 0, "chunks": 0, "sim_steps": 0,
                "loop_iterations": 0, "lane_slots": 0}


def reset_engine_stats() -> dict:
    """Zero the engine counters; returns the dict for convenience."""
    for k in ENGINE_STATS:
        ENGINE_STATS[k] = 0
    return ENGINE_STATS


def _count_loops(step_i: np.ndarray, n_slices: int) -> None:
    """Add one chunk's loops to ``ENGINE_STATS``: ``step_i`` holds every
    padded lane's steps, in ``n_slices`` equal contiguous slices that each
    loop until their slowest lane stops."""
    iters = int(step_i.reshape(n_slices, -1).max(axis=1).sum())
    ENGINE_STATS["loop_iterations"] += iters
    ENGINE_STATS["lane_slots"] += iters * (step_i.size // n_slices)


def span(name: str, **args):
    """A host span ``repro.<name>`` on the profiler's clock.  A TraceMe:
    with no profiler running it costs a check.  Spans are per call or per
    chunk, never per case or per loop iteration; ``args`` identify the
    call and chunk they belong to."""
    return jax.profiler.TraceAnnotation("repro." + name, **args)


class ChunkRaw(NamedTuple):
    """Per-case raw outputs of one chunk, real cases only (padding dropped)."""
    clock: np.ndarray      # (n, W) int
    ctr: np.ndarray        # (n, W, NC) int
    n_done: np.ndarray     # (n,)
    overflow: np.ndarray   # (n,) bool
    step_i: np.ndarray     # (n,)
    done_ns: np.ndarray    # (n, T) int — per-task completion stamps


@dataclasses.dataclass(frozen=True)
class ExecContext:
    """Shared executor inputs fixed by the plan: padded config + graphs.

    ``release_len`` is the shared length of every case's traced release
    vector — the plan's ``t_pad`` when any case in the run is open-system,
    else the closed system's 1-length placeholder.  Uniform length keeps
    closed and open cases stackable inside one vmapped chunk; closed cases
    carry a zero vector with ``closed=True``, which spawn_phase routes
    through the exact pre-arrival arithmetic.
    """
    cfg: SimConfig                   # n_workers == the plan's w_pad
    gq_cap: int
    graphs: Sequence[TaskGraph]
    garr: Sequence[GraphArrays]      # host arrays padded to the plan's t_pad
    release_len: int = 1
    call: int = 0                    # the run_cases call (names its spans)

    def case_for(self, s: CaseSpec) -> SweepCase:
        """The case of ``s``, with host (numpy) leaves."""
        if s.arrivals is None and self.release_len == 1:
            release = None
        else:
            release = arrivals_mod.padded_release(
                s.arrivals, self.graphs[s.graph].n_tasks, s.seed,
                self.release_len)
        return make_case(
            s.spec, s.n_workers, s.zone_size, s.seed,
            round(float(self.graphs[s.graph].mem_bound), 3),
            make_params(s.n_victim, s.n_steal, s.t_interval, s.p_local,
                        s.p_local_node, xp=np),
            topology=s.topology, release_ns=release,
            closed=s.arrivals is None, xp=np)


def _init_body(cfg: SimConfig, gq_cap: int, gb, cb: SweepCase):
    """Fresh stacked state for a chunk — split from the run body so the run
    jit can *donate* the state (see ``_run_batch``)."""

    def init_one(g, case):
        return init_state(g, cfg.n_workers, cfg.stack_cap, cfg.queue_cap,
                          gq_cap, case.seed)

    return jax.vmap(init_one)(gb, cb)


_init_batch = jax.jit(_init_body, static_argnums=(0, 1))


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _init_batch_sharded(cfg: SimConfig, gq_cap: int, n_dev: int, gb,
                        cb: SweepCase):
    """Sharded init: the produced state is laid out ``P("b")`` on the same
    mesh the run uses, so donating it to ``_run_batch_sharded`` aliases
    buffers in place (a single-device state would defeat the donation —
    mismatched shardings can't alias)."""
    mesh = Mesh(np.asarray(jax.devices()[:n_dev]), ("b",))
    return jax.shard_map(functools.partial(_init_body, cfg, gq_cap),
                         mesh=mesh, in_specs=(P("b"), P("b")),
                         out_specs=P("b"), check_vma=False)(gb, cb)


def _batch_body(cfg: SimConfig, gq_cap: int, gb, cb: SweepCase, st0):
    """Run a stacked batch of (graph, case) pairs to completion.

    The while loop is written manually over vmapped *steps* rather than
    vmapping the whole per-config run: the step function is a strict no-op
    for finished elements (the step body's internal ``running`` gate), so
    the loop needs no per-element freeze — which would otherwise
    materialize a select over the entire simulator state every iteration.

    The loop carry additionally threads the per-lane alive mask (the
    vmapped :func:`~repro.core.phases.run_gate`, the *same* predicate the
    step gates on), recomputed after each sweep of steps: the chunk exits
    as soon as every lane is finished **or stalled**, instead of dragging
    a deadlocked lane to the padded max-step horizon.  Rows stay bitwise
    identical to the serial executor's because the gate freezes each lane's
    ``step_i``/clock at the same step everywhere.  Each lane's victim-weight
    tables (:func:`~repro.core.dlb.victim_tables`) are built once, before
    the loop, and enter every step as a loop-invariant operand.  Returns
    only the arrays the host needs (clock, counters, termination info)."""

    backend = backends_mod.get_backend(cfg.backend)
    tb = jax.vmap(functools.partial(dlb.victim_tables, cfg.n_workers))(cb)

    def step_one(g, case, tables, st):
        return backend.build_step(cfg.n_workers, cfg.stack_cap, cfg.costs,
                                  g, case, cfg.max_steps, tables)(st)

    def gate_one(g, st):
        return phases_mod.run_gate(st, g, cfg.max_steps)

    step_b = jax.vmap(step_one)
    gate_b = jax.vmap(gate_one)

    def cond(carry):
        return jnp.any(carry[0])

    def body(carry):
        st = step_b(gb, cb, tb, carry[1])
        return gate_b(gb, st), st

    # the *full* final state is returned (not just the host-visible
    # arrays): donation aliases inputs to outputs, so every donated st0
    # leaf needs a matching output leaf to land in.  The host only fetches
    # the ChunkRaw fields; the rest is dropped with the pending handle.
    _, st = jax.lax.while_loop(cond, body, (gate_b(gb, st0), st0))
    return st


#: the stacked state is donated (built by ``_init_batch`` /
#: ``_init_batch_sharded`` and never reused): XLA aliases its buffers into
#: the while-loop carry instead of keeping a dead full-SimState copy live
_run_batch = jax.jit(_batch_body, static_argnums=(0, 1),
                     donate_argnums=(4,))


@functools.partial(jax.jit, static_argnums=(0, 1, 2), donate_argnums=(5,))
def _run_batch_sharded(cfg: SimConfig, gq_cap: int, n_dev: int, gb,
                       cb: SweepCase, st0):
    """``shard_map`` of the batched body over the leading batch axis.

    Each device traces the identical per-shard program (the body has no
    collectives), so results are bitwise those of ``_run_batch`` on the
    same lanes — sharding only changes *where* a lane runs.  Every device
    drives its own alive-mask loop over its slice, so a device whose lanes
    all finish (or stall) stops stepping early."""
    mesh = Mesh(np.asarray(jax.devices()[:n_dev]), ("b",))
    body = functools.partial(_batch_body, cfg, gq_cap)
    # check_vma=False: every in/out is batch-sharded, nothing is replicated
    return jax.shard_map(body, mesh=mesh, in_specs=(P("b"), P("b"), P("b")),
                         out_specs=P("b"), check_vma=False)(gb, cb, st0)


def _stack_chunk(ctx: ExecContext, specs_chunk: Sequence[CaseSpec],
                 padded: int):
    """Stack a chunk's graphs and cases, padding with inert lanes.  Both
    are stacked on the host and sent in one transfer: stacking the next
    chunk puts no computation in the device queue behind the running
    chunk, so it need not wait for that chunk to end."""
    cases = [ctx.case_for(s) for s in specs_chunk]
    garrs = [ctx.garr[s.graph] for s in specs_chunk]
    if padded > len(specs_chunk):
        # zero-task graph: the lane's running gate is false from step 0
        inert = garrs[0]._replace(n_tasks=np.int32(0))
        garrs = garrs + [inert] * (padded - len(specs_chunk))
        cases = cases + [cases[0]] * (padded - len(cases))

    def stack(parts):
        return jax.tree_util.tree_map(lambda *xs: np.stack(xs), *parts)

    return jax.device_put((stack(garrs), stack(cases)))


class Executor(abc.ABC):
    """One way of running a planned chunk.  Stateless; see EXECUTORS.

    The run is split into a non-blocking ``submit`` (host-side stacking +
    init + async device dispatch — JAX dispatch returns before the device
    finishes) and a blocking ``collect`` (device→host fetch).  The split is
    what lets :func:`repro.core.sweep.run_cases` pipeline chunks: chunk
    *k+1*'s planning/stacking/dispatch overlaps chunk *k*'s execution.
    ``run_chunk`` remains the submit-then-collect composition."""

    name: str = "?"

    def padded_size(self, chunk: ChunkPlan) -> int:
        """Lanes the chunk's device loops run, padding included."""
        return chunk.n_real

    def chunk_args(self, ctx: ExecContext, chunk: ChunkPlan) -> dict:
        """The identifiers every span of one chunk carries."""
        return dict(call=ctx.call, chunk=chunk.index, lanes=chunk.n_real,
                    padded=self.padded_size(chunk))

    @abc.abstractmethod
    def submit(self, ctx: ExecContext, specs: Sequence[CaseSpec],
               chunk: ChunkPlan):
        """Dispatch ``chunk.indices`` of ``specs`` without blocking;
        returns an opaque pending handle for ``collect``."""

    @abc.abstractmethod
    def collect(self, pending) -> ChunkRaw:
        """Block on a ``submit`` handle; rows follow chunk order."""

    def run_chunk(self, ctx: ExecContext, specs: Sequence[CaseSpec],
                  chunk: ChunkPlan) -> ChunkRaw:
        """Run ``chunk.indices`` of ``specs``; rows follow chunk order."""
        return self.collect(self.submit(ctx, specs, chunk))


class SerialExecutor(Executor):
    name = "serial"

    def submit(self, ctx, specs, chunk):
        args = self.chunk_args(ctx, chunk)
        with span("submit", **args):
            with span("stack", **args):
                cases = [(ctx.garr[specs[i].graph], ctx.case_for(specs[i]))
                         for i in chunk.indices]
            # each case's init is dispatched right before its run, so the
            # chunk's initial states are not all made up front: serial has
            # no init span
            with span("dispatch", **args):
                states = [_run_cached(ctx.cfg, ctx.gq_cap, garr, case,
                                      _init_cached(ctx.cfg, ctx.gq_cap, garr,
                                                   case))
                          for garr, case in cases]
        ENGINE_STATS["dispatches"] += len(states)
        ENGINE_STATS["chunks"] += 1
        return states, args

    def collect(self, pending):
        states, args = pending
        n = len(states)
        W = states[0].clock.shape[0]
        T = states[0].done_ns.shape[0]
        clock = np.zeros((n, W), np.int64)
        ctr = np.zeros((n, W, NC), np.int64)
        n_done = np.zeros(n, np.int64)
        overflow = np.zeros(n, bool)
        step_i = np.zeros(n, np.int64)
        done_ns = np.zeros((n, T), np.int64)
        with span("collect", **args):
            with span("wait", **args):
                states = jax.block_until_ready(states)
            with span("fetch", **args):
                for j, st in enumerate(states):
                    clock[j] = np.asarray(st.clock)
                    ctr[j] = np.asarray(st.ctr)
                    n_done[j] = int(st.n_done)
                    overflow[j] = bool(st.overflow)
                    step_i[j] = int(st.step_i)
                    done_ns[j] = np.asarray(st.done_ns)
        _count_loops(step_i, n)          # one one-lane loop per case
        return ChunkRaw(clock, ctr, n_done, overflow, step_i, done_ns)


class VmapExecutor(Executor):
    name = "vmap"

    def padded_size(self, chunk: ChunkPlan) -> int:
        return chunk.padded_size

    def n_devices(self) -> int:
        """Devices a chunk's lanes are split over, in contiguous slices."""
        return 1

    def submit(self, ctx, specs, chunk):
        args = self.chunk_args(ctx, chunk)
        with span("submit", **args):
            with span("stack", **args):
                gb, cb = _stack_chunk(ctx, [specs[i] for i in chunk.indices],
                                      args["padded"])
            with span("init", **args):
                st0 = self._init(ctx, gb, cb)
            with span("dispatch", **args):
                st = self._run(ctx, gb, cb, st0)
        ENGINE_STATS["dispatches"] += 1
        ENGINE_STATS["chunks"] += 1
        return st, args

    def collect(self, pending):
        st, args = pending
        n = args["lanes"]
        with span("collect", **args):
            with span("wait", **args):
                st = jax.block_until_ready(st)
            with span("fetch", **args):
                step_i = np.asarray(st.step_i)
                raw = ChunkRaw(np.asarray(st.clock)[:n],
                               np.asarray(st.ctr)[:n],
                               np.asarray(st.n_done)[:n],
                               np.asarray(st.overflow)[:n], step_i[:n],
                               np.asarray(st.done_ns)[:n])
        _count_loops(step_i, self.n_devices())
        return raw

    def _init(self, ctx, gb, cb):
        return _init_batch(ctx.cfg, ctx.gq_cap, gb, cb)

    def _run(self, ctx, gb, cb, st0):
        return _run_batch(ctx.cfg, ctx.gq_cap, gb, cb, st0)


class ShardedExecutor(VmapExecutor):
    name = "sharded"

    def padded_size(self, chunk: ChunkPlan) -> int:
        # device multiple on top of the plan's power of two, so compiled
        # shapes stay shared *and* every shard gets equal lanes
        n_dev = jax.device_count()
        p = chunk.padded_size
        return -(-p // n_dev) * n_dev

    def n_devices(self) -> int:
        return jax.device_count()

    def _init(self, ctx, gb, cb):
        return _init_batch_sharded(ctx.cfg, ctx.gq_cap, jax.device_count(),
                                   gb, cb)

    def _run(self, ctx, gb, cb, st0):
        return _run_batch_sharded(ctx.cfg, ctx.gq_cap, jax.device_count(),
                                  gb, cb, st0)


EXECUTORS = {e.name: e for e in
             (SerialExecutor(), VmapExecutor(), ShardedExecutor())}

#: accepted ``strategy=`` values; "batched" is the historical alias of vmap
STRATEGIES = ("auto",) + tuple(EXECUTORS) + ("batched",)


def select_executor(strategy: str, chunk: ChunkPlan) -> Executor:
    """Resolve a strategy to an executor for one chunk.

    ``auto``: sharded when >1 device is visible; otherwise vmap, except for
    heterogeneous DLB-knob chunks on CPU where per-case dispatch measures
    faster (straggler-bound batches; docs/BENCHMARKS.md)."""
    assert strategy in STRATEGIES, (strategy, STRATEGIES)
    if strategy == "batched":
        return EXECUTORS["vmap"]
    if strategy != "auto":
        return EXECUTORS[strategy]
    if jax.device_count() > 1:
        return EXECUTORS["sharded"]
    if (chunk.hetero_dlb and chunk.n_real > 1
            and jax.default_backend() == "cpu"):
        return EXECUTORS["serial"]
    return EXECUTORS["vmap"]
