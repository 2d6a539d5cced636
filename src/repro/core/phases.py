"""Phase layer: the scheduler step as five pure, individually-jittable maps.

The paper's runtime does five orthogonal things per scheduling point —
push spawned tasks, dequeue, run the thief protocol, answer steal requests
as a victim, and execute — over the XQueue / messaging-cell / DLB state.
Each is a pure ``(state, case, …) -> state`` function here, jittable on its
own (``jax.jit(phase, static_argnames=("costs", "ops"))``), vmap-safe (all
spec branching is mask arithmetic over the traced axis ids), and padded-lane
inert (lanes ``>= case.n_workers`` never change; tests/test_phases.py
proves it per phase).

Read/write footprints (fields of :class:`~repro.core.state.SimState`; every
phase also reads ``case`` and may bump ``ctr`` / advance ``clock``):

=============== =========================================== ================
phase           reads                                       writes
=============== =========================================== ================
adopt_phase     s_top, cells, rp                            rp, cells.round
spawn_phase     s_task/s_cnt/s_top, rr, rp, xq, g_*, clock  xq, g_*, s_*,
                                                            rr, rp, creator,
                                                            done/join/n_done
dequeue_phase   s_top, xq, g_*, deq_rr, clock               xq.head, g_head,
                                                            deq_rr,
                                                            nlink_bytes
thief_phase     s_top, idle, rng, cells, clock              idle, rng,
                                                            cells.req_*,
                                                            nlink_bytes
victim_phase    cells, xq, deq_rr, rp, clock                xq, rp,
                                                            cells.round,
                                                            nlink_bytes
exec_phase      creator, clock                              clock, done,
                                                            join_cnt,
                                                            creator, n_done,
                                                            s_* (spawns)
=============== =========================================== ================

Queue-touching inner kernels are pluggable: every phase takes a
:class:`StepOps` bundle — the XQueue push / pop-scan and the one-hot
counter bump — so a backend (:mod:`repro.core.backends`) can swap the
reference jnp implementations for Pallas kernels without touching phase
logic.  Backends must be bitwise identical (tests/test_backends.py).

Every cross-worker latency (``_comm``), the thief's victim choice, and the
memory-bound execution penalty consult the machine topology carried in
``case.topo`` (:mod:`repro.core.topology`): flat machines keep the
historical two-level ``c_zone``/``c_numa`` arithmetic bitwise, hierarchical
machines pay distance-matrix costs.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import dlb, messaging, xqueue
from repro.core import topology as topology_mod
from repro.core.costs import CostModel
from repro.core.state import (CTR, K_SPAWN, NV_CAP, WS_CAP, GraphArrays,
                              SimState, SweepCase)


class StepOps(NamedTuple):
    """The pluggable inner kernels of the step body (a backend's identity).

    ``push``/``pop_first`` carry :func:`xqueue.push` / :func:`xqueue.pop_first`
    signatures; ``ctr_add(ctr, col, val)`` adds the (W,) int32 ``val`` into
    counter column ``col``.  Implementations must be bitwise identical to the
    reference — the result cache relies on it.
    """
    name: str
    push: Callable
    pop_first: Callable
    ctr_add: Callable


def _ctr_add_ref(ctr: jax.Array, col: int, val: jax.Array) -> jax.Array:
    return ctr.at[:, col].add(val)


#: today's pure-jnp kernels (mask arithmetic / one-hot writes)
REFERENCE_OPS = StepOps(name="reference", push=xqueue.push,
                        pop_first=xqueue.pop_first, ctr_add=_ctr_add_ref)


class AxisMasks(NamedTuple):
    """Per-axis feature gates derived from a case's traced spec-axis ids."""
    is_locked: jax.Array   # locked_global queue lane
    uses_xq: jax.Array     # xqueue lane
    pays_count: jax.Array  # pays the centralized barrier's atomic count
    is_narp: jax.Array
    is_naws: jax.Array
    is_dlb: jax.Array


def axis_masks(case: SweepCase) -> AxisMasks:
    """Traced scalars selecting each lattice axis's machinery (see
    repro.core.spec for the ids).  The centralized barrier's global task
    count is a separate contended atomic only for xqueue runtimes — under
    the locked_global queue the count update rides the already-held task
    lock (legacy gomp behavior)."""
    is_locked = case.queue_id == 0
    uses_xq = ~is_locked
    pays_count = uses_xq & (case.barrier_id == 0)
    is_narp = case.balance_id == 1
    is_naws = case.balance_id == 2
    return AxisMasks(is_locked=is_locked, uses_xq=uses_xq,
                     pays_count=pays_count, is_narp=is_narp,
                     is_naws=is_naws, is_dlb=is_narp | is_naws)


def _me(st: SimState) -> jax.Array:
    return jnp.arange(st.s_top.shape[0], dtype=jnp.int32)


def _comm(costs: CostModel, a, b, case: SweepCase):
    """Lock-less latency of worker ``a`` touching a line owned by ``b``.

    Flat machine: the historical two-level model (``c_zone`` intra-zone,
    ``c_numa`` anywhere else).  Non-flat topology: a distance-matrix lookup
    between the endpoints' NUMA domains — steal requests, queue transfers,
    and redirected pushes all pay the *actual* inter-socket distance, which
    is what makes hierarchy-aware balancing measurable.
    """
    t = case.topo
    zsz = case.zone_size
    same = a == b
    same_zone = (a // zsz) == (b // zsz)
    legacy = jnp.where(same_zone, costs.c_zone, costs.c_numa)
    hier = t.dist[topology_mod.domain_of(a, zsz, t.n_domains),
                  topology_mod.domain_of(b, zsz, t.n_domains)]
    return jnp.where(same, costs.c_cache,
                     jnp.where(t.flat, legacy, hier)).astype(jnp.int32)


def _same_domain(a, b, case: SweepCase):
    """Do workers ``a`` and ``b`` share a NUMA domain?  Flat machines use
    the raw zone grid; hierarchical ones the *clipped* domain ids, so
    remainder workers absorbed into the last socket (when ``n_workers`` is
    not a socket multiple) classify consistently with ``_comm``'s pricing.
    """
    t = case.topo
    zsz = case.zone_size
    flat_eq = (a // zsz) == (b // zsz)
    hier_eq = (topology_mod.domain_of(a, zsz, t.n_domains)
               == topology_mod.domain_of(b, zsz, t.n_domains))
    return jnp.where(t.flat, flat_eq, hier_eq)


def _same_node(a, b, case: SweepCase):
    """Do workers ``a`` and ``b`` share a *node* (cluster tier)?
    Trivially true off-cluster, so every ``~_same_node`` gate below is
    identically false on flat and single-node machines."""
    t = case.topo
    zsz = case.zone_size
    na = t.node[topology_mod.domain_of(a, zsz, t.n_domains)]
    nb = t.node[topology_mod.domain_of(b, zsz, t.n_domains)]
    return jnp.where(t.cluster, na == nb, True)


def _xfer(a, b, case: SweepCase, nbytes):
    """The ``D/B`` payload term of a cross-worker link charge: ``nbytes``
    over the endpoints' link bandwidth.  Identically zero off-cluster and
    on self-links — the bitwise contract for flat and single-node
    machines (they never read ``topo.bw``)."""
    t = case.topo
    zsz = case.zone_size
    bw = t.bw[topology_mod.domain_of(a, zsz, t.n_domains),
              topology_mod.domain_of(b, zsz, t.n_domains)]
    chg = (nbytes // jnp.maximum(bw, 1)).astype(jnp.int32)
    return jnp.where(t.cluster & (a != b), chg, 0)


def _comm_sz(costs: CostModel, a, b, case: SweepCase, nbytes):
    """Full link price ``L + D/B``: the distance-matrix latency plus the
    payload transfer time (cluster topologies only — see topology.py)."""
    return _comm(costs, a, b, case) + _xfer(a, b, case, nbytes)


def _track_xnode(st: SimState, a, b, case: SweepCase, nbytes, mask
                 ) -> SimState:
    """Accrue cross-node bytes into the per-step bottleneck ledger
    (``nlink_bytes``); :func:`step_pipeline` converts the step's total
    into a shared-uplink occupancy charge and resets the ledger."""
    xn = mask & case.topo.cluster & ~_same_node(a, b, case)
    add = jnp.where(xn, nbytes, 0).astype(jnp.int32)
    return st._replace(nlink_bytes=st.nlink_bytes + add)


def _bump(ops: StepOps, ctr, name, mask_or_val):
    v = mask_or_val.astype(jnp.int32) if mask_or_val.dtype == bool \
        else mask_or_val
    return ops.ctr_add(ctr, CTR[name], v)


def _stack_push(st: SimState, mask, task0, cnt) -> SimState:
    W, S = st.s_task.shape
    idx = jnp.where(mask & (st.s_top < S), st.s_top, S)
    # one entry per worker row: one-hot select, not a scatter (idx == S
    # matches no column, preserving the drop semantics)
    one = jnp.arange(S, dtype=jnp.int32)[None, :] == idx[:, None]
    s_task = jnp.where(one, task0[:, None], st.s_task)
    s_cnt = jnp.where(one, cnt[:, None], st.s_cnt)
    s_top = st.s_top + (mask & (st.s_top < S)).astype(jnp.int32)
    overflow = st.overflow | jnp.any(mask & (st.s_top >= S))
    return st._replace(s_task=s_task, s_cnt=s_cnt, s_top=s_top,
                       overflow=overflow)


def _finish(st: SimState, ftask, g: GraphArrays) -> SimState:
    """Completion bookkeeping for per-worker finished tasks (-1 = none):
    spawn-range entries go on the finisher's own stack; the notify target's
    dependency count drops; a join reaching zero is claimed by exactly one
    finisher (scatter-min tie-break) who 'creates' it."""
    W = st.s_top.shape[0]
    T = g.dur.shape[0]
    me = _me(st)
    active = ftask >= 0
    safe = jnp.where(active, ftask, 0)
    done = st.done.at[jnp.where(active, ftask, T)].set(True, mode="drop")
    # completion stamp: the finisher's clock already includes the task's
    # execution time at both call sites (exec_phase and the
    # execute-immediately rule), so this is the task's finish time
    done_ns = st.done_ns.at[jnp.where(active, ftask, T)].max(
        st.clock, mode="drop")
    n_done = st.n_done + jnp.sum(active, dtype=jnp.int32)
    st = st._replace(done=done, done_ns=done_ns, n_done=n_done)
    # spawned children: one O(1) range entry
    nch = jnp.where(active, g.n_children[safe], 0)
    st = _stack_push(st, nch > 0, g.first_child[safe], nch)
    # notify join
    j = jnp.where(active, g.notify[safe], -1)
    jsafe = jnp.where(j >= 0, j, T)
    join_cnt = st.join_cnt.at[jsafe].add(-1, mode="drop")
    newly = (j >= 0) & (join_cnt[jnp.where(j >= 0, j, 0)] == 0)
    st = st._replace(join_cnt=join_cnt)

    # a join becomes ready only occasionally; the (T,)-sized claim
    # machinery runs behind a one-shot while so other steps skip it
    def cond(carry):
        return carry[0] & jnp.any(newly)

    def body(carry):
        _, st_c = carry
        # the lowest-id finisher among those completing the same join claims
        # it — a (W, W) pairwise tie-break, equivalent to the scatter-min
        # over task ids but without materializing a (T,)-sized array
        same = newly[:, None] & newly[None, :] & (j[:, None] == j[None, :])
        mine = newly & (jnp.argmax(same, axis=1).astype(jnp.int32) == me)
        creator = st_c.creator.at[jnp.where(mine, j, T)].set(me, mode="drop")
        st_c = _stack_push(st_c._replace(creator=creator), mine, j,
                           jnp.ones(W, jnp.int32))
        return jnp.asarray(False), st_c

    _, st = jax.lax.while_loop(cond, body, (jnp.asarray(True), st))
    return st


def _atomic_charge(st: SimState, mask, costs: CostModel,
                   ops: StepOps) -> SimState:
    """Contended RMWs on one shared cache line (XGOMP's global task count):
    simultaneous writers serialize; the k-th pays k hand-offs."""
    rank = jnp.cumsum(mask.astype(jnp.int32)) - 1
    cost = jnp.where(mask, costs.c_atomic + rank * costs.c_contend, 0)
    return st._replace(clock=st.clock + cost,
                       ctr=_bump(ops, st.ctr, "atomic_ops", mask))


# ---------------- pre-push victim adoption (NA-RP spawners) ----------------
def adopt_phase(st: SimState, running, *, case: SweepCase,
                costs: CostModel, ops: StepOps = REFERENCE_OPS) -> SimState:
    """NA-RP: spawning workers are victims too — adopt a thief pre-push.

    Reads s_top / cells / rp; writes rp, cells.round, ctr[req_handled].
    """
    del costs  # uniform phase signature; adoption itself is free
    m = axis_masks(case)
    spawner = (st.s_top > 0) & m.is_narp & running
    valid0 = messaging.victim_valid(st.cells) & spawner
    rp, _ = dlb.rp_adopt(st.rp, jnp.maximum(st.cells.req_tid, 0),
                         case.params.n_steal, valid0)
    return st._replace(
        rp=rp, cells=messaging.victim_advance(st.cells, valid0),
        ctr=_bump(ops, st.ctr, "req_handled", valid0))


# ---------------- phase A: push spawned tasks ----------------
def spawn_phase(st: SimState, running, *, g: GraphArrays, case: SweepCase,
                costs: CostModel, ops: StepOps = REFERENCE_OPS) -> SimState:
    """Each worker with a non-empty spawn stack pushes up to ``K_SPAWN``
    tasks: the locked_global lane pays the serialized lock + pq + malloc,
    the xqueue lane pushes to the round-robin (or NA-RP-redirected) target
    queue, full targets trigger the paper's execute-immediately rule.

    Reads s_*/rr/rp/xq/g_*/clock; writes xq (via ``ops.push``), g_buf/g_ts/
    g_tail, s_*, rr, rp, creator, clock, ctr, and — through the
    execute-immediately rule — done/join_cnt/n_done.
    """
    W, S = st.s_task.shape
    T = g.dur.shape[0]
    me = _me(st)
    m = axis_masks(case)
    n_w = case.n_workers

    for _ in range(K_SPAWN):
        avail = (st.s_top > 0) & running
        topi = jnp.maximum(st.s_top - 1, 0)
        etask = st.s_task[me, topi]
        ecnt = st.s_cnt[me, topi]
        # open-system injection gate: a task enters the runtime only once
        # the worker's clock reaches its release stamp; case.closed skips
        # the gate entirely (bitwise the pre-arrival arithmetic).  A
        # blocked spawner sleeps forward to the head task's release —
        # without the sleep its clock could freeze (a worker with a
        # non-empty stack never dequeues), deadlocking the injection.
        R = case.release_ns.shape[0]
        rel = case.release_ns[jnp.clip(etask, 0, R - 1)]
        released = case.closed | (st.clock >= rel)
        active = avail & released
        st = st._replace(clock=jnp.where(avail & ~released, rel, st.clock))
        task = jnp.where(active, etask, 0)

        # --- GOMP lane: serialized global-lock push (lock + pq + malloc)
        act_g = active & m.is_locked
        rank_g = jnp.cumsum(act_g.astype(jnp.int32)) - 1
        cost_g = jnp.where(
            act_g,
            costs.c_atomic + costs.c_pq_op + costs.c_alloc
            + rank_g * costs.c_lock, 0)

        # --- XQueue lane (all other modes), with NA-RP redirection
        act_x = active & m.uses_xq
        use_rp = act_x & m.is_narp & (st.rp.tgt >= 0) & (st.rp.left > 0)
        tgt = jnp.where(use_rp, jnp.maximum(st.rp.tgt, 0), st.rr % n_w)
        # pushing to a remote queue moves the task's payload: L + D/B on
        # cluster machines, the bare latency everywhere else
        pay = jnp.where(act_x, g.payload[task], 0)
        cost_x = jnp.where(
            act_x,
            costs.c_alloc + costs.c_slot
            + _comm_sz(costs, me, tgt, case, pay), 0)

        clock = st.clock + cost_g + cost_x
        gq = st.g_buf.shape[0]
        gidx = jnp.where(act_g, (st.g_tail + rank_g) % gq, gq)
        g_buf = st.g_buf.at[gidx].set(task, mode="drop")
        g_ts = st.g_ts.at[gidx].set(clock, mode="drop")
        g_tail = st.g_tail + jnp.sum(act_g, dtype=jnp.int32)

        with jax.named_scope("xq"):
            xq, ok = ops.push(st.xq, me, tgt, task, clock, act_x)
        pushed_x = ok
        imm = act_x & ~ok
        rr = st.rr + (act_x & ~use_rp).astype(jnp.int32)
        creator = st.creator.at[
            jnp.where(active, task, T)].set(me, mode="drop")

        ctr = _bump(ops, st.ctr, "static_push",
                    act_g | (pushed_x & ~use_rp))
        ctr = _bump(ops, ctr, "atomic_ops", act_g)
        same_d = _same_domain(me, tgt, case)
        ctr = _bump(ops, ctr, "stolen", pushed_x & use_rp)  # redirections
        ctr = _bump(ops, ctr, "stolen_local", pushed_x & use_rp & same_d)
        ctr = _bump(ops, ctr, "stolen_remote", pushed_x & use_rp & ~same_d)
        ctr = _bump(ops, ctr, "stolen_xnode",
                    pushed_x & use_rp & ~_same_node(me, tgt, case))
        # Alg. 3: stop on quota exhausted or thief queue full
        left = st.rp.left - (pushed_x & use_rp).astype(jnp.int32)
        drop = (use_rp & ~ok) | (left <= 0)
        rp = dlb.RPState(tgt=jnp.where(drop, -1, st.rp.tgt),
                         left=jnp.where(drop, 0, left))
        ctr = _bump(ops, ctr, "tgt_full", use_rp & ~ok)
        st = st._replace(xq=xq, g_buf=g_buf, g_ts=g_ts, g_tail=g_tail,
                         clock=clock, rr=rr, rp=rp, ctr=ctr,
                         creator=creator)
        st = _track_xnode(st, me, tgt, case, pay, act_x)
        # atomic global count: task created (XGOMP only)
        st = _atomic_charge(st, active & m.pays_count, costs, ops)

        # consume one task from the range entry (one-hot row update)
        sidx = jnp.where(active, topi, S)
        one = jnp.arange(S, dtype=jnp.int32)[None, :] == sidx[:, None]
        s_task = jnp.where(one, (etask + 1)[:, None], st.s_task)
        s_cnt = jnp.where(one, (ecnt - 1)[:, None], st.s_cnt)
        s_top = jnp.where(active & (ecnt - 1 == 0), st.s_top - 1,
                          st.s_top)
        st = st._replace(s_task=s_task, s_cnt=s_cnt, s_top=s_top)

        # execute-immediately rule for full target queues (paper §II-B):
        # queues rarely fill, so the whole block is a one-shot while
        def imm_cond(carry):
            return carry[0] & jnp.any(imm)

        def imm_body(carry):
            _, st_c = carry
            dur_t = jnp.where(imm, g.dur[task], 0)
            ctr = _bump(ops, st_c.ctr, "imm_exec", imm)
            ctr = _bump(ops, ctr, "exec", imm)
            ctr = _bump(ops, ctr, "self", imm)
            ctr = _bump(ops, ctr, "busy_ns", dur_t)
            st_c = st_c._replace(clock=st_c.clock + dur_t, ctr=ctr)
            st_c = _finish(st_c, jnp.where(imm, task, -1), g)
            # task finished -> atomic decrement (XGOMP only)
            st_c = _atomic_charge(st_c, imm & m.pays_count, costs, ops)
            return jnp.asarray(False), st_c

        _, st = jax.lax.while_loop(imm_cond, imm_body,
                                   (jnp.asarray(True), st))
    return st


# ---------------- phase B: dequeue ----------------
def dequeue_phase(st: SimState, running, *, g: GraphArrays, case: SweepCase,
                  costs: CostModel, ops: StepOps = REFERENCE_OPS):
    """Workers with empty spawn stacks pop one task — the locked_global lane
    from the single contended global queue, the xqueue lane by scanning its
    master queue then the rotated auxiliaries (``ops.pop_first``).

    Reads s_top/xq/g_*/deq_rr/clock; writes xq.head, g_head, deq_rr, clock,
    ctr.  Returns ``(st, task, ts, found)`` for the downstream phases.
    Popping from another worker's queue drags the task's payload across
    the link (``L + D/B`` on cluster machines via ``g.payload``).
    """
    me = _me(st)
    m = axis_masks(case)
    n_w = case.n_workers
    active_w = me < n_w
    idle_m = (st.s_top == 0) & active_w & running

    # --- GOMP lane: contended pops off the single global queue
    idle_g = idle_m & m.is_locked
    avail = st.g_tail - st.g_head
    rank = jnp.cumsum(idle_g.astype(jnp.int32)) - 1
    found_g = idle_g & (rank < avail)
    gq = st.g_buf.shape[0]
    gidx = (st.g_head + rank) % gq
    task_g = jnp.where(found_g, st.g_buf[gidx], 0)
    ts_g = jnp.where(found_g, st.g_ts[gidx], 0)
    g_head = st.g_head + jnp.sum(found_g, dtype=jnp.int32)
    cost_g = jnp.where(idle_g,
                       costs.c_atomic + costs.c_pq_op
                       + rank * costs.c_lock, 0)
    ctr = _bump(ops, st.ctr, "atomic_ops", idle_g)

    # --- XQueue lane: master queue then rotated aux scan
    idle_x = idle_m & m.uses_xq
    with jax.named_scope("xq"):
        xq, task_x, ts_x, src, found_x, checked = ops.pop_first(
            st.xq, st.deq_rr, idle_x, n_w)
    pay_x = jnp.where(found_x, g.payload[jnp.where(found_x, task_x, 0)], 0)
    cost_x = jnp.where(idle_x, checked * costs.c_cache, 0)
    cost_x = cost_x + jnp.where(found_x,
                                _comm_sz(costs, me, src, case, pay_x), 0)
    deq_rr = st.deq_rr + (found_x & (src != me)).astype(jnp.int32)

    task = jnp.where(m.is_locked, task_g, task_x)
    ts = jnp.where(m.is_locked, ts_g, ts_x)
    found = found_g | found_x
    st = st._replace(xq=xq, g_head=g_head, deq_rr=deq_rr, ctr=ctr,
                     clock=st.clock + cost_g + cost_x)
    st = _track_xnode(st, me, src, case, pay_x, found_x)
    return st, task, ts, found


# ---------------- phase B2: thief protocol ----------------
def thief_phase(st: SimState, found, running, *, case: SweepCase,
                tables: dlb.VictimTables, costs: CostModel,
                ops: StepOps = REFERENCE_OPS) -> SimState:
    """Idle workers that found nothing send steal requests to up to
    ``n_victim`` random victims (Alg. 1), on their first idle step and every
    ``t_interval`` thereafter.  ``tables`` are the case's victim-weight
    tables (:func:`repro.core.dlb.victim_tables`).

    Reads s_top/idle/rng/cells/clock; writes idle, rng, cells.req_round/
    req_tid (thief-owned), clock, ctr[req_sent].
    """
    W = st.s_top.shape[0]
    me = _me(st)
    m = axis_masks(case)
    params = case.params
    n_w = case.n_workers
    zsz = case.zone_size
    active_w = me < n_w
    thief_m = (st.s_top == 0) & ~found & active_w & m.is_dlb & running
    idle = jnp.where(thief_m, st.idle + 1, 0)
    do_req = thief_m & ((idle == 1) | (idle >= params.t_interval))
    idle = jnp.where(idle >= params.t_interval, 0, idle)
    st = st._replace(idle=idle)

    # most scheduling points have no thief at all (requests fire on the
    # first idle step and every t_interval after); the retry loop is an
    # early-exit while so those steps skip the victim-pick machinery.
    # The carry holds only what the loop actually mutates — rng, the
    # thief-written request cells, clock, a sent-count accumulator — so
    # the (batched) loop's per-iteration select overhead never touches
    # the big queue/stack/counter buffers.
    rounds = st.cells.round   # victim-owned; thieves only read it
    # the (W, W) distance-weight tables are built by the step's driver,
    # once per case and outside its loop (dlb.victim_tables): XLA would
    # not hoist their gather out of the device loop.  The node-split pair
    # feeds the cluster tier's two-level victim choice (ignored
    # off-cluster).
    node_tbls = (tables.node_local, tables.node_remote)

    def cond(carry):
        v = carry[0]
        return (v < NV_CAP) & jnp.any(do_req & (v < params.n_victim))

    def body(carry):
        v, rng, req_round, req_tid, clock, n_sent, nl = carry
        sm = do_req & (v < params.n_victim)
        rng, victim = dlb.pick_victim(rng, me, n_w, zsz, params.p_local,
                                      case.topo, remote_tbl=tables.remote,
                                      p_local_node=params.p_local_node,
                                      node_tbls=node_tbls)
        cells, sent = messaging.thief_send(
            messaging.Cells(rounds, req_round, req_tid), me, victim, sm)
        # request/reply control messages price as L + req_bytes/B on
        # cluster links (the bare latency everywhere else)
        c1 = _comm_sz(costs, me, victim, case, costs.req_bytes)
        cost = jnp.where(sm, 2 * c1, 0) + jnp.where(sent, c1, 0)
        msgs = jnp.where(sm, 2, 0) + jnp.where(sent, 1, 0)
        xn = sm & case.topo.cluster & ~_same_node(me, victim, case)
        nl = nl + jnp.where(xn, msgs * costs.req_bytes, 0).astype(jnp.int32)
        return (v + 1, rng, cells.req_round, cells.req_tid, clock + cost,
                n_sent + sent.astype(jnp.int32), nl)

    _v, rng, req_round, req_tid, clock, n_sent, nl = jax.lax.while_loop(
        cond, body,
        (jnp.int32(0), st.rng, st.cells.req_round, st.cells.req_tid,
         st.clock, jnp.zeros(W, jnp.int32), jnp.zeros(W, jnp.int32)))
    return st._replace(
        rng=rng, cells=messaging.Cells(rounds, req_round, req_tid),
        clock=clock, ctr=_bump(ops, st.ctr, "req_sent", n_sent),
        nlink_bytes=st.nlink_bytes + nl)


# ---------------- phase C: victim handling ----------------
def victim_phase(st: SimState, found, *, g: GraphArrays, case: SweepCase,
                 costs: CostModel, ops: StepOps = REFERENCE_OPS) -> SimState:
    """Busy workers with a valid steal request answer it — NA-WS bulk-moves
    up to ``n_steal`` tasks into the thief's queue (Alg. 4), NA-RP adopts
    the thief for future redirected pushes (Alg. 3).

    Reads cells/xq/deq_rr/rp/clock; writes xq (transfer), rp, cells.round,
    clock, ctr[stolen*/req_*/src_empty/tgt_full].  On cluster machines the
    bulk move is payload-priced: every transferred task costs
    ``L + payload/B`` over the victim→thief link, and cross-node moves feed
    the bottleneck ledger.
    """
    me = _me(st)
    m = axis_masks(case)
    params = case.params
    t = case.topo
    zsz = case.zone_size

    valid = messaging.victim_valid(st.cells) & found
    thief = jnp.maximum(st.cells.req_tid, 0)

    # NA-WS: bulk transfer to the thief's queue (Alg. 4) — the per-task
    # transfer latency below is the topology-aware endpoint distance,
    # plus payload/bandwidth on cluster links (xfer_bw = 0 disables the
    # payload term bitwise, the non-cluster contract)
    vm_ws = valid & m.is_naws
    comm_c = _comm(costs, me, thief, case)
    bw_vt = t.bw[topology_mod.domain_of(me, zsz, t.n_domains),
                 topology_mod.domain_of(thief, zsz, t.n_domains)]
    xfer_bw = jnp.where(t.cluster & (me != thief), bw_vt, 0).astype(jnp.int32)
    with jax.named_scope("xq"):
        xq, clock, stolen, src_empty, tgt_full, moved_bytes = \
            dlb.ws_transfer(st.xq, vm_ws, thief, params.n_steal,
                            st.clock, comm_c, st.deq_rr, WS_CAP,
                            case.n_workers, payload=g.payload,
                            xfer_bw=xfer_bw)
    same_d = _same_domain(me, thief, case)
    same_n = _same_node(me, thief, case)
    ctr = _bump(ops, st.ctr, "stolen", stolen)
    ctr = _bump(ops, ctr, "stolen_local", jnp.where(same_d, stolen, 0))
    ctr = _bump(ops, ctr, "stolen_remote", jnp.where(~same_d, stolen, 0))
    ctr = _bump(ops, ctr, "stolen_xnode", jnp.where(~same_n, stolen, 0))
    ctr = _bump(ops, ctr, "req_has_steal", vm_ws & (stolen > 0))
    ctr = _bump(ops, ctr, "src_empty", src_empty)
    ctr = _bump(ops, ctr, "tgt_full", tgt_full)

    # NA-RP: adopt the thief for future redirected pushes (Alg. 3)
    vm_rp = valid & m.is_narp
    rp, adopted = dlb.rp_adopt(st.rp, thief, params.n_steal, vm_rp)
    ctr = _bump(ops, ctr, "req_has_steal", adopted)

    handled = vm_ws | vm_rp
    ctr = _bump(ops, ctr, "req_handled", handled)
    nl = jnp.where(t.cluster & ~same_n, moved_bytes, 0).astype(jnp.int32)
    return st._replace(xq=xq, clock=clock, rp=rp, ctr=ctr,
                       nlink_bytes=st.nlink_bytes + nl,
                       cells=messaging.victim_advance(st.cells, handled))


# ---------------- phase D: execution ----------------
def exec_phase(st: SimState, task, ts, found, *, g: GraphArrays,
               case: SweepCase, costs: CostModel,
               ops: StepOps = REFERENCE_OPS) -> SimState:
    """Workers that dequeued a task run it: the clock first joins the
    producer-side timestamp (causality), memory-bound tasks pay the NUMA
    locality penalty, and completion bookkeeping (spawn ranges, join
    counts, claim tie-breaks) happens in ``_finish``.

    Reads creator/clock; writes clock, ctr, and — via ``_finish`` — done,
    join_cnt, creator, n_done, s_* (newly-ready spawn ranges).
    """
    me = _me(st)
    m = axis_masks(case)
    zsz = case.zone_size

    safe = jnp.where(found, task, 0)
    dur_t = jnp.where(found, g.dur[safe], 0)
    # memory-bound tasks run slower away from their creator's data
    # (paper SVI-B: the locality mechanism behind the DLB gains);
    # mem_bound == 0 keeps the exact integer durations (no f32
    # round-trip, which would perturb tasks >= 2^24 ns).  Under a
    # non-flat topology the cross-socket penalty scales with the NUMA
    # distance (normalized at c_numa = one interconnect hop), so far
    # socket pairs hurt streaming tasks more than adjacent ones.
    cr0 = st.creator[safe]
    t = case.topo
    same_d = _same_domain(cr0, me, case)
    d_cr = t.dist[topology_mod.domain_of(cr0, zsz, t.n_domains),
                  topology_mod.domain_of(me, zsz, t.n_domains)]
    pen_rem = jnp.where(
        t.flat, costs.exec_remote_penalty,
        1.0 + (costs.exec_remote_penalty - 1.0)
        * d_cr.astype(jnp.float32) / jnp.float32(costs.c_numa))
    pen = jnp.where(cr0 == me, 1.0,
                    jnp.where(same_d, costs.exec_zone_penalty, pen_rem))
    mult = 1.0 + case.mem_bound * (pen - 1.0)
    dur_t = jnp.where(case.mem_bound > 0,
                      (dur_t.astype(jnp.float32) * mult).astype(jnp.int32),
                      dur_t)
    start = jnp.maximum(st.clock, jnp.where(found, ts, 0))
    clock = jnp.where(found, start + dur_t, st.clock)
    ctr = _bump(ops, st.ctr, "exec", found)
    ctr = _bump(ops, ctr, "self", found & (cr0 == me))
    ctr = _bump(ops, ctr, "local", found & (cr0 != me) & same_d)
    ctr = _bump(ops, ctr, "remote", found & ~same_d)
    ctr = _bump(ops, ctr, "busy_ns", dur_t)
    st = st._replace(clock=clock, ctr=ctr)
    st = _finish(st, jnp.where(found, task, -1), g)
    # global task count decrement — only the centralized_count barrier
    # keeps one: contended atomic on the xqueue lane, plain atomic op
    # count on the locked lane (already serialized on the queue lock);
    # under the tree barrier there is no global count to decrement
    st = _atomic_charge(st, found & m.pays_count, costs, ops)
    return st._replace(ctr=_bump(
        ops, st.ctr, "atomic_ops",
        found & m.is_locked & (case.barrier_id == 0)))


#: the pipeline in step order (adopt_phase is the NA-RP pre-push hook)
PHASES = ("adopt_phase", "spawn_phase", "dequeue_phase", "thief_phase",
          "victim_phase", "exec_phase")


# ---------------- the composed step ----------------
def run_gate(st: SimState, g: GraphArrays, max_steps: int) -> jax.Array:
    """The run loop's per-simulation liveness predicate (scalar bool).

    Beyond the classic ``n_done < n_tasks & step_i < max_steps & ~overflow``
    it also requires *pending work to exist*: a spawn-stack entry, a queued
    xqueue task, or a queued locked-global task.  No-work is an absorbing
    state — tasks only materialize from spawns, dequeue-execute completions,
    or join claims, all of which need an existing stack/queue entry — so a
    lane that is incomplete *and* workless is permanently stalled (e.g. a
    malformed graph whose join dependency count exceeds its notifiers), and
    iterating it to the max-step horizon would only burn thief-protocol
    steps.  Completing runs are bitwise unaffected: at every step boundary
    short of completion they hold at least one stack or queue entry.

    Shared by the serial/batched while conds *and* the step body's internal
    ``running`` gate (``step_pipeline``), so ``step_i``/clock freeze at the
    same step under every executor — stalled lanes stay bitwise identical
    across serial, vmap, and sharded runs.
    """
    with jax.named_scope("gate"):
        has_work = (jnp.any(st.s_top > 0)
                    | jnp.any(st.xq.tail > st.xq.head)
                    | (st.g_tail > st.g_head))
        return ((st.n_done < g.n_tasks) & (st.step_i < max_steps)
                & ~st.overflow & has_work)


def step_pipeline(st: SimState, *, g: GraphArrays, case: SweepCase,
                  tables: dlb.VictimTables, costs: CostModel,
                  ops: StepOps = REFERENCE_OPS, max_steps: int) -> SimState:
    """One scheduling point: the six phases composed in step order.

    This is the *whole* step body — backends differ only in the ``ops``
    kernel set they pass (and in whether the composition itself runs as a
    fused Pallas kernel, see :mod:`repro.kernels.sched_step`); the
    composition lives here so every backend executes the identical
    sequence.  Each phase is gated on ``running`` (:func:`run_gate`): once
    a simulation finishes or stalls, its step is a strict no-op, which is
    what lets the batched engine drive a plain ``while any(alive)`` loop
    over vmapped steps without per-element freeze machinery.

    Each phase runs under a ``jax.named_scope`` of its short name
    (``adopt`` … ``exec``), the cluster occupancy charge under
    ``occupancy`` and :func:`run_gate` under ``gate``: HLO metadata only,
    which a device trace reads as per-phase time.  Inside the phases, the
    three calls that touch the XQueue's ``(W, W, Q)`` arrays (the push in
    spawn, the pop scan in dequeue, NA-WS's transfer in victim) run under
    a nested ``xq`` scope, so the queue's share of those phases reads
    apart.

    ``tables`` are the case's victim-weight tables
    (:func:`repro.core.dlb.victim_tables`), built by the step's driver
    once per case, outside its loop.
    """
    running = run_gate(st, g, max_steps)
    with jax.named_scope("adopt"):
        st = adopt_phase(st, running, case=case, costs=costs, ops=ops)
    with jax.named_scope("spawn"):
        st = spawn_phase(st, running, g=g, case=case, costs=costs, ops=ops)
    with jax.named_scope("dequeue"):
        st, task, ts, found = dequeue_phase(st, running, g=g, case=case,
                                            costs=costs, ops=ops)
    with jax.named_scope("thief"):
        st = thief_phase(st, found, running, case=case, tables=tables,
                         costs=costs, ops=ops)
    with jax.named_scope("victim"):
        st = victim_phase(st, found, g=g, case=case, costs=costs, ops=ops)
    with jax.named_scope("exec"):
        st = exec_phase(st, task, ts, found, g=g, case=case, costs=costs,
                        ops=ops)
    # shared inter-node bottleneck (cluster tier): all cross-node bytes
    # moved this step contend for one uplink, so each sender additionally
    # waits out the *other* senders' occupancy (total-minus-own over the
    # bottleneck bandwidth).  The ledger stays identically zero off-cluster
    # — flat and single-node machines add 0 to every clock — and resets
    # each step, making the charge a per-step occupancy model.
    with jax.named_scope("occupancy"):
        nl = st.nlink_bytes
        occ = jnp.where((nl > 0) & case.topo.cluster,
                        (jnp.sum(nl) - nl) // case.topo.bneck_bw,
                        0).astype(jnp.int32)
        st = st._replace(clock=st.clock + occ,
                         ctr=_bump(ops, st.ctr, "xnode_bytes", nl),
                         nlink_bytes=jnp.zeros_like(nl))
    return st._replace(step_i=st.step_i + running.astype(jnp.int32))
