"""NUMA-aware dynamic load balancing policies (paper §IV).

* ``pick_victim`` — conditionally-random victim selection: NUMA-local with
  probability ``p_local``, NUMA-remote otherwise (never self).  Under a
  non-flat :mod:`repro.core.topology` the remote choice is weighted
  inversely with the NUMA distance matrix (near sockets preferred).
* ``NA-RP`` (redirect push, Alg. 3) — a victim that accepted a thief redirects
  its *newly created* tasks to the thief's queue until ``n_steal`` tasks are
  pushed or the thief's queue fills.  Implemented as per-worker
  ``(rp_tgt, rp_left)`` state consulted by the scheduler's push phase.
* ``NA-WS`` (work stealing, Alg. 4) — a victim that accepted a thief dequeues
  up to ``n_steal`` tasks from its own queues and enqueues them to the thief's
  target queue ``(thief, victim)``; stops on own-empty or target-full.

The NUMA zone of worker ``w`` is ``w // (W // n_zones)`` — on the TPU side the
same index arithmetic maps a device to its pod/ICI neighborhood.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core import xqueue


def xorshift(s: jax.Array) -> jax.Array:
    """Per-lane xorshift32 PRNG — cheap enough to call several times a step."""
    s = s ^ (s << 13)
    s = s ^ (s >> 17)
    s = s ^ (s << 5)
    return s


def uniform(s: jax.Array) -> jax.Array:
    """U[0,1) from a uint32 state."""
    return (s >> 8).astype(jnp.float32) * (1.0 / (1 << 24))


def zone_of(w: jax.Array, zone_size: int) -> jax.Array:
    return w // zone_size


def remote_weight_table(me: jax.Array, n_workers, zone_size, topo,
                        restrict: str | None = None
                        ) -> Tuple[jax.Array, jax.Array]:
    """Loop-invariant table for the hierarchy-aware remote choice: per
    (thief, candidate) integer weights *inversely related to domain
    distance* — the nearest remote domain's workers carry weight
    ``1 + (d_max - d_near)``, the farthest carry ``1`` (integer weights off
    ``topo.dist``, so the draw→victim map stays exact).  Depends only on
    ``me``/``n_workers``/``zone_size``/``topo``, never on the PRNG draw or
    the simulation state, so the step never builds it: :func:`victim_tables`
    builds a case's three tables once, before the device loop, and
    ``phases.thief_phase`` reads them at every scheduling point.

    ``restrict`` narrows the candidate set for the cluster tier's
    two-level choice: ``"node_local"`` keeps only remote-socket candidates
    *inside* the thief's node, ``"node_remote"`` only candidates in
    *other* nodes (``topo.node`` maps sockets to nodes; on single-node
    machines node_local equals the unrestricted set and node_remote is
    empty).

    Vectorized over the worker lanes: ``me`` is ``(W,)``, the table is
    ``(W, W)``.  Returns ``(cum_weights, total_weight)``.
    """
    W = me.shape[0]
    j = jnp.arange(W, dtype=jnp.int32)
    dom_j = jnp.minimum(j // zone_size, topo.n_domains - 1)
    dom_me = jnp.minimum(me // zone_size, topo.n_domains - 1)
    d = topo.dist[dom_me[:, None], dom_j[None, :]]             # (W, W)
    remote = (j[None, :] < n_workers) & (dom_j[None, :] != dom_me[:, None])
    if restrict is not None:
        assert restrict in ("node_local", "node_remote"), restrict
        same_n = (topo.node[dom_me][:, None] == topo.node[dom_j][None, :])
        remote = remote & (same_n if restrict == "node_local" else ~same_n)
    dmax = jnp.max(jnp.where(remote, d, 0), axis=1, keepdims=True)
    wgt = jnp.where(remote, dmax - d + 1, 0)                   # (W, W)
    cum = jnp.cumsum(wgt, axis=1)
    return cum, cum[:, -1]


class VictimTables(NamedTuple):
    """One case's victim-weight tables, each a :func:`remote_weight_table`
    ``(cum (W, W), total (W,))`` pair: the unrestricted remote choice and
    the cluster tier's ``node_local`` / ``node_remote`` split."""
    remote: Tuple[jax.Array, jax.Array]
    node_local: Tuple[jax.Array, jax.Array]
    node_remote: Tuple[jax.Array, jax.Array]


def victim_tables(W: int, case) -> VictimTables:
    """The thief's victim-weight tables of one
    :class:`~repro.core.state.SweepCase` at padded width ``W``.

    They depend only on ``case.n_workers``/``zone_size``/``topo``, which a
    run never changes, so every driver of the step
    (``executors._batch_body`` under ``vmap``, ``scheduler._run_jit``)
    builds them once, inside its jitted program and before its
    ``while_loop``, and hands them to the step as a loop-invariant operand.
    """
    me = jnp.arange(W, dtype=jnp.int32)
    n_w, zsz, topo = case.n_workers, case.zone_size, case.topo
    return VictimTables(
        remote=remote_weight_table(me, n_w, zsz, topo),
        node_local=remote_weight_table(me, n_w, zsz, topo,
                                       restrict="node_local"),
        node_remote=remote_weight_table(me, n_w, zsz, topo,
                                        restrict="node_remote"))


def _remote_weighted(draw: jax.Array, cum: jax.Array, total: jax.Array
                     ) -> Tuple[jax.Array, jax.Array]:
    """Sample from a :func:`remote_weight_table`.  ``draw`` is the same
    non-negative PRNG draw the flat path consumes: the hierarchy changes
    *where* steal requests go, never how much randomness a step uses.
    Returns ``(victim, has_remote)``."""
    W = cum.shape[-1]
    r = draw[:, None] % jnp.maximum(total[:, None], 1)
    # victim = first lane whose cumulative weight exceeds r (zero-weight
    # lanes share their predecessor's cumsum, so they are never selected)
    victim = jnp.sum((cum <= r).astype(jnp.int32), axis=1)
    return jnp.minimum(victim, W - 1), total > 0


def pick_victim(rng: jax.Array, me: jax.Array, n_workers, zone_size,
                p_local: jax.Array, topo=None, remote_tbl=None,
                p_local_node=None, node_tbls=None
                ) -> Tuple[jax.Array, jax.Array]:
    """Random victim != me; same zone/domain with probability ``p_local``.

    ``n_workers`` and ``zone_size`` may be Python ints or traced scalars (the
    batched sweep engine varies both under one compiled shape).  ``topo``
    (a :class:`~repro.core.topology.TopoArrays`, optional) makes the choice
    hierarchy-aware: the local candidate set becomes ``me``'s *clipped NUMA
    domain* (the last domain absorbs remainder workers when ``n_workers``
    is not a socket multiple, matching the comm/penalty pricing) and remote
    victims are weighted inversely with NUMA distance
    (:func:`remote_weight_table`, hoistable via ``remote_tbl``); flat
    topologies — and ``topo=None`` — keep the historical uniform choice
    bitwise (same PRNG consumption either way).  With ``topo`` set,
    ``me``/``rng`` must be the full ``(W,)`` lane vectors.

    ``p_local_node`` adds the cluster tier's second stratum: the single
    uniform draw ``u`` stratifies three ways — socket-local for
    ``u < p_local``, node-local-remote-socket for
    ``u < p_local + (1-p_local)·p_local_node``, cross-node otherwise — so
    cross-node steal requests are strictly rarer than cross-socket ones
    without consuming any extra randomness (exactly two xorshifts per call
    on every path, the PRNG-parity contract).  The cross-node stratum is
    additionally *bandwidth-aware*: on a fabric starved below its native
    bandwidth (``topo.bw_scale < 1``, via
    ``MachineTopology.with_bandwidth``) the stratum narrows in proportion
    to the remaining capacity, so the cross-node steal fraction falls as
    the inter-node bandwidth shrinks.  Only consulted when
    ``topo.cluster``; empty strata fall back to whichever side has
    candidates.  ``node_tbls`` hoists the two node-restricted weight
    tables (``remote_weight_table(..., restrict=...)`` pair).

    Returns (rng', victim). Degenerate topologies (single zone / 1-wide zones)
    fall back to whichever side has candidates.
    """
    W, Z = n_workers, zone_size
    rng = xorshift(rng)
    u = uniform(rng)
    want_local = u < p_local
    rng = xorshift(rng)
    draw = (rng >> jnp.uint32(1)).astype(jnp.int32)  # non-negative
    zbase = (me // Z) * Z
    # local candidate: one of the Z-1 zone members != me
    off_l = draw % jnp.maximum(Z - 1, 1)
    local = zbase + off_l + (off_l >= (me - zbase)).astype(jnp.int32)
    # remote candidate: one of the W-Z workers outside the zone
    off_r = draw % jnp.maximum(W - Z, 1)
    remote = jnp.where(off_r >= zbase, off_r + Z, off_r)
    has_local = Z > 1
    has_remote = W > Z
    if topo is not None:
        # hierarchical local set = the clipped domain's block [start, end):
        # identical to the raw zone when W divides evenly, wider for the
        # last domain otherwise — so same-domain remainder workers can
        # steal from each other (consistent with _comm/_same_domain)
        dom_me = jnp.minimum(me // Z, topo.n_domains - 1)
        start = dom_me * Z
        end = jnp.where(dom_me == topo.n_domains - 1, W, (dom_me + 1) * Z)
        size = end - start
        off_h = draw % jnp.maximum(size - 1, 1)
        local_h = start + off_h + (off_h >= (me - start)).astype(jnp.int32)
        if remote_tbl is None:
            remote_tbl = remote_weight_table(me, W, Z, topo)
        remote_h, has_remote_h = _remote_weighted(draw, *remote_tbl)
        if p_local_node is not None:
            # cluster two-level remote choice: same draw, stratified u
            if node_tbls is None:
                node_tbls = (remote_weight_table(me, W, Z, topo,
                                                 restrict="node_local"),
                             remote_weight_table(me, W, Z, topo,
                                                 restrict="node_remote"))
            nl_v, has_nl = _remote_weighted(draw, *node_tbls[0])
            nr_v, has_nr = _remote_weighted(draw, *node_tbls[1])
            # bandwidth-aware stratification: a starved inter-node fabric
            # (topo.bw_scale < 1, see MachineTopology.with_bandwidth)
            # narrows the cross-node stratum in proportion to its
            # remaining capacity — cross-node steal attempts get rarer
            # exactly as the link gets dearer.  Native fabric keeps the
            # plain two-level split bitwise (the where, not the algebra:
            # 1-(1-pn) re-rounds in float32).
            pn_eff = jnp.where(
                topo.bw_scale < 1.0,
                1.0 - (1.0 - p_local_node) * topo.bw_scale, p_local_node)
            want_node = u < p_local + (1.0 - p_local) * pn_eff
            use_nl = jnp.where(has_nl & has_nr, want_node, has_nl)
            remote_c = jnp.where(use_nl, nl_v, nr_v)
            remote_h = jnp.where(topo.cluster, remote_c, remote_h)
            has_remote_h = jnp.where(topo.cluster, has_nl | has_nr,
                                     has_remote_h)
        local = jnp.where(topo.flat, local, local_h)
        remote = jnp.where(topo.flat, remote, remote_h)
        has_local = jnp.where(topo.flat, has_local, size > 1)
        has_remote = jnp.where(topo.flat, has_remote, has_remote_h)
    use_local = jnp.where(has_local & has_remote, want_local,
                          jnp.asarray(has_local))
    victim = jnp.where(use_local, local, remote).astype(jnp.int32)
    return rng, victim


class RPState(NamedTuple):
    tgt: jax.Array   # (W,) adopted thief id, -1 = none (Alg. 3 "No thief")
    left: jax.Array  # (W,) remaining tasks to redirect


def rp_make(n_workers: int) -> RPState:
    return RPState(tgt=jnp.full(n_workers, -1, jnp.int32),
                   left=jnp.zeros(n_workers, jnp.int32))


def rp_adopt(rp: RPState, thief: jax.Array, n_steal: jax.Array,
             valid: jax.Array) -> Tuple[RPState, jax.Array]:
    """Alg. 3 doLoadBalancing: adopt the requesting thief iff none is active."""
    adopt = valid & (rp.tgt < 0)
    return RPState(
        tgt=jnp.where(adopt, thief, rp.tgt),
        left=jnp.where(adopt, n_steal, rp.left),
    ), adopt


def ws_transfer(xq: xqueue.XQ, victim_mask: jax.Array, thief: jax.Array,
                n_steal: jax.Array, clock: jax.Array, comm_cost: jax.Array,
                deq_rr: jax.Array, ws_cap: int, n_active=None,
                payload=None, xfer_bw=None):
    """Alg. 4: each victim moves up to ``n_steal`` tasks from its own queues to
    queue ``(thief, victim)``, stopping on own-empty or target-full.

    The paper's while loop pops one task at a time: the victim drains its
    queues in dequeue scan order (master first, then the rotated auxiliaries)
    and appends to the thief's queue until ``n_steal`` tasks moved, its own
    queues ran dry, or the target filled.  Because the scan rotation is fixed
    for the whole transfer and the target queue ``(thief, victim)`` is never
    one of the victim's own sources, the loop's effect is *closed-form*: the
    transfer count is ``k = min(n_steal, ws_cap, available, target_free)``,
    the r-th moved task is the r-th element of the scan-order concatenation
    of the victim's queues, and per-source take counts are a waterfall over
    the scan-order prefix sums.  This computes that directly — one gather +
    one one-hot write instead of up to ``ws_cap`` full-buffer loop
    iterations — and is bitwise identical to the loop (timestamps included:
    the r-th task is stamped ``max(clock + before_r, ts) + cost_r`` where
    ``before_r`` is the exclusive prefix sum of per-task costs).

    The cluster tier prices each moved task individually:
    ``cost_r = comm_cost + payload[task_r] // xfer_bw`` when ``xfer_bw``
    (the per-victim link bandwidth, bytes/ns) is positive, and bounds the
    transfer by a time *window* of ``n_steal * comm_cost`` — the victim
    stops handing tasks over once the elapsed transfer time leaves the
    window, so a starved link moves fewer tasks per steal.  ``xfer_bw ==
    0`` — or ``payload=None`` — keeps the constant-cost arithmetic, for
    which the prefix sums collapse to ``r·comm`` / ``k·comm`` and the
    window fits exactly ``n_steal`` tasks: bitwise the pre-cluster
    behavior.

    ``n_active`` (traced) restricts the scan to live workers under a padded
    shape.  Returns (xq', clock', stolen_count, src_empty, tgt_full,
    moved_bytes).
    """
    W = xq.head.shape[0]
    zeros = jnp.zeros(W, jnp.int32)
    false = jnp.zeros(W, bool)

    # gate the whole transfer behind a one-shot while loop: on the many
    # scheduling points with no valid steal request the body never executes
    # (lax.cond would not survive vmap — it batches to a select that still
    # evaluates both branches)
    def cond(carry):
        return carry[0] & jnp.any(victim_mask)

    def body(carry):
        _, xq_c, clock_c, _, _, _, _ = carry
        out = _ws_bulk(xq_c, victim_mask, thief, n_steal, clock_c,
                       comm_cost, deq_rr, ws_cap, n_active,
                       payload, xfer_bw)
        return (jnp.asarray(False),) + out

    carry = jax.lax.while_loop(
        cond, body,
        (jnp.asarray(True), xq, clock, zeros, false, false, zeros))
    return carry[1], carry[2], carry[3], carry[4], carry[5], carry[6]


def _ws_bulk(xq: xqueue.XQ, victim_mask, thief, n_steal, clock, comm_cost,
             deq_rr, ws_cap: int, n_active, payload=None, xfer_bw=None):
    W = xq.head.shape[0]
    Q = xqueue.capacity(xq)
    if n_active is None:
        n_active = W
    me = jnp.arange(W, dtype=jnp.int32)
    n_steal = jnp.minimum(n_steal, jnp.int32(ws_cap))

    order, valid = xqueue._scan_order(W, me, deq_rr, n_active)   # (W, W)
    sz = xq.tail - xq.head                                       # (W, W)
    sz_ord = jnp.where(valid, jnp.take_along_axis(sz, order, axis=1), 0)
    cum = jnp.cumsum(sz_ord, axis=1)
    avail = cum[:, -1]
    cum_before = cum - sz_ord
    free0 = Q - (xq.tail[thief, me] - xq.head[thief, me])
    k = jnp.minimum(n_steal, jnp.minimum(avail, free0))
    k = jnp.where(victim_mask, jnp.maximum(k, 0), 0)

    # source of the r-th moved task: first scan-order queue whose prefix sum
    # exceeds r, at offset r - cum_before (k <= Q, so r ranges over [0, Q))
    r_iota = jnp.arange(Q, dtype=jnp.int32)[None, :]             # (1, Q)
    j_r = jnp.sum(cum[:, None, :] <= r_iota[:, :, None],
                  axis=2).astype(jnp.int32)                      # (W, Q)
    j_r = jnp.minimum(j_r, W - 1)
    src_r = jnp.take_along_axis(order, j_r, axis=1)              # (W, Q)
    off_r = r_iota - jnp.take_along_axis(cum_before, j_r, axis=1)
    slot_r = (xq.head[me[:, None], src_r] + off_r) % Q
    task_r = xq.buf[me[:, None], src_r, slot_r]                  # (W, Q)
    ts_r = xq.ts[me[:, None], src_r, slot_r]
    # per-task transfer cost: the constant endpoint latency, plus
    # payload/bandwidth when the cluster tier prices this link
    if payload is None or xfer_bw is None:
        cost_r = jnp.broadcast_to(comm_cost[:, None], task_r.shape)
    else:
        pay_r = payload[task_r]                                  # (W, Q)
        cost_r = comm_cost[:, None] + jnp.where(
            xfer_bw[:, None] > 0,
            pay_r // jnp.maximum(xfer_bw[:, None], 1), 0)
    # exclusive prefix sum: task r starts after tasks [0, r) moved —
    # constant cost collapses this to r·comm, the pre-cluster stamps
    before_r = jnp.cumsum(cost_r, axis=1) - cost_r
    windowed = jnp.zeros_like(victim_mask)
    if payload is not None and xfer_bw is not None:
        # a priced link bounds the bulk transfer by a time *window*, not a
        # bare count: the victim pops a task only if its transfer would
        # still *complete* inside ``n_steal * L`` — the span the count cap
        # spends on a constant-cost link, so when every task costs exactly
        # ``comm_cost`` the window fits exactly ``n_steal`` tasks and the
        # pre-cluster ``k`` survives bitwise.  Starving a link inflates
        # each task's ``L + D/B`` share, so fewer tasks fit per steal —
        # down to zero: a steal whose first payload alone overflows the
        # window aborts, and the thief's next strata draw usually lands
        # closer.  Cross-node balancing throttles itself as bandwidth
        # shrinks.
        window = (n_steal * comm_cost)[:, None]                  # (W, 1)
        k_win = jnp.sum((r_iota < k[:, None])
                        & (before_r + cost_r <= window),
                        axis=1).astype(jnp.int32)
        k_full = k
        k = jnp.where(xfer_bw > 0, k_win, k)
        windowed = k < k_full
    take_r = r_iota < k[:, None]
    # failure flags, exactly as the loop would observe them: another
    # iteration would still want a task (k < n_steal) and finds the target
    # full (k == free0; checked BEFORE popping, so no task is ever lost) or
    # its own queues empty (k == avail with target space left); a stop on
    # window expiry raises neither flag — the victim quit voluntarily
    can_more = victim_mask & (k < n_steal) & ~windowed
    tgt_full = can_more & (k == free0)
    src_empty = can_more & (free0 > k) & (k == avail)
    push_ts_r = jnp.maximum(clock[:, None] + before_r, ts_r) + cost_r

    # destination slot of task r is (tail0 + r) % Q in queue (thief, me):
    # express per physical slot q via r = (q - tail0) % Q, then write the
    # whole batch with one one-hot select over the consumer dimension
    tail0 = xq.tail[thief, me]
    q_iota = jnp.arange(Q, dtype=jnp.int32)[None, :]
    r_of_q = (q_iota - tail0[:, None]) % Q                       # (W, Q)
    val_q = jnp.take_along_axis(task_r, r_of_q, axis=1)
    tsv_q = jnp.take_along_axis(push_ts_r, r_of_q, axis=1)
    wr_q = jnp.take_along_axis(take_r, r_of_q, axis=1)
    one_c = me[:, None] == thief[None, :]                        # (Wc, Wv)
    upd = one_c[:, :, None] & wr_q[None, :, :]                   # (Wc, Wv, Q)
    buf = jnp.where(upd, val_q[None, :, :], xq.buf)
    tsb = jnp.where(upd, tsv_q[None, :, :], xq.ts)
    tail = xq.tail + jnp.where(one_c, k[None, :], 0)

    # per-source head advance: invert the scan order analytically
    p_iota = me[None, :]
    n_act = jnp.maximum(n_active, 1)
    pos_p = xqueue.scan_pos(W, me, deq_rr, n_active)             # (W, W)
    cb_p = jnp.take_along_axis(cum_before,
                               jnp.minimum(pos_p, W - 1), axis=1)
    take_p = jnp.clip(k[:, None] - cb_p, 0, jnp.maximum(sz, 0))
    take_p = jnp.where(p_iota < n_act, take_p, 0)
    head = xq.head + take_p

    clock = clock + jnp.sum(jnp.where(take_r, cost_r, 0), axis=1)
    moved_bytes = (jnp.zeros_like(k) if payload is None or xfer_bw is None
                   else jnp.sum(jnp.where(take_r & (xfer_bw[:, None] > 0),
                                          pay_r, 0), axis=1))
    return (xqueue.XQ(buf, tsb, head, tail), clock, k, src_empty, tgt_full,
            moved_bytes)
