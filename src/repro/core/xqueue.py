"""XQueue: lock-less MPMC queueing built from per-pair SPSC ring buffers.

Faithful to the paper (§II-B / Fig. 2): worker *i* owns one *master* SPSC
queue (pair ``(i, i)``) plus one *auxiliary* SPSC queue per other worker
(pair ``(consumer=i, producer=p)``).  Any task worker ``p`` sends to worker
``c`` goes into queue ``(c, p)`` — so every buffer has exactly one producer
and one consumer, which is the entire correctness argument of B-queue.

TPU/JAX adaptation: the SPSC "only the producer writes the tail, only the
consumer writes the head" discipline becomes *disjoint-slice writes inside a
bulk-synchronous step*: the push phase writes only ``(tail, buf[tgt, self])``
slices keyed by producer id, the pop phase writes only ``(head)`` slices keyed
by consumer id.  No two lanes ever write the same element in the same phase,
which is the vectorized statement of the lock-less invariant.

Timestamps ride along with every task so the simulator's virtual clocks stay
causal: a consumer popping a task first advances its clock to the producer's
clock at push time.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp


class XQ(NamedTuple):
    buf: jax.Array   # (W, W, Q) int32 — buf[consumer, producer, slot] task ids
    ts: jax.Array    # (W, W, Q) int32 — producer-side virtual timestamps
    head: jax.Array  # (W, W) int32 monotonic consumer cursor
    tail: jax.Array  # (W, W) int32 monotonic producer cursor


def make(n_workers: int, capacity: int) -> XQ:
    W, Q = n_workers, capacity
    return XQ(
        buf=jnp.full((W, W, Q), -1, jnp.int32),
        ts=jnp.zeros((W, W, Q), jnp.int32),
        head=jnp.zeros((W, W), jnp.int32),
        tail=jnp.zeros((W, W), jnp.int32),
    )


def sizes(xq: XQ) -> jax.Array:
    """(W, W) occupancy, consumer-major."""
    return xq.tail - xq.head


def capacity(xq: XQ) -> int:
    return xq.buf.shape[-1]


def push(xq: XQ, producer: jax.Array, consumer: jax.Array, task: jax.Array,
         ts: jax.Array, mask: jax.Array) -> Tuple[XQ, jax.Array]:
    """Vectorized push: lane ``i`` (producer ``producer[i]``) appends ``task[i]``
    to queue ``(consumer[i], producer[i])``.

    Producer ids must be distinct across active lanes (they are: lane == worker),
    so all writes touch disjoint (consumer, producer) pairs.
    Returns (new_xq, ok) where ok is False for full queues (caller then applies
    the paper's execute-immediately rule).
    """
    Q = capacity(xq)
    W = xq.head.shape[0]
    lane = jnp.arange(W, dtype=jnp.int32)
    # permute lane data into producer-indexed order (active producers are
    # distinct, so this is a tiny W-element inversion)
    inv = jnp.full((W,), W, jnp.int32).at[
        jnp.where(mask, producer, W)].set(lane, mode="drop")
    has = inv < W
    safe = jnp.minimum(inv, W - 1)
    cons_p = jnp.where(has, consumer[safe], 0)
    task_p = task[safe]
    ts_p = ts[safe]
    cur_p = xq.tail[cons_p, lane] - xq.head[cons_p, lane]
    ok_p = has & (cur_p < Q)
    slot_p = xq.tail[cons_p, lane] % Q
    # exactly one slot per producer column changes, so the write is a one-hot
    # select instead of a scatter (scatters vectorize terribly on CPU under
    # vmap; this elementwise form is bitwise identical to the scatter)
    one_c = ok_p[None, :] & (lane[:, None] == cons_p[None, :])     # (Wc, Wp)
    one_slot = one_c[:, :, None] & (
        jnp.arange(Q, dtype=jnp.int32)[None, None, :]
        == slot_p[None, :, None])                                  # (Wc, Wp, Q)
    buf = jnp.where(one_slot, task_p[None, :, None], xq.buf)
    tsb = jnp.where(one_slot, ts_p[None, :, None], xq.ts)
    tail = xq.tail + one_c.astype(jnp.int32)
    ok = mask & ok_p[producer]
    return XQ(buf, tsb, xq.head, tail), ok


def _scan_order(W: int, me: jax.Array, rot: jax.Array, n_active):
    """Candidate source order for each consumer: master queue first, then the
    other ``n_active - 1`` live producers starting at rotation ``rot`` (dequeue
    round-robin).  ``n_active`` may be a traced scalar ≤ the static width ``W``
    (padded lanes are skipped via the returned validity mask)."""
    # aux candidates: all live producers != me, rotated
    j = jnp.arange(W - 1)[None, :]                       # (1, W-1)
    nm1 = jnp.maximum(n_active - 1, 1)
    raw = (me[:, None] + 1 + ((rot[:, None] + j) % nm1)) % jnp.maximum(
        n_active, 1)
    order = jnp.concatenate([me[:, None], raw], axis=1)   # (W, W)
    W0 = me.shape[0]
    valid = jnp.concatenate(
        [jnp.ones((W0, 1), bool),
         jnp.broadcast_to(j < (n_active - 1), (W0, W - 1))], axis=1)
    return order, valid


def scan_pos(W: int, me: jax.Array, rot: jax.Array, n_active) -> jax.Array:
    """(W, W) scan *position* of producer ``p`` in consumer ``me``'s dequeue
    order: the master queue (p == me) is position 0, auxiliary producer ``p``
    sits at ``1 + ((p - me - 1) mod n - rot) mod (n - 1)`` — the closed-form
    inverse of ``_scan_order``, computed without any gather."""
    n_act = jnp.maximum(n_active, 1)
    nm1 = jnp.maximum(n_active - 1, 1)
    p = jnp.arange(W, dtype=jnp.int32)[None, :]
    d = (p - me[:, None] - 1) % n_act
    return jnp.where(p == me[:, None], 0, 1 + (d - rot[:, None]) % nm1)


def pop_compute(buf: jax.Array, ts: jax.Array, head: jax.Array,
                tail: jax.Array, rot: jax.Array, mask: jax.Array, n_active):
    """The pop scan as pure array math (the shared math core).

    Operates on the raw XQ arrays.  The Pallas pop kernel
    (:mod:`repro.kernels.sched_queue`) is this math with its gathers written
    as one-hot sums, which Mosaic lowers; tests assert the two bitwise.

    Returns ``(head', task, ts, src, found, checked)``.
    """
    W = head.shape[0]
    Q = buf.shape[-1]
    me = jnp.arange(W, dtype=jnp.int32)
    p = me[None, :]
    pos = scan_pos(W, me, rot, n_active)                  # (W, W)
    sz = tail - head                                      # (W, W) [c, p]
    cand = (sz > 0) & (p < jnp.maximum(n_active, 1))
    pos_m = jnp.where(cand, pos, W + 1)
    best = jnp.min(pos_m, axis=1)
    found_any = best <= W
    found = mask & found_any
    src = jnp.where(found_any,
                    jnp.argmin(pos_m, axis=1).astype(jnp.int32), me)
    checked = jnp.where(found_any, best + 1, n_active)
    safe_src = jnp.where(found, src, me)
    slot = head[me, safe_src] % Q
    task = buf[me, safe_src, slot]
    tsv = ts[me, safe_src, slot]
    # one consumed slot per consumer row: one-hot add, not a scatter
    head = head + (found[:, None]
                   & (me[None, :] == safe_src[:, None])).astype(jnp.int32)
    return head, task, tsv, src, found, checked


def pop_first(xq: XQ, rot: jax.Array, mask: jax.Array, n_active=None):
    """Every consumer pops one task: master queue first, then auxiliary queues
    in rotated round-robin order (paper §II-B).

    The first-nonempty-in-scan-order queue is found by an argmin over
    analytic scan positions (``scan_pos``) rather than gathering occupancies
    into scan order — batched gathers pay per-index overhead on CPU.

    ``n_active`` (traced scalar, default: the static width) restricts the scan
    to the first ``n_active`` workers so batched sweeps can vary worker count
    under one padded shape.

    Returns (xq', task, ts, src, found, checked) — ``checked`` is the number of
    queues inspected (each inspection is charged by the cost model).
    """
    if n_active is None:
        n_active = xq.head.shape[0]
    head, task, ts, src, found, checked = pop_compute(
        xq.buf, xq.ts, xq.head, xq.tail, rot, mask, n_active)
    return XQ(xq.buf, xq.ts, head, xq.tail), task, ts, src, found, checked
