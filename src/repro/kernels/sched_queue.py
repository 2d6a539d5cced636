"""Pallas kernels for the scheduler's hot queue phases.

The step body's inner loops are dominated by XQueue traffic — the per-pair
SPSC push, the rotated pop scan — and by one-hot counter bumps.  This module
implements that :class:`~repro.core.phases.StepOps` kernel set as Pallas
kernels (the ``pallas`` step backend, see :mod:`repro.core.backends`):

* **push** — every producer's store as one masked vector store: producer
  ``p`` writes only its own ``(consumer, p, slot)`` cell and bumps only its
  own tail cursor (producers are distinct and each owns its column), the
  B-queue single-writer discipline.
* **pop**  — the whole rotated scan (first non-empty queue in scan order,
  its head cell, the head advance) in one kernel.  It is
  :func:`repro.core.xqueue.pop_compute` with every gather written as a
  one-hot sum and the argmin as a least index, integer arithmetic that
  equals the reference bitwise (tests/test_backends.py asserts it per
  phase).
* **ctr_add** — the per-phase counter-column bump as a masked add.

Each kernel is compiled where it is lowered for a TPU and interpreted
where it is lowered for anything else (:func:`platform_pallas_call`), so CI
drives the exact kernel code on CPU (the ``JAX_PLATFORMS=cpu``
pallas-backend job).  All kernels are int32-only and grid-free (the
working set of a batch of simulations is one VMEM block), written for any
number of leading batch axes, and batch through a ``custom_vmap`` rule
that hands the kernel the whole batch as one block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap
from jax.experimental import pallas as pl

from repro.core import xqueue
from repro.core.xqueue import XQ


def platform_pallas_call(kernel, **kw):
    """``pl.pallas_call`` whose interpret mode follows the lowering platform.

    The call is compiled where it is lowered for a TPU and interpreted where
    it is lowered for anything else (the CPU in CI).  The choice is made per
    lowering (:func:`jax.lax.platform_dependent`), not from
    ``jax.default_backend()``, so a TPU compile issued from a CPU process
    holds the Mosaic kernel or raises the TPU compiler's error.
    """
    tpu = pl.pallas_call(kernel, interpret=False, **kw)
    other = pl.pallas_call(kernel, interpret=True, **kw)

    def call(*args):
        return jax.lax.platform_dependent(*args, tpu=tpu, default=other)

    return call


def _rank_generic(kernel, out_shape):
    """One ``pallas_call`` of ``kernel`` whose batching passes the whole
    batch as one block.

    ``kernel`` must be written for any number of leading batch axes (axes
    counted from the end), and ``out_shape(*args)`` gives the outputs'
    ShapeDtypeStructs for the arguments' shapes.  The ``custom_vmap`` rule
    broadcasts unbatched operands and calls the same kernel one rank up,
    where Pallas' generic rule would map a ``(Squeezed, n)`` block over the
    batch, which the TPU's (8, 128) tiling refuses.
    """

    @custom_vmap
    def call(*args):
        return platform_pallas_call(kernel, out_shape=out_shape(*args))(*args)

    @call.def_vmap
    def _batched(axis_size, in_batched, *args):
        args = [a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
                for a, b in zip(args, in_batched)]
        out = call(*args)
        return out, jax.tree_util.tree_map(lambda _: True, out)

    return call


def _same_shapes(*args):
    return tuple(jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args)


# ---------------- counter bump ----------------

def _ctr_add_kernel(ctr_ref, val_ref, out_ref, *, col: int):
    ctr = ctr_ref[...]
    hit = jax.lax.broadcasted_iota(jnp.int32, ctr.shape, ctr.ndim - 1) == col
    out_ref[...] = ctr + jnp.where(hit, val_ref[...], 0)


def ctr_add(ctr: jax.Array, col: int, val: jax.Array) -> jax.Array:
    """``ctr[:, col] += val`` as a Pallas kernel (col is static): a masked
    add over the whole counter block."""
    val = jnp.broadcast_to(val, ctr.shape[:-1])[..., None]
    call = _rank_generic(functools.partial(_ctr_add_kernel, col=col),
                         lambda c, v: _same_shapes(c)[0])
    return call(ctr, val)


# ---------------- SPSC push ----------------

def _push_kernel(buf_ref, ts_ref, tail_ref, hit_ref, hit3_ref, slot_ref,
                 task_ref, tsp_ref, obuf_ref, ots_ref, otail_ref):
    """Every producer's store at once: cell ``(c, p, slot[p])`` takes
    ``task[p]`` where ``hit[c, p]`` (at most one ``c`` per producer ``p``,
    the SPSC single-writer discipline), and the hit queues' tails advance.
    A masked vector store over the block, where a scalar store per producer
    would need dynamic indices.  ``hit3``/``slot``/``task``/``tsp`` arrive
    shaped to broadcast against the ``(..., Wc, Wp, Q)`` buffers."""
    buf = buf_ref[...]
    q = jax.lax.broadcasted_iota(jnp.int32, buf.shape, buf.ndim - 1)
    cell = (hit3_ref[...] != 0) & (q == slot_ref[...])
    obuf_ref[...] = jnp.where(cell, task_ref[...], buf)
    ots_ref[...] = jnp.where(cell, tsp_ref[...], ts_ref[...])
    otail_ref[...] = tail_ref[...] + hit_ref[...]


def push(xq: XQ, producer: jax.Array, consumer: jax.Array, task: jax.Array,
         ts: jax.Array, mask: jax.Array):
    """Pallas twin of :func:`repro.core.xqueue.push` (same signature/result).

    The W-element producer inversion and the (Wc, Wp) hit mask stay in jnp
    (the same math as the reference push); the (W, W, Q) buffer traffic,
    the hot part, runs as one Pallas kernel.
    """
    Q = xqueue.capacity(xq)
    W = xq.head.shape[0]
    lane = jnp.arange(W, dtype=jnp.int32)
    # permute lane data into producer-indexed order (identical math to the
    # reference push; active producers are distinct)
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

    hit = (ok_p[None, :] & (lane[:, None] == cons_p[None, :])
           ).astype(jnp.int32)                                    # (Wc, Wp)
    col = (1, W, 1)
    buf, tsb, tail = _rank_generic(
        _push_kernel, lambda b, t, tl, *_: _same_shapes(b, t, tl))(
        xq.buf, xq.ts, xq.tail, hit, hit[:, :, None], slot_p.reshape(col),
        task_p.reshape(col), ts_p.reshape(col))
    ok = mask & ok_p[producer]
    return XQ(buf, tsb, xq.head, tail), ok


# ---------------- pop scan ----------------

def _pop_kernel(buf_ref, ts_ref, head_ref, tail_ref, pos_ref, mask_ref,
                na_ref, ohead_ref, otask_ref, ots_ref, osrc_ref, ofound_ref,
                ochecked_ref):
    """:func:`repro.core.xqueue.pop_compute` without gathers: the argmin is
    the least producer at the least scan position, and each read of one
    cell is a one-hot sum over its row (exactly one cell hits).  ``buf`` and
    ``ts`` arrive as ``(..., W, W*Q)`` rows; per-consumer values are
    ``(..., W, 1)`` columns."""
    head = head_ref[...]
    na = na_ref[...]
    W = head.shape[-1]
    Q = buf_ref.shape[-1] // W
    p = jax.lax.broadcasted_iota(jnp.int32, head.shape, head.ndim - 1)
    me = jax.lax.broadcasted_iota(jnp.int32, na.shape[:-2] + (W, 1),
                                  head.ndim - 2)
    cand = ((tail_ref[...] - head) > 0) & (p < jnp.maximum(na, 1))
    pos_m = jnp.where(cand, pos_ref[...], W + 1)
    best = jnp.min(pos_m, axis=-1, keepdims=True)
    found_any = best <= W
    found = (mask_ref[...] != 0) & found_any
    first = jnp.min(jnp.where(pos_m == best, p, W), axis=-1, keepdims=True)
    src = jnp.where(found_any, first, me)
    safe_src = jnp.where(found, src, me)
    row = p == safe_src
    slot = jnp.sum(jnp.where(row, head, 0), axis=-1, keepdims=True) % Q
    buf = buf_ref[...]
    k = jax.lax.broadcasted_iota(jnp.int32, buf.shape, buf.ndim - 1)
    cell = k == safe_src * Q + slot
    otask_ref[...] = jnp.sum(jnp.where(cell, buf, 0), axis=-1, keepdims=True)
    ots_ref[...] = jnp.sum(jnp.where(cell, ts_ref[...], 0), axis=-1,
                           keepdims=True)
    osrc_ref[...] = src
    ofound_ref[...] = found.astype(jnp.int32)
    ochecked_ref[...] = jnp.where(found_any, best + 1, na)
    ohead_ref[...] = head + (found & row).astype(jnp.int32)


def _pop_shapes(buf, ts, head, *_):
    col = jax.ShapeDtypeStruct(head.shape[:-1] + (1,), jnp.int32)
    return (jax.ShapeDtypeStruct(head.shape, jnp.int32),) + (col,) * 5


def pop_first(xq: XQ, rot: jax.Array, mask: jax.Array, n_active=None):
    """Pallas twin of :func:`repro.core.xqueue.pop_first`: the whole rotated
    scan in one VMEM-resident kernel.  The scan positions
    (:func:`repro.core.xqueue.scan_pos`) are computed in jnp."""
    W = xq.head.shape[0]
    Q = xqueue.capacity(xq)
    if n_active is None:
        n_active = W
    me = jnp.arange(W, dtype=jnp.int32)
    pos = xqueue.scan_pos(W, me, rot, n_active)
    na = jnp.asarray(n_active, jnp.int32).reshape(1, 1)
    head, task, ts, src, found, checked = _rank_generic(
        _pop_kernel, _pop_shapes)(
        xq.buf.reshape(W, W * Q), xq.ts.reshape(W, W * Q), xq.head, xq.tail,
        pos, mask.astype(jnp.int32)[:, None], na)
    return (XQ(xq.buf, xq.ts, head, xq.tail), task[:, 0], ts[:, 0],
            src[:, 0], found[:, 0] != 0, checked[:, 0])


def pallas_ops():
    """The pallas :class:`~repro.core.phases.StepOps` kernel set."""
    from repro.core.phases import StepOps
    return StepOps(name="pallas", push=push, pop_first=pop_first,
                   ctr_add=ctr_add)
