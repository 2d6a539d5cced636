"""The whole-step megakernel: one Pallas launch per scheduling point.

The ``pallas`` backend swaps individual queue kernels into the jnp phase
pipeline, which leaves the fixed costs in place: six phase dispatches per
simulated step, each round-tripping the full :class:`SimState` through HBM.
This module takes the opposite cut — the *entire* composed step body
(:func:`repro.core.phases.step_pipeline`: adopt → spawn → dequeue → thief →
victim → exec) runs inside a single ``pallas_call``, so one launch reads the
state once, keeps the whole working set resident, executes every phase, and
writes the state once.

Fusion contract (the ``pallas_fused`` backend of
:mod:`repro.core.backends`):

* **Bitwise by construction.**  The kernel body calls the very same
  ``step_pipeline`` over the very same reference math cores
  (``REFERENCE_OPS`` — :func:`repro.core.xqueue.push` /
  :func:`~repro.core.xqueue.pop_first` / the one-hot counter bump) that the
  ``reference`` backend runs.  No arithmetic is re-derived; the only thing
  that changes is the launch granularity.
* **Pytree marshalling at the boundary.**  Pallas refs carry arrays, not
  pytrees, and want ≥1-d non-bool operands, so ``(st, g, case, tables)``
  flattens to leaves with ``bool → int32`` and ``0-d → (1,)`` encodings
  applied at the call boundary and undone first thing inside the kernel
  (and again on the way out).  Dtypes otherwise survive untouched — int32
  state, uint32 RNG lanes, float32 knobs.
* **What still forces a phase boundary:** nothing *inside* a step — the
  internal ``while_loop``s (the execute-immediately rule, the thief retry,
  the one-shot join claim) trace into the kernel body as-is.  The step
  *loop* stays outside: per-step termination is the engine's
  ``run_gate``-driven ``while_loop``, and the host-side barrier episode is
  accounted after the run as always.

The call is interpreted where it is lowered for anything but a TPU, so CI
drives the exact kernel code on CPU.  Lowered for a TPU it goes to Mosaic,
which does not lower this body yet (the reference pipeline's scatter-add
counter bump is the first primitive it refuses; ROADMAP Speed 2): a TPU
compile raises that error and never falls back to the interpreter.  The
call is grid-free (the per-simulation working set lives in one
block) and vmap/shard_map-safe — the graph, case and victim-table leaves
enter as kernel operands, so the sweep executors batch the megakernel like
any other step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap

from repro.core import dlb, phases
from repro.core.phases import REFERENCE_OPS
from repro.core.state import GraphArrays, SimState, SweepCase  # noqa: F401
from repro.core.costs import CostModel
from repro.kernels.sched_queue import platform_pallas_call


def _enc(x: jax.Array, batch: bool = False) -> jax.Array:
    """Leaf encoding at the kernel boundary: bool → int32, scalar → one
    trailing lane.  ``batch`` marks leaves carrying a leading batch axis, so
    "scalar" means ``ndim == 1`` there (a batch of 0-d leaves)."""
    x = jnp.asarray(x)
    if x.dtype == jnp.bool_:
        x = x.astype(jnp.int32)
    if x.ndim == int(batch):
        x = x[..., None]
    return x


def _enc_sds(a: jax.ShapeDtypeStruct, batch: bool = False):
    dt = jnp.int32 if a.dtype == jnp.bool_ else a.dtype
    shape = a.shape if len(a.shape) > int(batch) else a.shape + (1,)
    return jax.ShapeDtypeStruct(shape, dt)


def _dec(v: jax.Array, like: jax.ShapeDtypeStruct,
         batch: bool = False) -> jax.Array:
    """Undo :func:`_enc` given the (possibly batched) leaf's shape/dtype."""
    if len(like.shape) == int(batch):
        v = v[..., 0]
    if like.dtype == jnp.bool_:
        v = v != 0
    return v


def _step_kernel(*refs, treedef, in_avals, st_avals, costs: CostModel,
                 max_steps: int, batch: bool):
    """The megakernel body: decode → reconstruct pytrees → run the whole
    phase pipeline → encode the next state into the output refs.  With
    ``batch`` every operand carries a leading batch axis and the pipeline
    runs under ``jax.vmap`` *inside* the kernel."""
    n_in = len(in_avals)
    in_refs, out_refs = refs[:n_in], refs[n_in:]
    leaves = [_dec(r[...], a, batch) for r, a in zip(in_refs, in_avals)]
    st, g, case, tables = jax.tree_util.tree_unflatten(treedef, leaves)
    run = functools.partial(phases.step_pipeline, costs=costs,
                            ops=REFERENCE_OPS, max_steps=max_steps)
    if batch:
        st = jax.vmap(lambda s, gi, ci, ti: run(s, g=gi, case=ci, tables=ti)
                      )(st, g, case, tables)
    else:
        st = run(st, g=g, case=case, tables=tables)
    out_leaves = jax.tree_util.tree_leaves(st)
    assert len(out_leaves) == len(st_avals) == len(out_refs)
    for r, leaf in zip(out_refs, out_leaves):
        r[...] = _enc(leaf, batch)


def _pallas_step(leaves, treedef, n_st: int, costs: CostModel,
                 max_steps: int, batch: bool):
    """One ``pallas_call`` over the encoded leaves of
    ``(st, g, case, tables)``;
    returns the decoded leaves of the next state.  State operands alias
    their outputs (the step is a state *update* — no second copy)."""
    leaves = [jnp.asarray(x) for x in leaves]
    avals = tuple(jax.ShapeDtypeStruct(x.shape, x.dtype) for x in leaves)
    st_avals = avals[:n_st]
    kernel = functools.partial(
        _step_kernel, treedef=treedef, in_avals=avals,
        st_avals=st_avals, costs=costs, max_steps=max_steps, batch=batch)
    outs = platform_pallas_call(
        kernel,
        out_shape=tuple(_enc_sds(a, batch) for a in st_avals),
        input_output_aliases={i: i for i in range(n_st)},
    )(*[_enc(x, batch) for x in leaves])
    return [_dec(o, a, batch) for o, a in zip(outs, st_avals)]


def build_fused_step(costs: CostModel, g: GraphArrays, case: SweepCase,
                     tables: dlb.VictimTables, max_steps: int):
    """Compose ``step(st) -> st`` as one fused Pallas launch.

    Mirrors ``StepBackend.build_step``: ``costs``/``max_steps`` are static
    (baked into the kernel), ``g``/``case`` and the case's victim-weight
    ``tables`` are traced pytrees entering as kernel operands — so the
    returned ``step`` vmaps over a batch of (graph, case, tables, state)
    tuples exactly like the unfused backends.

    Batching is a :func:`jax.custom_batching.custom_vmap` rule rather than
    Pallas' generic one: the generic rule drives the interpreter once per
    batch element (~2.3× the unbatched step on CPU), while the custom rule
    issues a *single* batched ``pallas_call`` whose kernel body vmaps the
    phase pipeline over the leading axis — the same one-launch-per-step
    shape the unbatched path has, and bitwise the same arithmetic
    (``vmap`` of identical ops).
    """

    @custom_vmap
    def fused(st: SimState, g: GraphArrays, case: SweepCase,
              tables: dlb.VictimTables) -> SimState:
        leaves, treedef = jax.tree_util.tree_flatten((st, g, case, tables))
        n_st = len(jax.tree_util.tree_leaves(st))
        new = _pallas_step(leaves, treedef, n_st, costs, max_steps,
                           batch=False)
        return jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(st), new)

    @fused.def_vmap
    def _fused_batched(axis_size, in_batched, st, g, case, tables):
        def bcast(x, b):
            x = jnp.asarray(x)
            return x if b else jnp.broadcast_to(x[None],
                                                (axis_size,) + x.shape)

        stb, gb, cb, tb = jax.tree_util.tree_map(
            bcast, (st, g, case, tables), tuple(in_batched))
        leaves, treedef = jax.tree_util.tree_flatten((stb, gb, cb, tb))
        n_st = len(jax.tree_util.tree_leaves(stb))
        new = _pallas_step(leaves, treedef, n_st, costs, max_steps,
                           batch=True)
        out = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(stb), new)
        return out, jax.tree_util.tree_map(lambda _: True, out)

    def step(st: SimState) -> SimState:
        return fused(st, g, case, tables)

    return step
