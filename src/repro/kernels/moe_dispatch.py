"""Pallas TPU MoE dispatch — the XQueue *push* as a TPU kernel.

The paper's core data structure is a per-worker SPSC queue that only its
producer writes.  The TPU-native translation: grid = (experts, token-blocks)
with the token dimension innermost ("arbitrary"/sequential), so each expert
program owns its (C, D) queue slice resident in VMEM for the entire pass and
appends matching tokens with dynamic row stores — single-writer by
construction, zero synchronization, exactly the SPSC discipline.

Routing (expert/pos per token) comes precomputed from core/balance.py (the
NA-RP/NA-WS redirect logic); this kernel is pure data movement.  Work is
O(E/ep * T) scans per chip — on TPU the scan is a VMEM-resident masked
select over (block_t, k) int lanes, with the HBM traffic being just x once
per expert-row of the grid (the dominant term; see tests for correctness,
EXPERIMENTS.md §Perf for the structural argument).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, e_ref, p_ref, o_ref, *, block_t: int, k: int):
    e = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[0] = jnp.zeros_like(o_ref[0])

    def body(i, _):
        t = i // k
        kk = i % k
        match = (e_ref[t, kk] == e)

        @pl.when(match)
        def _store():
            p = p_ref[t, kk]
            o_ref[0, pl.dslice(p, 1), :] = x_ref[pl.dslice(t, 1), :]

        return 0

    jax.lax.fori_loop(0, block_t * k, body, 0)


def moe_dispatch_pallas(x, expert, pos, *, n_experts: int, capacity: int,
                        block_t: int = 256, interpret: bool = False):
    """x: (T, D); expert/pos: (T, k) (-1 = dropped).  Returns (E, C, D)."""
    T, D = x.shape
    k = expert.shape[1]
    block_t = min(block_t, T)
    nt = T // block_t
    kernel = functools.partial(_kernel, block_t=block_t, k=k)
    return pl.pallas_call(
        kernel,
        grid=(n_experts, nt),
        in_specs=[
            pl.BlockSpec((block_t, D), lambda e, j: (j, 0)),
            pl.BlockSpec((block_t, k), lambda e, j: (j, 0)),
            pl.BlockSpec((block_t, k), lambda e, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((1, capacity, D), lambda e, j: (e, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_experts, capacity, D), x.dtype),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, expert, pos)
