"""Pallas TPU RWKV6 recurrence — the fine-grained dependency chain.

Grid = (B, H, time-blocks) with time innermost (sequential); the (Dh, Dh)
state matrix lives in VMEM scratch across the whole sequence, so HBM traffic
is exactly one read of r/k/v/w and one write of out per token — the memory-
optimal schedule for a recurrence whose state fits VMEM (64x64 f32 = 16 KB).

The sequential-within-block form is used rather than the parallel chunked
form because per-channel decay products overflow f32 for fast-forgetting
channels (see kernels/ref.py).  Each step is rank-1-update VPU work
vectorized over (Dh, Dh).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, o_ref, sN_ref, s_ref,
            *, block_t: int, nt: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        s_ref[...] = s0_ref[0, 0].astype(jnp.float32)

    u = u_ref[0].astype(jnp.float32)                      # (Dh,)

    def step(t, _):
        rt = r_ref[0, 0, t].astype(jnp.float32)           # (Dh,)
        kt = k_ref[0, 0, t].astype(jnp.float32)
        vt = v_ref[0, 0, t].astype(jnp.float32)
        wt = w_ref[0, 0, t].astype(jnp.float32)
        kv = kt[:, None] * vt[None, :]                    # (Dh, Dh)
        s = s_ref[...]
        out = jnp.sum(rt[:, None] * (s + u[:, None] * kv), axis=0)
        o_ref[0, 0, t] = out.astype(o_ref.dtype)
        s_ref[...] = wt[:, None] * s + kv
        return 0

    jax.lax.fori_loop(0, block_t, step, 0)

    @pl.when(j == nt - 1)
    def _fin():
        sN_ref[0, 0] = s_ref[...].astype(sN_ref.dtype)


def rwkv6_pallas(r, k, v, w, u, state, *, block_t: int = 128,
                 interpret: bool = False):
    """r/k/v/w: (B, H, T, Dh); u: (H, Dh); state: (B, H, Dh, Dh) f32.
    Returns (out (B,H,T,Dh), state' (B,H,Dh,Dh))."""
    B, H, T, Dh = r.shape
    block_t = min(block_t, T)
    nt = T // block_t
    kernel = functools.partial(_kernel, block_t=block_t, nt=nt)
    out, s_new = pl.pallas_call(
        kernel,
        grid=(B, H, nt),
        in_specs=[
            pl.BlockSpec((1, 1, block_t, Dh), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_t, Dh), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_t, Dh), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_t, Dh), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, Dh), lambda b, h, j: (h, 0)),
            pl.BlockSpec((1, 1, Dh, Dh), lambda b, h, j: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_t, Dh), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, Dh, Dh), lambda b, h, j: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, Dh), r.dtype),
            jax.ShapeDtypeStruct((B, H, Dh, Dh), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((Dh, Dh), jnp.float32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, w, u, state)
    return out, s_new
