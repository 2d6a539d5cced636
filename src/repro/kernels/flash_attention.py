"""Pallas TPU flash attention (forward).

Design (TPU-native, not a CUDA port): grid = (B, H, nq, nk) with the KV
dimension innermost and declared "arbitrary" (sequential) so the online-
softmax accumulators live in VMEM scratch across KV steps.  Q/K/V blocks are
MXU-aligned (block_q x head_dim, block_k x head_dim); masking (causal /
sliding window) is computed from broadcasted iotas; softcap is fused.

Used for training/prefill forward on TPU backends (ops.py dispatch); the
backward falls back to ref.py's custom-VJP chunked implementation.  GQA is
pre-expanded by the wrapper (k/v repeated to H heads) — the expansion is the
TP-friendly layout anyway (see models/layers.attn_apply).

Validated against ref.attention_naive in interpret mode over a shape/dtype
sweep (tests/test_kernels.py).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            block_q: int, block_k: int, nk: int, causal: bool, window: int,
            softcap: Optional[float], scale: float):
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    i = pl.program_id(2)
    qpos = i * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 0)
    kpos = j * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 1)
    run = True
    if causal:
        # whole block above the diagonal contributes nothing
        run = (j * block_k) <= (i * block_q + block_q - 1)

    @pl.when(run if causal else True)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale    # (bq, dh)
        k = k_ref[0, 0].astype(jnp.float32)            # (bk, dh)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        mask = jnp.ones((block_q, block_k), bool)
        if causal:
            mask &= qpos >= kpos
        if window > 0:
            mask &= (qpos - kpos) < window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal=True, window=0, softcap=None,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = False):
    """q: (B,H,S,Dh); k/v: (B,KV,S,Dh) — KV expanded to H if needed."""
    B, H, S, Dh = q.shape
    if k.shape[1] != H:                       # GQA: expand for the kernel
        rep = H // k.shape[1]
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    nq = S // block_q
    nk = S // block_k
    kernel = functools.partial(
        _kernel, block_q=block_q, block_k=block_k, nk=nk, causal=causal,
        window=window, softcap=softcap, scale=Dh ** -0.5)
    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, Dh), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, Dh), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_k, Dh), lambda b, h, i, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, Dh),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, Dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, Dh), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
