"""Pallas TPU kernel: blocked flash attention (causal / sliding-window).

Grid = (batch*kv_heads*groups, q_blocks, kv_blocks) with the kv dimension
innermost and ``arbitrary`` semantics: running max / denominator / output
accumulate in VMEM scratch across kv steps and the output tile is emitted on
the last kv block.  BlockSpecs tile q/k/v into (block, head_dim) VMEM panels
(head_dim = 128 on the assigned archs — MXU aligned).

This is the TPU-native replacement for the jnp double-scan in
``models.attention._blocked_sdpa`` (same math, same oracle).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_kernel", "flash_attention_pallas"]

NEG_INF = -1e30


def flash_kernel(q_ref, k_ref, v_ref, out_ref, acc_ref, m_ref, l_ref, *,
                 scale, causal, window, q_block, kv_block, kv_steps):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)                    # (qb, d)
    k = k_ref[0].astype(jnp.float32)                    # (kb, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    pos_q = qi * q_block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    pos_k = ki * kv_block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = jnp.ones_like(s, dtype=jnp.bool_)
    if causal:
        mask &= pos_q >= pos_k
    if window is not None:
        mask &= (pos_q - pos_k) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)
    v = v_ref[0].astype(jnp.float32)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == kv_steps - 1)
    def _emit():
        out_ref[0] = (acc_ref[...] /
                      jnp.maximum(l_ref[...], 1e-30)[:, None]
                      ).astype(out_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal=True, window=None, scale=None,
                           q_block: int = 256, kv_block: int = 256,
                           interpret: bool = False):
    """q: (BH, Sq, d), k/v: (BH, Sk, d) — heads pre-flattened, KV heads
    pre-broadcast (GQA grouping handled by the wrapper)."""
    BH, Sq, d = q.shape
    Sk = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    qb = min(q_block, Sq)
    kb = min(kv_block, Sk)
    if Sq % qb:
        qb = Sq
    if Sk % kb:
        kb = Sk
    nq, nk = Sq // qb, Sk // kb
    grid = (BH, nq, nk)
    kern = functools.partial(flash_kernel, scale=scale, causal=causal,
                             window=window, q_block=qb, kv_block=kb,
                             kv_steps=nk)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, qb, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, kb, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, kb, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, qb, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((qb, d), jnp.float32),
            pltpu.VMEM((qb,), jnp.float32),
            pltpu.VMEM((qb,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
