"""Pallas TPU flash attention (forward): blocked causal attention, online softmax.

TPU mapping (DESIGN.md Sec. 6): grid = (batch*heads, q_blocks, kv_blocks) with
the kv dimension sequential ("arbitrary" semantics); per-(bh, qb) running max /
normalizer / accumulator live in VMEM scratch across kv iterations.  Block shapes
are (q_block, head_dim) / (kv_block, head_dim) — multiples of the (8, 128) TPU
tile; head_dim 64/128 aligns the MXU contraction.

Validated against ref.py in interpret mode on the CPU; compiled by Mosaic on
TPU (see tests/test_tpu_compile.py).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .interpret import interpret_mode

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                  *, causal: bool, q_block: int, kv_block: int, scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    run = True
    if causal:
        # whole kv block strictly above the diagonal? skip.
        run = (ki * kv_block) <= (qi * q_block + q_block - 1)

    @pl.when(run if causal else True)
    def _body():
        q = q_ref[0, :, :].astype(jnp.float32)            # (qb, hd)
        k = k_ref[0, :, :].astype(jnp.float32)            # (kb, hd)
        v = v_ref[0, :, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = qi * q_block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = ki * kv_block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_scr[...]                                # (qb, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _final():
        o_ref[0, :, :] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, causal: bool = True, q_block: int = 128,
                        kv_block: int = 128, interpret=None) -> jnp.ndarray:
    """q, k, v: (BH, S, hd) — batch and heads pre-merged, kv pre-repeated to H.
    Returns (BH, S, hd)."""
    bh, s, hd = q.shape
    q_block = min(q_block, s)
    kv_block = min(kv_block, s)
    assert s % q_block == 0 and s % kv_block == 0
    nq, nk = s // q_block, s // kv_block
    scale = 1.0 / math.sqrt(hd)
    kernel = functools.partial(_flash_kernel, causal=causal, q_block=q_block,
                               kv_block=kv_block, scale=scale)
    return pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, q_block, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, kv_block, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, kv_block, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, q_block, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_block, 1), jnp.float32),    # running max
            pltpu.VMEM((q_block, 1), jnp.float32),    # normalizer
            pltpu.VMEM((q_block, hd), jnp.float32),   # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret_mode(interpret),
    )(q, k, v)
