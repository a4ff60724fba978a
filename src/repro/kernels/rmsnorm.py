"""Pallas TPU fused RMSNorm: one row-block per grid step, fp32 accumulation.

Block shape (rows, d) — rows a multiple of 8, d padded to 128 by the caller's
model dims (all assigned archs have d % 128 == 0 except smollm's 576 = 4.5*128;
the kernel only requires the *tile* alignment, handled by Mosaic's implicit
padding on TPU and exact in interpret mode).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .interpret import interpret_mode


def _rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps) * s_ref[...].astype(jnp.float32)
                  ).astype(o_ref.dtype)


def rmsnorm_fwd(x, scale, *, eps: float = 1e-5, block_rows: int = 128,
                interpret=None) -> jnp.ndarray:
    """x: (R, D); scale: (D,)."""
    r, d = x.shape
    block_rows = min(block_rows, r)
    while r % block_rows:
        block_rows //= 2
    block_rows = max(block_rows, 1)
    return pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(r // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, d), x.dtype),
        interpret=interpret_mode(interpret),
    )(x, scale)
