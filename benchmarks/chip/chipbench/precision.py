"""The control's lower precision: float8 with a per-tensor scale, as fp8
matmuls take their operands: e4m3 for values, e5m2 for the gradient that
flows back through them (the usual fp8 training recipe)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

def _round(x, dtype):
    s = jnp.max(jnp.abs(x)) / float(jnp.finfo(dtype).max)
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(dtype).astype(x.dtype) * s


@jax.custom_vjp
def fp8(x):
    return _round(x, jnp.float8_e4m3fn)


def _fwd(x):
    return fp8(x), None


def _bwd(_, g):
    return (_round(g, jnp.float8_e5m2),)


fp8.defvjp(_fwd, _bwd)
