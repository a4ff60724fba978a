"""Jit'd public wrappers around the Pallas kernels (shape adaptation + dispatch).

`interpret=None` lets `kernels.interpret.interpret_mode` decide: compiled on a
TPU, interpreted elsewhere.  Attention's kernel, with its backward, is
`kernels.flash_attention.flash_attention`, which `models.layers.attention`
calls.
"""
from __future__ import annotations

from functools import partial

import jax

from . import rmsnorm as _rn
from . import ssd_scan as _ssd


@partial(jax.jit, static_argnames=("eps", "interpret"))
def rmsnorm(x, scale, *, eps: float = 1e-5, interpret=None):
    """x: (..., D)."""
    shape = x.shape
    out = _rn.rmsnorm_fwd(x.reshape(-1, shape[-1]), scale, eps=eps,
                          interpret=interpret)
    return out.reshape(shape)


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, B, C, *, chunk: int = 128, interpret=None):
    """Mamba2 SSD over chunks.  x: (b, s, h, p); B, C: (b, s, 1, n) or (b, s, n)."""
    if B.ndim == 4:
        B = B[:, :, 0, :]
    if C.ndim == 4:
        C = C[:, :, 0, :]
    return _ssd.ssd_scan_fwd(x, dt, A, B, C, chunk=chunk, interpret=interpret)
