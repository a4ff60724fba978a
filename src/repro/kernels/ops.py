"""Jit'd public wrappers around the Pallas kernels (shape adaptation + dispatch).

`interpret=None` lets `kernels.interpret.interpret_mode` decide: compiled on a
TPU, interpreted elsewhere.  The model reaches these via cfg.attn_impl ==
"pallas".
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import flash_attention as _fa
from . import rmsnorm as _rn
from . import ssd_scan as _ssd


@partial(jax.jit, static_argnames=("causal", "q_block", "kv_block", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, q_block: int = 128,
                    kv_block: int = 128, interpret=None):
    """q: (B, S, H, hd); k, v: (B, S, H, hd) (kv already repeated to H heads).
    Returns (B, S, H, hd)."""
    b, s, h, hd = q.shape
    fold = lambda t: t.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    out = _fa.flash_attention_fwd(fold(q), fold(k), fold(v), causal=causal,
                                  q_block=q_block, kv_block=kv_block,
                                  interpret=interpret)
    return out.reshape(b, h, s, hd).transpose(0, 2, 1, 3)


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
