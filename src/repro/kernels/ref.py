"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def attention_ref(q, k, v, *, causal: bool = True, scale=None) -> jnp.ndarray:
    """q, k, v: (BH, S, hd); scores scaled by ``scale`` (default 1/sqrt(hd))."""
    scale = 1.0 / math.sqrt(q.shape[-1]) if scale is None else scale
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        mask = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
        s = jnp.where(mask[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)).astype(q.dtype)


def rmsnorm_ref(x, scale, eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(x.dtype)


def ssd_chunk_ref(x, dt, A, B, C, h0):
    """One SSD chunk, sequential recurrence (oracle for the chunk kernel).

    x: (l, h, p); dt: (l, h); A: (h,); B, C: (l, n) (single group);
    h0: (h, p, n) incoming state.  Returns (y, h_out)."""
    l = x.shape[0]

    def step(hstate, t):
        da = jnp.exp(dt[t] * A)                               # (h,)
        upd = jnp.einsum("h,hp,n->hpn", dt[t], x[t].astype(jnp.float32),
                         B[t].astype(jnp.float32))
        hstate = hstate * da[:, None, None] + upd
        y = jnp.einsum("n,hpn->hp", C[t].astype(jnp.float32), hstate)
        return hstate, y

    h_out, ys = jax.lax.scan(step, h0.astype(jnp.float32), jnp.arange(l))
    return ys.astype(x.dtype), h_out
