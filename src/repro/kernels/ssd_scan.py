"""Pallas TPU Mamba2 SSD scan: per-(batch, head-block) chunked recurrence.

Grid = (B, H/hb, n_chunks); the chunk dimension is sequential ("arbitrary"):
the (hb, P, N) inter-chunk state lives in VMEM scratch across chunk steps.
Inside a chunk the recurrence is unrolled into the quadratic "dual" form
(matmuls on the MXU) exactly like models/mamba2.ssd_chunked:

  y_diag = (C B^T ∘ L) diag(dt) x      L = exp(segsum(dt*A))
  state  = state * exp(sum dt*A) + B^T (decay ∘ dt ∘ x)
  y_off  = C state_in ∘ exp(cumsum dt*A)

Single B/C group (Mamba2 default).  Validated in interpret mode against
ref.ssd_chunk_ref chained over chunks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .interpret import interpret_mode

NEG_INF = -1e30


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_scr, *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, :, :, :].astype(jnp.float32)        # (l, hb, p)
    dt = dt_ref[0, :, :].astype(jnp.float32)         # (l, hb)
    A = a_ref[...].astype(jnp.float32)               # (hb,)
    Bm = b_ref[0, :, :].astype(jnp.float32)          # (l, n)
    Cm = c_ref[0, :, :].astype(jnp.float32)          # (l, n)

    da = dt * A[None, :]                             # (l, hb)
    da_cs = jnp.cumsum(da, axis=0)                   # inclusive
    # L[i, j] = exp(da_cs[i] - da_cs[j]) for i >= j (per head)
    diff = da_cs[:, None, :] - da_cs[None, :, :]     # (l, l, hb)
    tri = jnp.tril(jnp.ones((chunk, chunk), jnp.bool_))
    L = jnp.where(tri[:, :, None], jnp.exp(diff), 0.0)
    CB = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (l, l)
    M = CB[:, :, None] * L * dt[None, :, :]          # (i, j, hb)
    y_diag = jnp.einsum("ijh,jhp->ihp", M, x)

    # inter-chunk contribution from the incoming state
    state_in = state_scr[...]                        # (hb, p, n)
    y_off = jnp.einsum("ln,hpn->lhp", Cm, state_in) * jnp.exp(da_cs)[:, :, None]

    # state update
    decay = jnp.exp(da_cs[-1:, :] - da_cs)           # (l, hb)
    upd = jnp.einsum("ln,lh,lhp->hpn", Bm, decay * dt, x)
    state_scr[...] = state_in * jnp.exp(da_cs[-1])[:, None, None] + upd

    y_ref[0, :, :, :] = (y_diag + y_off).astype(y_ref.dtype)


def ssd_scan_fwd(x, dt, A, B, C, *, chunk: int = 128, head_block: int = 0,
                 interpret=None):
    """x: (b, s, h, p); dt: (b, s, h); A: (h,); B, C: (b, s, n) (single group).
    Returns y: (b, s, h, p)."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0
    nc = s // chunk
    hb = head_block or h
    assert h % hb == 0
    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(b, h // hb, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, hb, p), lambda bi, hi, ci: (bi, ci, hi, 0)),
            pl.BlockSpec((1, chunk, hb), lambda bi, hi, ci: (bi, ci, hi)),
            pl.BlockSpec((hb,), lambda bi, hi, ci: (hi,)),
            pl.BlockSpec((1, chunk, n), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda bi, hi, ci: (bi, ci, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, hb, p), lambda bi, hi, ci: (bi, ci, hi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, s, h, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((hb, p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret_mode(interpret),
    )(x, dt, A, B, C)
