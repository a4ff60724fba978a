"""Mamba2 (SSD — state-space duality, arXiv:2405.21060) blocks.

Chunked SSD algorithm (training/prefill, sub-quadratic):
  within a chunk of length Q the recurrence is unrolled into an attention-like
  quadratic form (the "duality"); across chunks a linear recurrence carries the
  (H, P, N) state.  `ssd_chunked` is the jnp implementation (also the oracle for
  kernels/ssd_scan); `ssd_reference` is the naive sequential recurrence used to
  validate it.

Decode is O(1) per token: the state update h <- h*exp(dt*A) + dt * x B^T.

Sharding: d_inner (heads H) carries "tp"; the state dim N is replicated; the
recurrence is local to each (batch, head) shard — an SSM has *no* sequence-dim
collectives, which is exactly why the attention-centric parts of the paper's
technique do not bind here (DESIGN.md §Arch-applicability).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .. import telemetry
from .layers import rms_norm
from .sharding import Sharder

NGROUPS = 1  # B/C projection groups (Mamba2 default 1 group broadcast over heads)


def ssm_param_defs(cfg: ModelConfig, n_layers: Optional[int] = None) -> Dict:
    L = n_layers if n_layers is not None else cfg.n_layers
    D, Di, N, H = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    conv_dim = Di + 2 * NGROUPS * N
    return {
        "ln": ((L, D), (None, None)),
        "in_proj": ((L, D, 2 * Di + 2 * NGROUPS * N + H), (None, "fsdp", "tp")),
        "conv_w": ((L, cfg.ssm_conv, conv_dim), (None, None, "tp")),
        "conv_b": ((L, conv_dim), (None, "tp")),
        "A_log": ((L, H), (None, "tp")),
        "dt_bias": ((L, H), (None, "tp")),
        "D_skip": ((L, H), (None, "tp")),
        "gate_ln": ((L, Di), (None, "tp")),
        "out_proj": ((L, Di, D), (None, "tp", "fsdp")),
    }


def _segsum(da: jnp.ndarray) -> jnp.ndarray:
    """Stable segment-sum: out[..., i, j] = sum_{j < t <= i} da[..., t] (lower-tri)."""
    Q = da.shape[-1]
    cs = jnp.cumsum(da, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]          # (..., i, j): sum_{j<t<=i}
    mask = jnp.tril(jnp.ones((Q, Q), bool), k=0)
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(x, dt, A, B, C, chunk: int):
    """SSD over chunks.

    x: (b, s, h, p); dt: (b, s, h) (post-softplus); A: (h,) negative;
    B, C: (b, s, g, n).  Returns y: (b, s, h, p) and final state (b, h, p, n).
    """
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    assert s % chunk == 0
    nc = s // chunk
    rep = h // g
    xb = x.reshape(b, nc, chunk, h, p)
    dtb = dt.reshape(b, nc, chunk, h)
    Bb = jnp.repeat(B.reshape(b, nc, chunk, g, n), rep, axis=3)   # (b,nc,l,h,n)
    Cb = jnp.repeat(C.reshape(b, nc, chunk, g, n), rep, axis=3)

    da = dtb * A                                                    # (b,nc,l,h)
    da_t = da.transpose(0, 1, 3, 2)                                 # (b,nc,h,l)
    da_cs = jnp.cumsum(da_t, axis=-1)                               # (b,nc,h,l)

    # ---- intra-chunk (the "attention-like" quadratic block) ----
    L = jnp.exp(_segsum(da_t))                                      # (b,nc,h,l,l)
    CB = jnp.einsum("bcihn,bcjhn->bchij", Cb, Bb)
    M = CB * L
    y_diag = jnp.einsum("bchij,bcjh,bcjhp->bcihp", M.astype(jnp.float32),
                        dtb.astype(jnp.float32), xb.astype(jnp.float32))

    # ---- chunk states ----
    decay_states = jnp.exp(da_cs[..., -1:] - da_cs)                 # (b,nc,h,l)
    states = jnp.einsum("bclhn,bchl,bclh,bclhp->bchpn",
                        Bb.astype(jnp.float32), decay_states.astype(jnp.float32),
                        dtb.astype(jnp.float32), xb.astype(jnp.float32))

    # ---- inter-chunk recurrence ----
    chunk_decay = jnp.exp(da_cs[..., -1])                           # (b,nc,h)

    def scan_fn(prev, inp):
        st, dec = inp                                               # (b,h,p,n), (b,h)
        new = st + prev * dec[..., None, None]
        return new, prev                                            # emit state *entering* chunk

    init = jnp.zeros((b, h, p, n), jnp.float32)
    final, prev_states = jax.lax.scan(
        scan_fn, init,
        (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2)))
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)              # (b,nc,h,p,n)

    # ---- inter-chunk output ----
    state_decay = jnp.exp(da_cs)                                    # (b,nc,h,l)
    y_off = jnp.einsum("bclhn,bchpn,bchl->bclhp", Cb.astype(jnp.float32),
                       prev_states, state_decay.astype(jnp.float32))

    y = (y_diag + y_off).reshape(b, s, h, p)
    return y.astype(x.dtype), final


def ssd_reference(x, dt, A, B, C):
    """Naive sequential recurrence (oracle): h_t = h_{t-1}*exp(dt_t A) + dt_t B_t x_t^T."""
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    rep = h // g
    Bf = jnp.repeat(B, rep, axis=2).astype(jnp.float32)
    Cf = jnp.repeat(C, rep, axis=2).astype(jnp.float32)
    xf, dtf = x.astype(jnp.float32), dt.astype(jnp.float32)

    def step(hstate, t):
        da = jnp.exp(dtf[:, t] * A)                                 # (b,h)
        upd = jnp.einsum("bh,bhp,bhn->bhpn", dtf[:, t], xf[:, t], Bf[:, t])
        hstate = hstate * da[..., None, None] + upd
        y = jnp.einsum("bhn,bhpn->bhp", Cf[:, t], hstate)
        return hstate, y

    init = jnp.zeros((b, h, p, n), jnp.float32)
    final, ys = jax.lax.scan(step, init, jnp.arange(s))
    return ys.transpose(1, 0, 2, 3).astype(x.dtype), final


def _causal_conv(xBC, w, bias, conv_state=None):
    """Depthwise causal conv1d, kernel (K, C).  xBC: (B, S, C).
    With conv_state (B, K-1, C) for decode (S=1), returns (out, new_state)."""
    K = w.shape[0]
    if conv_state is not None:
        window = jnp.concatenate([conv_state, xBC], axis=1)         # (B, K, C)
        out = jnp.einsum("bkc,kc->bc", window.astype(jnp.float32), w.astype(jnp.float32))
        out = out + bias
        return jax.nn.silu(out)[:, None, :].astype(xBC.dtype), window[:, 1:, :]
    pad = jnp.pad(xBC, ((0, 0), (K - 1, 0), (0, 0)))
    windows = jnp.stack([pad[:, i:i + xBC.shape[1], :] for i in range(K)], axis=2)  # (B,S,K,C)
    out = jnp.einsum("bskc,kc->bsc", windows.astype(jnp.float32), w.astype(jnp.float32)) + bias
    return jax.nn.silu(out).astype(xBC.dtype), pad[:, -(K - 1):, :] if K > 1 else None


@telemetry.scope("mixer")
def mamba_block(x, lp, cfg: ModelConfig, shd: Optional[Sharder],
                state: Optional[Dict] = None):
    """One Mamba2 block.  x: (B, S, D).  state (decode): {"conv": (B,K-1,Cdim),
    "ssm": (B,H,P,N)}.  Returns (out, new_state)."""
    Bsz, S, D = x.shape
    Di, N, H, P = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_headdim
    h = rms_norm(x, lp["ln"], fast=cfg.fast_norm)
    zxbcdt = jnp.einsum("bsd,de->bse", h, lp["in_proj"])
    z, xin, BC, dt = jnp.split(zxbcdt, [Di, 2 * Di, 2 * Di + 2 * NGROUPS * N], axis=-1)
    xBC = jnp.concatenate([xin, BC], axis=-1)                       # (B,S,Di+2gN)
    if shd is not None:
        xBC = shd.constrain(xBC, "batch", None, "tp")

    if state is None:
        xBC, new_conv = _causal_conv(xBC, lp["conv_w"], lp["conv_b"])
    else:
        xBC, new_conv = _causal_conv(xBC, lp["conv_w"], lp["conv_b"], state["conv"])

    xs, Bmat, Cmat = jnp.split(xBC, [Di, Di + NGROUPS * N], axis=-1)
    xs = xs.reshape(Bsz, S, H, P)
    Bmat = Bmat.reshape(Bsz, S, NGROUPS, N)
    Cmat = Cmat.reshape(Bsz, S, NGROUPS, N)
    A = -jnp.exp(lp["A_log"].astype(jnp.float32))                   # (H,)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"])    # (B,S,H)

    if state is None:
        chunk = min(cfg.ssm_chunk, S)
        if S % chunk != 0:
            y, final = ssd_reference(xs, dt, A, Bmat, Cmat)
        else:
            y, final = ssd_chunked(xs, dt, A, Bmat, Cmat, chunk)
        new_ssm = final
    else:
        # O(1) decode: single-step recurrence
        da = jnp.exp(dt[:, 0] * A)                                  # (B,H)
        rep = H // NGROUPS
        Bf = jnp.repeat(Bmat[:, 0], rep, axis=1).astype(jnp.float32)
        Cf = jnp.repeat(Cmat[:, 0], rep, axis=1).astype(jnp.float32)
        upd = jnp.einsum("bh,bhp,bhn->bhpn", dt[:, 0], xs[:, 0].astype(jnp.float32), Bf)
        new_ssm = state["ssm"] * da[..., None, None] + upd
        y = jnp.einsum("bhn,bhpn->bhp", Cf, new_ssm)[:, None].astype(x.dtype)

    y = (y.astype(jnp.float32) + xs.astype(jnp.float32) * lp["D_skip"][None, None, :, None])
    y = y.reshape(Bsz, S, Di).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype), lp["gate_ln"],
                 fast=cfg.fast_norm)
    out = jnp.einsum("bse,ed->bsd", y, lp["out_proj"])
    # prefill also returns resumable states (conv tail + final ssm state)
    new_state = {"conv": new_conv, "ssm": new_ssm}
    return out, new_state
