"""Mamba-2 language model (state-space duality, arXiv:2405.21060): the plain
reference, the seeded weights, operation and byte counts, and the map onto
the program's layout.

Reference semantics, float32, matmuls at HIGHEST.  Per layer, on the residual
stream x (kept in float32, as ``residual_in_fp32`` says):
  h = RMSNorm(x); [z, x', B, C, dt] = h·W_in
  [x', B, C] = silu(causal depthwise conv over time of [x', B, C] + bias)
  dt = softplus(dt + dt_bias); A = −exp(A_log)
  state_t = state_{t−1}·exp(dt_t·A) + dt_t·x'_t ⊗ B_t      (per head, one group)
  y_t = state_t·C_t + D·x'_t
  x += W_out·RMSNorm(y ⊙ silu(z))
then a final RMSNorm and logits = x·Embᵀ (tied).  The recurrence runs token
by token (no chunking), so it shares nothing with the program's chunked scan.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


def sizes(c: Dict[str, Any]) -> Dict[str, Any]:
    D, E, P, N = c["d_model"], c["expand"], c["headdim"], c["d_state"]
    Di = E * D
    return {"L": c["n_layer"], "D": D, "Di": Di, "P": P, "N": N,
            "H": Di // P, "G": c["ngroups"], "K": c["d_conv"],
            "Q": c["chunk_size"], "V": c["vocab_size"],
            "conv_dim": Di + 2 * c["ngroups"] * N, "eps": c["layer_norm_epsilon"],
            "std": c["initializer_range"]}


# ------------------------------------------------------------------ counts
def _layer_matmul(s) -> int:
    D, Di, N, H, G = s["D"], s["Di"], s["N"], s["H"], s["G"]
    return D * (2 * Di + 2 * G * N + H) + Di * D


def weight_bytes(c) -> int:
    """Bytes of the weights a forward pass reads, as stored (bf16; A_log and
    D float32), the tied embedding once."""
    s = sizes(c)
    bf16 = (_layer_matmul(s) + s["K"] * s["conv_dim"] + s["conv_dim"] + s["H"]
            + s["Di"] + s["D"])
    return s["L"] * (2 * bf16 + 4 * 2 * s["H"]) + 2 * (s["V"] * s["D"] + s["D"])


def state_bytes(c, batch: int) -> int:
    """The SSM state (float32) and the conv tail (bf16) of ``batch`` rows."""
    s = sizes(c)
    ssm = s["H"] * s["P"] * s["N"] * 4
    conv = (s["K"] - 1) * s["conv_dim"] * 2
    return batch * s["L"] * (ssm + conv)


def prefill_cost(c, batch: int, prompt: int):
    """(operations, HBM bytes) a prefill of ``batch`` prompts needs: the
    projections and conv over every token, the chunked scan (causal half of
    each chunk's quadratic form, chunk states and their read-out), logits at
    the last position; weights read once, states written."""
    s = sizes(c)
    T = batch * prompt
    H, P, N, Q, G = s["H"], s["P"], s["N"], s["Q"], s["G"]
    ssd = Q * N * G + Q * H * P + 4 * H * P * N
    per_tok = 2 * _layer_matmul(s) + 2 * s["K"] * s["conv_dim"] + ssd
    flops = s["L"] * T * per_tok + 2 * batch * s["V"] * s["D"]
    return float(flops), float(weight_bytes(c) + state_bytes(c, batch))


def decode_cost(c, batch: int):
    """(operations, HBM bytes) of one decode step of ``batch`` rows: every
    weight read once, the states read and written."""
    s = sizes(c)
    per_row = s["L"] * (2 * _layer_matmul(s) + 2 * s["K"] * s["conv_dim"]
                        + 4 * s["H"] * s["P"] * s["N"]) + 2 * s["V"] * s["D"]
    return float(batch * per_row), float(weight_bytes(c) + 2 * state_bytes(c, batch))


# ----------------------------------------------------------------- weights
def _dtypes():
    """Storage type of each leaf, as the program keeps it."""
    return {"A_log": F32, "D_skip": F32}


def init_layer(key, layer, c) -> Dict[str, jnp.ndarray]:
    """Layer ``layer``'s weights in float32, a pure function of (key, layer),
    drawn as the published initialisation draws them: projections normal
    (out_proj scaled by 1/sqrt(n_layer)), conv uniform ±1/sqrt(d_conv),
    A uniform in [1, 16], dt log-uniform in [1e-3, 1e-1] through inverse
    softplus, D and the norms one."""
    s = sizes(c)
    k = jax.random.fold_in(key, layer)
    r = lambda i: jax.random.fold_in(k, i)
    D, Di, N, H, G, K = s["D"], s["Di"], s["N"], s["H"], s["G"], s["K"]
    bound = 1.0 / math.sqrt(K)
    dt = jnp.exp(jax.random.uniform(r(5), (H,), F32, math.log(1e-3), math.log(1e-1)))
    dt = jnp.maximum(dt, 1e-4)
    return {
        "ln": jnp.ones((D,), F32),
        "in_proj": s["std"] * jax.random.normal(r(1), (D, 2 * Di + 2 * G * N + H), F32),
        "conv_w": jax.random.uniform(r(2), (K, s["conv_dim"]), F32, -bound, bound),
        "conv_b": jax.random.uniform(r(3), (s["conv_dim"],), F32, -bound, bound),
        "A_log": jnp.log(jax.random.uniform(r(4), (H,), F32, 1.0, 16.0)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "D_skip": jnp.ones((H,), F32),
        "gate_ln": jnp.ones((Di,), F32),
        "out_proj": (s["std"] / math.sqrt(s["L"]))
        * jax.random.normal(r(6), (Di, D), F32),
    }


def init_globals(key, c):
    s = sizes(c)
    k = jax.random.fold_in(key, 1 << 20)
    return {"emb": s["std"] * jax.random.normal(k, (s["V"], s["D"]), F32),
            "ln_f": jnp.ones((s["D"],), F32)}


def _store(tree, dtype):
    keep = _dtypes()
    return {k: (_store(v, dtype) if isinstance(v, dict)
                else v.astype(keep.get(k, dtype))) for k, v in tree.items()}


def _stored_f32(tree, dtype):
    """float32 values as ``_store`` would store them: rounded with
    ``reduce_precision``, which the compiler keeps (a cast down and back up
    inside one program may be dropped as excess precision)."""
    keep = _dtypes()

    def rnd(k, v):
        dt = jnp.dtype(keep.get(k, dtype))
        if dt == F32:
            return v.astype(F32)
        fi = jnp.finfo(dt)
        return jax.lax.reduce_precision(v.astype(F32), exponent_bits=fi.nexp,
                                        mantissa_bits=fi.nmant)

    return {k: (_stored_f32(v, dtype) if isinstance(v, dict) else rnd(k, v))
            for k, v in tree.items()}


def make_weights(key, c, dtype=jnp.bfloat16):
    layers = jax.lax.map(lambda l: init_layer(key, l, c), jnp.arange(sizes(c)["L"]))
    return _store({"layers": layers, **init_globals(key, c)}, dtype)


# --------------------------------------------------------------- reference
def identity(x):
    return x


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _layer(x, p, s, rnd: Callable):
    """x: (R, T, D) float32 residual stream of R sequences."""
    R, T, _ = x.shape
    Di, N, H, P, G, K = s["Di"], s["N"], s["H"], s["P"], s["G"], s["K"]
    h = _rms(x, p["ln"], s["eps"])
    zx = jnp.matmul(rnd(h), rnd(p["in_proj"]), precision=HI)
    z, xbc, dt = zx[..., :Di], zx[..., Di:2 * Di + 2 * G * N], zx[..., 2 * Di + 2 * G * N:]
    pad = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(pad[:, i:i + T, :] * p["conv_w"][i] for i in range(K)) + p["conv_b"]
    xbc = jax.nn.silu(conv)
    xs = xbc[..., :Di].reshape(R, T, H, P)
    Bm = xbc[..., Di:Di + G * N].reshape(R, T, G, N)
    Cm = xbc[..., Di + G * N:].reshape(R, T, G, N)
    Bm = jnp.repeat(Bm, H // G, axis=2)
    Cm = jnp.repeat(Cm, H // G, axis=2)
    dt = jax.nn.softplus(dt + p["dt_bias"])                       # (R, T, H)
    A = -jnp.exp(p["A_log"])

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp                                 # (R,H,P) (R,H,N) .. (R,H)
        state = (state * jnp.exp(dt_t * A)[..., None, None]
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return state, jnp.sum(state * c_t[:, :, None, :], axis=-1)

    tm = lambda a: jnp.moveaxis(a, 1, 0)
    _, ys = jax.lax.scan(step, jnp.zeros((R, H, P, N), F32),
                         (tm(xs), tm(Bm), tm(Cm), tm(dt)))
    y = tm(ys) + xs * p["D_skip"][:, None]
    y = _rms(y.reshape(R, T, Di) * jax.nn.silu(z), p["gate_ln"], s["eps"])
    return x + jnp.matmul(rnd(y), rnd(p["out_proj"]), precision=HI)


def logits(key, c, tokens, positions, rnd: Callable = identity, dtype=jnp.bfloat16):
    """Reference logits (R, len(positions), V) of the sequences ``tokens``
    (R, T) at ``positions``; each layer's weights are made from ``key`` as the
    configuration stores them (``dtype``) when the layer is reached."""
    s = sizes(c)
    store = lambda t: _stored_f32(t, dtype)
    g = store(init_globals(key, c))
    x = g["emb"][tokens]

    def body(x, layer):
        return _layer(x, store(init_layer(key, layer, c)), s, rnd), None

    x, _ = jax.lax.scan(body, x, jnp.arange(s["L"]))
    x = _rms(x[:, positions], g["ln_f"], s["eps"])
    return jnp.matmul(rnd(x), rnd(g["emb"]).T, precision=HI)


# ----------------------------------------------------------- program map
def leaf_name(path: str) -> str:
    return path


def to_program(w):
    """The program's tree; its untied ``head``, which the tied unembedding
    never reads, is the embedding itself (no copy)."""
    return {"emb": w["emb"], "layers": w["layers"], "ln_f": w["ln_f"],
            "head": w["emb"]}


def program_config(c):
    import dataclasses

    from repro.configs.base import get_config
    from repro.models import mamba2 as pm

    p = c["program"]
    mc = dataclasses.replace(get_config(p["arch"]), **p.get("overrides", {}))
    s = sizes(c)
    want = {"family": "ssm", "n_layers": s["L"], "d_model": s["D"],
            "ssm_state": s["N"], "ssm_expand": c["expand"],
            "ssm_headdim": s["P"], "ssm_conv": s["K"], "ssm_chunk": s["Q"],
            "vocab": s["V"], "tie_embeddings": c["tie_embeddings"]}
    bad = {k: (getattr(mc, k), v) for k, v in want.items() if getattr(mc, k) != v}
    if bad or pm.NGROUPS != s["G"] or s["eps"] != 1e-5:
        raise ValueError(f"program config {p} departs from the file: {bad}")
    return mc
