"""Llama-architecture decoder (SmolLM and kin): the plain reference, the
seeded weights, the operation counts, and the map onto the program's layout.

Reference semantics (per token stream, float32, matmuls at HIGHEST):
embedding lookup; per layer x += Wo·attn(RoPE(Wq·n1(x)), RoPE(Wk·n1(x)),
Wv·n1(x)) with causal grouped-query softmax attention, then
x += W2·(silu(W1·n2(x)) * W3·n2(x)); final RMSNorm; logits = x·Embᵀ (tied);
mean next-token cross-entropy.  RMSNorm is x·rsqrt(mean(x²)+eps)·w; RoPE
rotates the two halves of each head (rotate-half convention).
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
LAYER_LEAVES = ("ln1", "wq", "wk", "wv", "wo", "ln2", "w1", "w3", "w2")


def sizes(c: Dict[str, Any]) -> Dict[str, Any]:
    D, H = c["hidden_size"], c["num_attention_heads"]
    return {"L": c["num_hidden_layers"], "D": D, "F": c["intermediate_size"],
            "H": H, "K": c["num_key_value_heads"], "hd": D // H,
            "V": c["vocab_size"], "eps": c["rms_norm_eps"],
            "theta": c["rope_theta"], "std": c["initializer_range"]}


# ------------------------------------------------------------------ counts
def matmul_params(c) -> int:
    """Weights that multiply activations per token: the layers' projections
    and the tied unembedding (the embedding lookup multiplies nothing)."""
    s = sizes(c)
    D, F, H, K, hd = s["D"], s["F"], s["H"], s["K"], s["hd"]
    per_layer = D * H * hd + 2 * D * K * hd + H * hd * D + 3 * D * F
    return s["L"] * per_layer + s["V"] * D


def train_flops_per_token(c, seq: int) -> float:
    """Forward and backward operations one trained token needs: 6 per matmul
    weight, plus causal attention (scores and values over the seq/2 keys a
    token sees on average, each a multiply-add, times 3 for fwd+bwd).
    Recomputation under rematerialization is not counted."""
    s = sizes(c)
    attn = 6 * s["L"] * seq * s["H"] * s["hd"]
    return float(6 * matmul_params(c) + attn)


# ----------------------------------------------------------------- weights
def _shapes(c):
    s = sizes(c)
    D, F, H, K, hd = s["D"], s["F"], s["H"], s["K"], s["hd"]
    return {"ln1": (D,), "wq": (D, H * hd), "wk": (D, K * hd),
            "wv": (D, K * hd), "wo": (H * hd, D), "ln2": (D,),
            "w1": (D, F), "w3": (D, F), "w2": (F, D)}


def init_layer(key, layer, c) -> Dict[str, jnp.ndarray]:
    """Layer ``layer``'s weights in float32, a pure function of (key, layer)."""
    k = jax.random.fold_in(key, layer)
    std = sizes(c)["std"]
    out = {}
    for i, (name, shape) in enumerate(_shapes(c).items()):
        if name.startswith("ln"):
            out[name] = jnp.ones(shape, F32)
        else:
            out[name] = std * jax.random.normal(jax.random.fold_in(k, i), shape, F32)
    return out


def init_globals(key, c) -> Dict[str, jnp.ndarray]:
    s = sizes(c)
    k = jax.random.fold_in(key, 1 << 20)
    return {"emb": s["std"] * jax.random.normal(k, (s["V"], s["D"]), F32),
            "ln_f": jnp.ones((s["D"],), F32)}


def make_weights(key, c, dtype=jnp.bfloat16):
    """All weights, layers stacked on a leading axis, stored as ``dtype``."""
    layers = jax.lax.map(lambda l: init_layer(key, l, c),
                         jnp.arange(sizes(c)["L"]))
    w = {"layers": layers, **init_globals(key, c)}
    return jax.tree.map(lambda a: a.astype(dtype), w)


# --------------------------------------------------------------- reference
def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (S, n, hd)."""
    S, hd = x.shape[0], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def identity(x):
    return x


def _layer(x, p, s, rnd: Callable):
    S = x.shape[0]
    H, K, hd = s["H"], s["K"], s["hd"]
    mm = lambda a, b: jnp.matmul(rnd(a), rnd(b), precision=HI)
    h = _rms(x, p["ln1"], s["eps"])
    q = _rope(mm(h, p["wq"]).reshape(S, H, hd), s["theta"])
    k = _rope(mm(h, p["wk"]).reshape(S, K, hd), s["theta"])
    v = mm(h, p["wv"]).reshape(S, K, hd)
    k = jnp.repeat(k, H // K, axis=1)
    v = jnp.repeat(v, H // K, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", rnd(q), rnd(k), precision=HI) / math.sqrt(hd)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    pr = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", rnd(pr), rnd(v), precision=HI)
    x = x + mm(o.reshape(S, H * hd), p["wo"])
    h = _rms(x, p["ln2"], s["eps"])
    return x + mm(jax.nn.silu(mm(h, p["w1"])) * mm(h, p["w3"]), p["w2"])


def loss(w, tokens, c, rnd: Callable = identity):
    """Mean next-token cross-entropy of one sequence ``tokens`` (S,)."""
    s = sizes(c)
    x = w["emb"][tokens]
    body = jax.checkpoint(lambda x, p: (_layer(x, p, s, rnd), None))
    x, _ = jax.lax.scan(body, x, w["layers"])
    x = _rms(x, w["ln_f"], s["eps"])
    logits = jnp.matmul(rnd(x[:-1]), rnd(w["emb"]).T, precision=HI)
    gold = jnp.take_along_axis(logits, tokens[1:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


# ----------------------------------------------------------- program map
def leaf_name(path: str) -> str:
    """The program's leaf path -> this module's (``layers/mlp/w1`` -> ``layers/w1``)."""
    return path.replace("mlp/", "")


def to_program(w):
    L = w["layers"]
    return {"emb": w["emb"], "ln_f": w["ln_f"],
            "layers": {"ln1": L["ln1"], "wq": L["wq"], "wk": L["wk"],
                       "wv": L["wv"], "wo": L["wo"], "ln2": L["ln2"],
                       "mlp": {"w1": L["w1"], "w3": L["w3"], "w2": L["w2"]}}}


def program_config(c):
    """The program's ModelConfig for this file, refused where it would run
    other sizes than the file states."""
    import dataclasses

    from repro.configs.base import get_config

    p = c["program"]
    mc = dataclasses.replace(get_config(p["arch"]), **p.get("overrides", {}))
    s = sizes(c)
    want = {"family": "dense", "mlp": "swiglu", "n_layers": s["L"],
            "d_model": s["D"], "n_heads": s["H"], "n_kv_heads": s["K"],
            "d_ff": s["F"], "vocab": s["V"], "rope_theta": s["theta"],
            "tie_embeddings": c["tie_word_embeddings"], "qkv_bias": False}
    bad = {k: (getattr(mc, k), v) for k, v in want.items() if getattr(mc, k) != v}
    if bad or s["eps"] != 1e-5 or c["hidden_act"] != "silu":
        raise ValueError(f"program config {p} departs from the file: {bad}")
    return mc
