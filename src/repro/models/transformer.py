"""Decoder-only transformer family: dense (stablelm/mistral/qwen/smollm), VLM
backbone (internvl2), audio decoder (musicgen), MoE (dbrx/deepseek via moe.py).

Everything is pure-functional: params are nested dicts; layer params are stacked
along a leading L axis and consumed by lax.scan (keeps HLO size O(1) in depth —
essential for 88-layer x 512-device dry-runs); remat ("block") checkpoints each
layer body.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .. import telemetry
from .layers import apply_rope, attention, decode_attention, mlp, rms_norm
from .sharding import Sharder

PARAM_DTYPE = jnp.bfloat16


# --------------------------------------------------------------------- defs
def dense_param_defs(cfg: ModelConfig) -> Dict[str, Any]:
    """name -> (shape, logical dims).  Single source for init/abstract/specs."""
    L, D, F, V = cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.vocab
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    defs: Dict[str, Any] = {}
    nq = max(cfg.n_codebooks, 1)
    emb_shape = (V, D) if nq == 1 else (nq, V, D)
    emb_logical = ("vocab", None) if nq == 1 else (None, "vocab", None)
    defs["emb"] = (emb_shape, emb_logical)
    lyr: Dict[str, Any] = {
        "ln1": ((L, D), (None, None)),
        "wo": ((L, H * hd, D), (None, "tp", "fsdp")),
        "ln2": ((L, D), (None, None)),
    }
    if cfg.fused_qkv and not cfg.qkv_bias:
        lyr["wqkv"] = ((L, D, (H + 2 * K) * hd), (None, "fsdp", "tp"))
    else:
        lyr["wq"] = ((L, D, H * hd), (None, "fsdp", "tp"))
        lyr["wk"] = ((L, D, K * hd), (None, "fsdp", "tp"))
        lyr["wv"] = ((L, D, K * hd), (None, "fsdp", "tp"))
    if cfg.qkv_bias:
        lyr["bq"] = ((L, H * hd), (None, "tp"))
        lyr["bk"] = ((L, K * hd), (None, "tp"))
        lyr["bv"] = ((L, K * hd), (None, "tp"))
    if cfg.family == "moe":
        E, Fe = cfg.n_experts, (cfg.d_expert or F)
        lyr["router"] = ((L, D, E), (None, "fsdp", None))
        lyr["experts"] = {
            "w1": ((L, E, D, Fe), (None, "expert", "fsdp", None)),
            "w3": ((L, E, D, Fe), (None, "expert", "fsdp", None)),
            "w2": ((L, E, Fe, D), (None, "expert", None, "fsdp")),
        }
        if cfg.n_shared_experts:
            Fs = cfg.n_shared_experts * Fe
            lyr["shared"] = {
                "w1": ((L, D, Fs), (None, "fsdp", "tp")),
                "w3": ((L, D, Fs), (None, "fsdp", "tp")),
                "w2": ((L, Fs, D), (None, "tp", "fsdp")),
            }
    else:
        m = {"w1": ((L, D, F), (None, "fsdp", "tp")),
             "w2": ((L, F, D), (None, "tp", "fsdp"))}
        if cfg.mlp == "swiglu":
            m["w3"] = ((L, D, F), (None, "fsdp", "tp"))
        lyr["mlp"] = m
    defs["layers"] = lyr
    defs["ln_f"] = ((D,), (None,))
    if not cfg.tie_embeddings:
        defs["head"] = (emb_shape, emb_logical)
    return defs


def init_from_defs(defs, key, d_model: int):
    flat = {}

    def walk(d, prefix=""):
        for k, v in d.items():
            if isinstance(v, dict):
                walk(v, prefix + k + "/")
            else:
                flat[prefix + k] = v

    walk(defs)
    keys = jax.random.split(key, len(flat))
    out_flat = {}
    for (name, (shape, _)), kk in zip(sorted(flat.items()), keys):
        if name.endswith(("ln1", "ln2", "ln_f", "norm", "ln")):
            out_flat[name] = jnp.ones(shape, PARAM_DTYPE)
        elif name.endswith(("bq", "bk", "bv", "dt_bias")):
            out_flat[name] = jnp.zeros(shape, PARAM_DTYPE)
        elif name.endswith("A_log"):
            out_flat[name] = jnp.log(jnp.linspace(1.0, 16.0, shape[-1], dtype=jnp.float32)
                                     * jnp.ones(shape)).astype(jnp.float32)
        elif name.endswith("D_skip"):
            out_flat[name] = jnp.ones(shape, jnp.float32)
        else:
            scale = 1.0 / (d_model ** 0.5)
            out_flat[name] = (jax.random.normal(kk, shape, jnp.float32) * scale).astype(PARAM_DTYPE)

    def rebuild(d, prefix=""):
        out = {}
        for k, v in d.items():
            if isinstance(v, dict):
                out[k] = rebuild(v, prefix + k + "/")
            else:
                out[k] = out_flat[prefix + k]
        return out

    return rebuild(defs)


def abstract_from_defs(defs):
    def walk(d):
        out = {}
        for k, v in d.items():
            if isinstance(v, dict):
                out[k] = walk(v)
            else:
                shape, _ = v
                dt = jnp.float32 if k in ("A_log", "D_skip") else PARAM_DTYPE
                out[k] = jax.ShapeDtypeStruct(shape, dt)
        return out
    return walk(defs)


def logical_from_defs(defs):
    def walk(d):
        out = {}
        for k, v in d.items():
            if isinstance(v, dict):
                out[k] = walk(v)
            else:
                out[k] = v[1]
        return out
    return walk(defs)


# ------------------------------------------------------------------ blocks
def _layer_slice(lyr, i):
    return jax.tree.map(lambda a: a[i], lyr)


@telemetry.scope("attention")
def attn_block(x, lp, cfg: ModelConfig, shd: Sharder, positions,
               kv: Optional[Tuple] = None, pos=None):
    """Pre-norm attention block.  kv=(k_cache, v_cache) for decode (S-sharded)."""
    B, S, D = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    h = rms_norm(x, lp["ln1"], fast=cfg.fast_norm)
    if "wqkv" in lp:
        qkv = jnp.einsum("bsd,de->bse", h, lp["wqkv"])
        q, k, v = jnp.split(qkv, [H * hd, (H + K) * hd], axis=-1)
    else:
        q = jnp.einsum("bsd,de->bse", h, lp["wq"])
        k = jnp.einsum("bsd,de->bse", h, lp["wk"])
        v = jnp.einsum("bsd,de->bse", h, lp["wv"])
        if cfg.qkv_bias:
            q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, K, hd)
    v = v.reshape(B, S, K, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    new_kv = None
    if kv is not None:
        k_cache, v_cache = kv
        if pos is None:  # prefill: write the whole prefix
            k_cache = jax.lax.dynamic_update_slice(k_cache, k.astype(k_cache.dtype), (0, 0, 0, 0))
            v_cache = jax.lax.dynamic_update_slice(v_cache, v.astype(v_cache.dtype), (0, 0, 0, 0))
            o = attention(q, k, v, impl=cfg.attn_impl, q_block=cfg.q_block, shd=shd)
        else:           # decode: write one token at `pos`, attend over the cache
            k_cache = jax.lax.dynamic_update_slice(k_cache, k.astype(k_cache.dtype), (0, pos, 0, 0))
            v_cache = jax.lax.dynamic_update_slice(v_cache, v.astype(v_cache.dtype), (0, pos, 0, 0))
            if shd is not None:
                k_cache = shd.constrain(k_cache, "batch", "seq", None, None)
                v_cache = shd.constrain(v_cache, "batch", "seq", None, None)
            o = decode_attention(q, k_cache, v_cache, pos, shd=shd)
        new_kv = (k_cache, v_cache)
    else:
        o = attention(q, k, v, impl=cfg.attn_impl, q_block=cfg.q_block, shd=shd)
    o = jnp.einsum("bse,ed->bsd", o.reshape(B, S, H * hd), lp["wo"])
    return o, new_kv


@telemetry.scope("mlp")
def ffn_block(x, lp, cfg: ModelConfig, shd: Sharder):
    h = rms_norm(x, lp["ln2"], fast=cfg.fast_norm)
    if cfg.family == "moe":
        from .moe import moe_ffn
        out, aux = moe_ffn(h, lp, cfg, shd)
        return out, aux
    return mlp(h, lp["mlp"], cfg.mlp, shd), 0.0


def transformer_layer(x, lp, cfg: ModelConfig, shd: Sharder, positions,
                      kv=None, pos=None):
    a, new_kv = attn_block(x, lp, cfg, shd, positions, kv, pos)
    x = x + a
    f, aux = ffn_block(x, lp, cfg, shd)
    x = x + f
    if shd is not None:
        # residual_shard: keep the carried residual d_model-sharded over `model`
        # between blocks (16x less saved-activation memory under remat; XLA
        # inserts the per-block all-gather at use — Megatron-SP adapted to FSDP+TP)
        x = shd.constrain(x, "batch", None, "tp" if cfg.residual_shard else None)
    return x, new_kv, aux


# ----------------------------------------------------------------- forward
def embed_tokens(params, tokens, cfg: ModelConfig):
    if cfg.n_codebooks:
        # tokens: (B, S, nq); sum codebook embeddings
        embs = params["emb"]                       # (nq, V, D)
        x = jnp.zeros(tokens.shape[:2] + (cfg.d_model,), PARAM_DTYPE)
        for i in range(cfg.n_codebooks):
            x = x + embs[i][tokens[..., i]]
        return x
    return params["emb"][tokens]                   # (B, S, D)


@telemetry.scope("head")
def unembed(params, x, cfg: ModelConfig, shd: Sharder):
    head = params["emb"] if cfg.tie_embeddings else params["head"]
    if cfg.n_codebooks:
        logits = jnp.einsum("bsd,qvd->bsqv", x, head)
    else:
        logits = jnp.einsum("bsd,vd->bsv", x, head)
    if shd is not None:
        logits = shd.constrain(logits, *(("batch",) + (None,) * (logits.ndim - 2) + ("tp",)))
    return logits


def forward(params, x, cfg: ModelConfig, shd: Sharder, positions):
    """Training/prefill trunk (no cache).  x: (B, S, D) embeddings."""
    lyr = params["layers"]

    def body(carry, lp):
        h, aux = carry
        h, _, a = transformer_layer(h, lp, cfg, shd, positions)
        return (h, aux + a), None

    if cfg.remat == "block":
        body = jax.checkpoint(body)
    if cfg.use_scan:
        (x, aux), _ = jax.lax.scan(body, (x, 0.0), lyr)
    else:
        aux = 0.0
        for i in range(cfg.n_layers):
            (x, aux), _ = body((x, aux), _layer_slice(lyr, i))
    return rms_norm(x, params["ln_f"]), aux


def forward_with_cache(params, x, cfg: ModelConfig, shd: Sharder, positions,
                       cache, pos=None):
    """Prefill (pos=None) or single-token decode (pos=scalar).  cache:
    {"k": (L,B,S,K,hd), "v": ...}."""
    lyr = params["layers"]

    def body(carry, xs):
        h = carry
        lp, kc, vc = xs
        h, new_kv, _ = transformer_layer(h, lp, cfg, shd, positions, (kc, vc), pos)
        return h, new_kv

    x, kvs = jax.lax.scan(body, x, (lyr, cache["k"], cache["v"]))
    new_cache = {"k": kvs[0], "v": kvs[1]}
    return rms_norm(x, params["ln_f"]), new_cache


# ------------------------------------------------------------------- losses
@telemetry.scope("head")
def cross_entropy(logits, targets, mask=None):
    """CE that stays sharded over the vocab dim: the gold logit is extracted with
    a masked reduction (partial + psum) instead of take_along_axis, which would
    all-gather the full (B,S,V) fp32 logits when V is sharded."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    gold = jnp.sum(jnp.where(iota == targets[..., None], logits, 0.0), axis=-1)
    nll = lse - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
