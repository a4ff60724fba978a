"""Zamba2 hybrid (arXiv:2411.15242; ``Zamba2ForCausalLM`` of HF transformers):
a Mamba2 backbone whose layers in ``hybrid_layer_ids`` first apply one of
``n_mem_blocks`` shared transformer blocks, in turn.

With x0 the token embeddings and D the model width, for each layer l:

  if l is the j-th entry of hybrid_layer_ids (block b = j mod n_mem_blocks):
      u = RMSNorm(concat[x, x0]; in_norm[b])               # 2D wide
      q, k, v = u·Wq[b], u·Wk[b], u·Wv[b]; RoPE on q, k (rotate-half, hd)
      a = causal_softmax(q·kᵀ · (hd / 2) ** -0.5) · v · Wo[b]     # back to D
      m = RMSNorm(a; ff_norm[b])                           # no residual inside
      g, up = m·W1[b] + (m·A[j])·B1[j], m·W3[b] + (m·A[j])·B3[j]   # LoRA, rank r
      T = ((gelu_erf(g) * up)·W2[b])·Lin[j]                # Lin[j]: D x D
  else:
      T = 0
  x = x + Mamba2_l(x + T)      # T enters the mixer's input (its norm), not x

then the final RMSNorm and the (tied) unembedding.  Parameters: the Mamba2
layers stacked (L, ...), the shared blocks stacked (n_mem_blocks, ...), the
per-application adapter and linear stacked (len(hybrid_layer_ids), ...).

Departures from the published code: none in the equations; the Mamba2 dt is
clamped below at ``ssm_dt_min`` after the softplus, as HF's torch path does
(its CUDA path leaves dt unclamped).

Execution: the layers run as runs of a ``lax.scan`` between consecutive
hybrid layers, each over its layer indices with the stacked parameters and
Mamba2 states indexed inside the loop (no copy of a weight slice); the shared
block before a run adds T to the run's first layer.  Caches: the Mamba2 conv
and SSM states of every layer (L, B, ...) beside one (B, S, K, hd) key and
value cache per application.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from .. import telemetry
from ..configs.base import ModelConfig
from .layers import (apply_rope, attention, decode_attention, layer_scan, mlp,
                     rms_norm)
from .mamba2 import mamba_block, ssm_param_defs
from .sharding import Sharder


def attn_scale(cfg: ModelConfig) -> float:
    """Zamba2's softmax scale: the head dim is twice the model's per-head
    width (the block reads [x, x0]), and the scores take (hd / 2) ** -0.5."""
    return (cfg.head_dim / 2) ** -0.5


def hybrid_param_defs(cfg: ModelConfig) -> Dict:
    D, F, V = cfg.d_model, cfg.d_ff, cfg.vocab
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    NB, A, r = cfg.n_mem_blocks, len(cfg.hybrid_layer_ids), cfg.adapter_rank
    defs = {
        "emb": ((V, D), ("vocab", None)),
        "mamba": ssm_param_defs(cfg),
        "blocks": {
            "in_norm": ((NB, 2 * D), (None, None)),
            "wq": ((NB, 2 * D, H * hd), (None, "fsdp", "tp")),
            "wk": ((NB, 2 * D, K * hd), (None, "fsdp", "tp")),
            "wv": ((NB, 2 * D, K * hd), (None, "fsdp", "tp")),
            "wo": ((NB, H * hd, D), (None, "tp", "fsdp")),
            "ff_norm": ((NB, D), (None, None)),
            "mlp": {"w1": ((NB, D, F), (None, "fsdp", "tp")),
                    "w3": ((NB, D, F), (None, "fsdp", "tp")),
                    "w2": ((NB, F, D), (None, "tp", "fsdp"))},
        },
        "adapters": {
            "a": ((A, D, r), (None, "fsdp", None)),
            "b1": ((A, r, F), (None, None, "tp")),
            "b3": ((A, r, F), (None, None, "tp")),
            "lin": ((A, D, D), (None, "fsdp", None)),
        },
        "ln_f": ((D,), (None,)),
    }
    if not cfg.tie_embeddings:
        defs["head"] = ((V, D), ("vocab", None))
    return defs


def _index(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


def shared_block(x, x0, bp, ap, cfg: ModelConfig, shd: Optional[Sharder],
                 positions, kv=None, pos=None):
    """One application of a shared block (weights ``bp``) with its adapter and
    output linear (``ap``).  Returns (T, new (k, v) cache or None)."""
    B, S, D = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    scale = attn_scale(cfg)
    with telemetry.scope("attention"):
        u = rms_norm(jnp.concatenate([x, x0], axis=-1), bp["in_norm"])
        q = jnp.einsum("bse,ef->bsf", u, bp["wq"]).reshape(B, S, H, hd)
        k = jnp.einsum("bse,ef->bsf", u, bp["wk"]).reshape(B, S, K, hd)
        v = jnp.einsum("bse,ef->bsf", u, bp["wv"]).reshape(B, S, K, hd)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        new_kv = None
        if kv is not None and pos is not None:      # decode: one token at pos
            kc, vc = (jax.lax.dynamic_update_slice(c, t.astype(c.dtype), (0, pos, 0, 0))
                      for c, t in zip(kv, (k, v)))
            o = decode_attention(q, kc, vc, pos, shd=shd, scale=scale)
            new_kv = (kc, vc)
        else:
            if kv is not None:                      # prefill: the prompt's keys
                new_kv = tuple(jax.lax.dynamic_update_slice(c, t.astype(c.dtype),
                                                            (0, 0, 0, 0))
                               for c, t in zip(kv, (k, v)))
            o = attention(q, k, v, impl=cfg.attn_impl, q_block=cfg.q_block,
                          shd=shd, scale=scale)
        a = jnp.einsum("bse,ed->bsd", o.reshape(B, S, H * hd), bp["wo"])
    with telemetry.scope("mlp"):
        f = mlp(rms_norm(a, bp["ff_norm"]), bp["mlp"], cfg.mlp, shd, adapter=ap)
    with telemetry.scope("adapter"):
        t = jnp.einsum("bsd,de->bse", f, ap["lin"])
    return t, new_kv


def _mamba_run(x, t, mp, states, first: int, last: int, cfg: ModelConfig,
               shd: Optional[Sharder], decode: bool):
    """Mamba2 layers first..last-1 over x, T (or None) added to the first
    one's input.  ``states`` (L, B, ...) conv / ssm, or None: decode reads and
    writes layer l's, prefill writes them, in place (``layer_scan``)."""

    def step(x, l, lp, st):
        h = x if t is None else jnp.where(l == first, x + t, x)
        out, new = mamba_block(h, lp, cfg, shd, st if decode else None)
        x = x + out
        if shd is not None:
            x = shd.constrain(x, "batch", None, None)
        return x, new

    return layer_scan(step, x, mp, states, first, last,
                      remat=cfg.remat == "block" and states is None)


def hybrid_trunk(params, x0, cfg: ModelConfig, shd: Optional[Sharder], positions,
                 cache: Optional[Dict] = None, pos=None):
    """The layers and the final norm.  cache None: training / scoring;
    pos None: prefill into ``cache``; else decode one token at ``pos``.
    Returns (x, new cache or None)."""
    ids = cfg.hybrid_layer_ids
    bounds = [0, *ids, cfg.n_layers]
    states = None if cache is None else {"conv": cache["conv"], "ssm": cache["ssm"]}
    kvs: List = []

    def block(x, bp, ap, kv):
        return shared_block(x, x0, bp, ap, cfg, shd, positions, kv, pos)

    if cfg.remat == "block" and cache is None:
        block = jax.checkpoint(block)
    x, t = x0, None
    for g in range(len(bounds) - 1):
        if g:
            j = g - 1
            kv = None if cache is None else (cache["k"][j], cache["v"][j])
            t, new_kv = block(x, _index(params["blocks"], j % cfg.n_mem_blocks),
                              _index(params["adapters"], j), kv)
            kvs.append(new_kv)
        if bounds[g + 1] > bounds[g]:
            x, states = _mamba_run(x, t, params["mamba"], states, bounds[g],
                                   bounds[g + 1], cfg, shd, pos is not None)
    x = rms_norm(x, params["ln_f"])
    if cache is None:
        return x, None
    return x, {**states, "k": [kv[0] for kv in kvs], "v": [kv[1] for kv in kvs]}


def hybrid_forward(params, x0, cfg: ModelConfig, shd: Optional[Sharder], positions):
    """Training/scoring trunk.  x0: (B, S, D) embeddings."""
    return hybrid_trunk(params, x0, cfg, shd, positions)[0]
