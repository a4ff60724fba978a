"""Model facade: one interface over all 10 architectures.

  model = build_model(cfg)
  params = model.init(key)                      # real arrays (smoke/small scale)
  aparams = model.abstract_params()             # ShapeDtypeStructs (dry-run)
  loss = model.loss(params, batch)              # train objective
  logits, cache = model.prefill(params, batch, cache_len)
  logits, cache = model.decode(params, cache, tokens, pos)
  batch = model.input_specs(shape)              # abstract inputs per ShapeConfig
  cache = model.abstract_cache(shape)           # abstract KV/SSM cache

Logical-axis trees (`param_logical`, `cache_logical`, `batch_logical`) feed the
Sharder to produce in/out shardings for pjit — see launch/dryrun.py.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, ShapeConfig
from . import hybrid as H
from . import mamba2 as M
from . import transformer as T
from .sharding import Sharder

PARAM_DTYPE = T.PARAM_DTYPE


def _text_len(cfg: ModelConfig, seq_len: int) -> int:
    return seq_len - cfg.n_img_tokens if cfg.family == "vlm" else seq_len


@dataclasses.dataclass
class Model:
    cfg: ModelConfig
    shd: Sharder

    # ------------------------------------------------------------- params
    def param_defs(self):
        c = self.cfg
        if c.family == "ssm":
            D, V = c.d_model, c.vocab
            return {
                "emb": ((V, D), ("vocab", None)),
                "layers": M.ssm_param_defs(c),
                "ln_f": ((D,), (None,)),
                "head": ((V, D), ("vocab", None)),
            }
        if c.family == "hybrid":
            return H.hybrid_param_defs(c)
        return T.dense_param_defs(c)

    def init(self, key):
        return T.init_from_defs(self.param_defs(), key, self.cfg.d_model)

    def abstract_params(self):
        return T.abstract_from_defs(self.param_defs())

    def param_logical(self):
        return T.logical_from_defs(self.param_defs())

    # ------------------------------------------------------------- embed
    def _embed(self, params, batch) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Returns (x, positions)."""
        c = self.cfg
        if c.family == "vlm":
            tx = params["emb"][batch["tokens"]]
            x = jnp.concatenate([batch["img_embeds"].astype(tx.dtype), tx], axis=1)
        else:
            x = T.embed_tokens(params, batch["tokens"], c)
        if self.shd.mesh is not None:
            x = self.shd.constrain(x, "batch", None, None)
        B, S = x.shape[0], x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        return x, positions

    # --------------------------------------------------------------- loss
    def loss(self, params, batch):
        c = self.cfg
        x, positions = self._embed(params, batch)
        if c.family == "ssm":
            xh = self._ssm_forward(params, x)
        elif c.family == "hybrid":
            xh = H.hybrid_forward(params, x, c, self.shd, positions)
        else:
            xh, aux = T.forward(params, x, c, self.shd, positions)
        logits = T.unembed(params, xh[:, :-1], c, self.shd)
        if c.family == "vlm":
            targets = batch["tokens"][:, 1:]
            logits = logits[:, c.n_img_tokens:]
            loss = T.cross_entropy(logits, targets)
        elif c.n_codebooks:
            targets = batch["tokens"][:, 1:]          # (B, S-1, nq)
            loss = T.cross_entropy(logits.transpose(0, 1, 2, 3), targets)
        else:
            targets = batch["tokens"][:, 1:]
            loss = T.cross_entropy(logits, targets)
        if c.family == "moe":
            loss = loss + 0.01 * aux
        return loss

    # ------------------------------------------------------------- ssm fw
    def _ssm_forward(self, params, x):
        c = self.cfg

        def body(carry, lp):
            out, _ = M.mamba_block(carry, lp, c, self.shd)
            h = carry + out
            if self.shd.mesh is not None:
                h = self.shd.constrain(h, "batch", None, None)
            return h, None

        if c.remat == "block":
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(body, x, params["layers"])
        return T.rms_norm(x, params["ln_f"])

    # ------------------------------------------------------------ serving
    def abstract_cache(self, shape: ShapeConfig, batch_size: Optional[int] = None):
        c = self.cfg
        B = batch_size or shape.global_batch
        S = shape.seq_len
        K, hd = c.n_kv_heads, c.head_dim
        mk = lambda sh, dt=PARAM_DTYPE: jax.ShapeDtypeStruct(sh, dt)
        if c.family in ("ssm", "hybrid"):
            L = c.n_layers
            d = {
                "conv": mk((L, B, c.ssm_conv - 1, M.conv_dim(c))),
                "ssm": mk((L, B, c.ssm_heads, c.ssm_headdim, c.ssm_state), jnp.float32),
            }
            if c.family == "hybrid":    # keys and values of each application
                A = len(c.hybrid_layer_ids)
                d["k"] = [mk((B, S, K, hd)) for _ in range(A)]
                d["v"] = [mk((B, S, K, hd)) for _ in range(A)]
            return d
        L = c.n_layers
        return {"k": mk((L, B, S, K, hd)), "v": mk((L, B, S, K, hd))}

    def cache_logical(self, shape: ShapeConfig):
        c = self.cfg
        if c.family in ("ssm", "hybrid"):
            d = {"conv": (None, "batch", None, "tp"),
                 "ssm": (None, "batch", "tp", None, None)}
            if c.family == "hybrid":
                A = len(c.hybrid_layer_ids)
                d["k"] = [("batch", "seq", None, None)] * A
                d["v"] = [("batch", "seq", None, None)] * A
            return d
        return {"k": (None, "batch", "seq", None, None),
                "v": (None, "batch", "seq", None, None)}

    def init_cache(self, shape: ShapeConfig, batch_size: Optional[int] = None):
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            self.abstract_cache(shape, batch_size),
                            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))

    def prefill(self, params, batch, cache):
        """Forward over a prompt, filling `cache`.  Returns (last_logits, cache)."""
        c = self.cfg
        x, positions = self._embed(params, batch)
        if c.family == "ssm":
            xh, cache = self._ssm_cached(params, x, cache, pos=None)
        elif c.family == "hybrid":
            xh, cache = H.hybrid_trunk(params, x, c, self.shd, positions, cache)
        else:
            xh, cache = T.forward_with_cache(params, x, c, self.shd, positions, cache)
        logits = T.unembed(params, xh[:, -1:], c, self.shd)
        return logits, cache

    def decode(self, params, cache, tokens, pos):
        """One decode step.  tokens: (B,) int32 (audio: (B, nq)).  pos: scalar."""
        c = self.cfg
        if c.n_codebooks:
            x = T.embed_tokens(params, tokens[:, None, :], c)     # (B,1,D)
        elif c.family == "vlm":
            x = params["emb"][tokens[:, None]]
        else:
            x = T.embed_tokens(params, tokens[:, None], c)
        if self.shd.mesh is not None:
            x = self.shd.constrain(x, "batch", None, None)
        positions = jnp.full((x.shape[0], 1), pos, jnp.int32)
        if c.family == "ssm":
            xh, cache = self._ssm_cached(params, x, cache, pos=pos)
        elif c.family == "hybrid":
            xh, cache = H.hybrid_trunk(params, x, c, self.shd, positions, cache,
                                       pos=pos)
        else:
            xh, cache = T.forward_with_cache(params, x, c, self.shd, positions,
                                             cache, pos=pos)
        logits = T.unembed(params, xh[:, -1:], c, self.shd)
        return logits, cache

    def _ssm_cached(self, params, x, cache, pos=None):
        c = self.cfg

        if pos is None:
            def body(carry, lp):
                out, st = M.mamba_block(carry, lp, c, self.shd)
                return carry + out, st
            x, states = jax.lax.scan(body, x, params["layers"])
        else:
            def body(carry, xs):
                lp, conv, ssm = xs
                out, st = M.mamba_block(carry, lp, c, self.shd,
                                        {"conv": conv, "ssm": ssm})
                return carry + out, st
            x, states = jax.lax.scan(body, x, (params["layers"], cache["conv"], cache["ssm"]))
        return T.rms_norm(x, params["ln_f"]), states

    # -------------------------------------------------------------- inputs
    def input_specs(self, shape: ShapeConfig) -> Dict[str, jax.ShapeDtypeStruct]:
        """Abstract model inputs for one ShapeConfig (modality frontends are stubs:
        VLM gets precomputed patch embeddings, audio gets codebook token ids)."""
        c = self.cfg
        B, S = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        if shape.kind == "decode":
            tok = jax.ShapeDtypeStruct((B, c.n_codebooks), i32) if c.n_codebooks \
                else jax.ShapeDtypeStruct((B,), i32)
            return {"tokens": tok, "pos": jax.ShapeDtypeStruct((), i32)}
        if c.family == "vlm":
            return {
                "tokens": jax.ShapeDtypeStruct((B, _text_len(c, S)), i32),
                "img_embeds": jax.ShapeDtypeStruct((B, c.n_img_tokens, c.d_model), PARAM_DTYPE),
            }
        if c.n_codebooks:
            return {"tokens": jax.ShapeDtypeStruct((B, S, c.n_codebooks), i32)}
        return {"tokens": jax.ShapeDtypeStruct((B, S), i32)}

    def batch_logical(self, shape: ShapeConfig):
        c = self.cfg
        if shape.kind == "decode":
            tok = ("batch", None) if c.n_codebooks else ("batch",)
            return {"tokens": tok, "pos": ()}
        if c.family == "vlm":
            return {"tokens": ("batch", None), "img_embeds": ("batch", None, None)}
        if c.n_codebooks:
            return {"tokens": ("batch", None, None)}
        return {"tokens": ("batch", None)}

    def make_batch(self, shape: ShapeConfig, seed: int = 0):
        """Concrete random batch (smoke tests / examples)."""
        import numpy as np
        rng = np.random.RandomState(seed)
        specs = self.input_specs(shape)
        out = {}
        for k, sds in specs.items():
            if jnp.issubdtype(sds.dtype, jnp.integer):
                hi = self.cfg.vocab if k == "tokens" else 2
                if k == "pos":
                    out[k] = jnp.array(shape.seq_len // 2, jnp.int32)
                else:
                    out[k] = jnp.array(rng.randint(0, hi, sds.shape), jnp.int32)
            else:
                out[k] = jnp.array(rng.randn(*sds.shape), jnp.float32).astype(sds.dtype)
        return out


def build_model(cfg: ModelConfig, mesh=None, seq_axes=None, overrides=None) -> Model:
    """seq_axes: remap the "seq" logical axis (KV-cache sequence sharding), e.g.
    ("model", "data") for batch=1 long-context decode where the batch axes idle.
    overrides: full logical-axis remap dict, e.g. {"fsdp": ("data",)} to keep
    ZeRO sharding pod-local (params replicated across pods; gradients cross DCN
    once per step instead of param gathers per microbatch — EXPERIMENTS §Perf)."""
    ov = dict(overrides or {})
    if seq_axes:
        ov["seq"] = tuple(seq_axes)
    return Model(cfg, Sharder(mesh, ov or None))
