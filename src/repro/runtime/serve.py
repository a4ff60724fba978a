"""Batched serving: prefill + decode loop with greedy/temperature sampling.

The decode step attends over the sequence-sharded KV cache (DESIGN.md Sec. 5);
requests are served in fixed-size batches with left-padded prompts (continuous
batching reduces to swapping retired rows — `generate` retires rows on EOS by
masking).  The collective policy applies through the model's sharding
constraints; this loop adds the serving-level bookkeeping.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig, ShapeConfig
from ..models.model import build_model
from .. import telemetry


@dataclasses.dataclass
class ServeConfig:
    max_new_tokens: int = 32
    temperature: float = 0.0     # 0 => greedy
    eos_id: int = -1             # -1 => never stop early
    seed: int = 0


class BatchedServer:
    def __init__(self, model_cfg: ModelConfig, max_seq: int, batch_size: int,
                 mesh=None, params=None):
        self.cfg = model_cfg
        self.shape = ShapeConfig("serve", max_seq, batch_size, "decode")
        self.model = build_model(model_cfg, mesh)
        self.params = params if params is not None else \
            self.model.init(jax.random.PRNGKey(0))
        self._prefill = jax.jit(self.model.prefill)
        self._decode = jax.jit(self.model.decode)
        # kept apart from the two above, which a caller may wrap
        self._lower = {"serve.prefill": self._prefill.lower,
                       "serve.decode": self._decode.lower}
        self._noted = set()     # prompt shapes whose programs are noted

    def generate(self, prompts: np.ndarray, serve: Optional[ServeConfig] = None) -> np.ndarray:
        """prompts: (B, P) int32 (audio: (B, P, nq)).  Returns generated ids
        (B, max_new) (audio: (B, max_new, nq))."""
        serve = serve or ServeConfig()
        B = prompts.shape[0]
        P = prompts.shape[1]
        note = prompts.shape not in self._noted    # once per program
        self._noted.add(prompts.shape)
        with telemetry.span("serve.prefill"):
            cache = self.model.init_cache(self.shape, batch_size=B)
            batch = {"tokens": jnp.asarray(prompts)}
            if note:
                telemetry.note_program("serve.prefill", self._lower["serve.prefill"],
                                       self.params, batch, cache)
            logits, cache = self._prefill(self.params, batch, cache)
        key = jax.random.PRNGKey(serve.seed)
        outs = []
        done = np.zeros((B,), bool)
        with telemetry.span("serve.sample"):
            tok = self._sample(logits, serve, key)
        for t in range(serve.max_new_tokens):
            with telemetry.span("serve.readback"):
                host_tok = np.asarray(tok)
            outs.append(host_tok)
            if serve.eos_id >= 0:
                done |= (host_tok.reshape(B, -1)[:, 0] == serve.eos_id)
                if done.all():
                    break
            with telemetry.span("serve.decode"):
                pos = jnp.array(P + t, jnp.int32)
                if note and t == 0:
                    telemetry.note_program("serve.decode", self._lower["serve.decode"],
                                           self.params, cache, tok, pos)
                logits, cache = self._decode(self.params, cache, tok, pos)
            with telemetry.span("serve.sample"):
                key, sub = jax.random.split(key)
                tok = self._sample(logits, serve, sub)
        return np.stack(outs, axis=1)

    def _sample(self, logits, serve: ServeConfig, key):
        lg = logits[:, -1] if logits.ndim == 3 else logits[:, -1, :, :]
        if serve.temperature <= 0.0:
            return jnp.argmax(lg, axis=-1).astype(jnp.int32)
        return jax.random.categorical(key, lg / serve.temperature, axis=-1).astype(jnp.int32)
