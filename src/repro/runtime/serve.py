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


#: the cache entries that are the server's own: the keys and values, which
#: each program extends in place
KV = ("k", "v")


def split_cache(cache):
    """(keys and values, recurrent state) of a model's cache."""
    return ({k: a for k, a in cache.items() if k in KV},
            {k: a for k, a in cache.items() if k not in KV})


class BatchedServer:
    def __init__(self, model_cfg: ModelConfig, max_seq: int, batch_size: int,
                 mesh=None, params=None):
        self.cfg = model_cfg
        self.shape = ShapeConfig("serve", max_seq, batch_size, "decode")
        self.model = build_model(model_cfg, mesh)
        self.params = params if params is not None else \
            self.model.init(jax.random.PRNGKey(0))
        model = self.model

        def prefill(params, batch, kv, state):
            return model.prefill(params, batch, {**kv, **state})

        def decode(params, kv, state, tokens, pos):
            return model.decode(params, {**kv, **state}, tokens, pos)

        # The keys and values (``self._kv``) are donated: each program writes
        # its new entries into them in place, and the server alone holds
        # them.  The recurrent state is handed in and out, not donated: a
        # caller may pass a step's input state on again (the benchmark's
        # ``decode_state_unchanged`` fault does), so it must stay readable.
        self._programs = {"serve.prefill": jax.jit(prefill, donate_argnums=2),
                          "serve.decode": jax.jit(decode, donate_argnums=1)}
        # kept apart from ``_prefill`` and ``_decode``, which a caller may wrap
        self._lower = {name: p.lower for name, p in self._programs.items()}
        self._kv = None
        self._noted = set()     # prompt shapes whose programs are noted

    def _prefill(self, params, batch, state):
        logits, cache = self._programs["serve.prefill"](params, batch, self._kv, state)
        self._kv, state = split_cache(cache)
        return logits, state

    def _decode(self, params, state, tokens, pos):
        logits, cache = self._programs["serve.decode"](params, self._kv, state, tokens, pos)
        self._kv, state = split_cache(cache)
        return logits, state

    def generate(self, prompts: np.ndarray, serve: Optional[ServeConfig] = None) -> np.ndarray:
        """prompts: (B, P) int32 (audio: (B, P, nq)).  Returns generated ids
        (B, max_new) (audio: (B, max_new, nq))."""
        serve = serve or ServeConfig()
        B = prompts.shape[0]
        P = prompts.shape[1]
        note = prompts.shape not in self._noted    # once per program
        self._noted.add(prompts.shape)
        with telemetry.span("serve.prefill"):
            self._kv, state = split_cache(self.model.init_cache(self.shape, batch_size=B))
            batch = {"tokens": jnp.asarray(prompts)}
            if note:
                telemetry.note_program("serve.prefill", self._lower["serve.prefill"],
                                       self.params, batch, self._kv, state,
                                       cache_args=(2, 3))
            logits, state = self._prefill(self.params, batch, state)
        key = jax.random.PRNGKey(serve.seed)
        outs = []
        done = np.zeros((B,), bool)
        with telemetry.span("serve.sample"):
            tok = self._sample(logits, serve, key)
        # Decode step t is dispatched before the token it consumes is read
        # back, so the device decodes while the host waits, reads and
        # dispatches: the host's per-token round trip (and its jitter) stays
        # off the device's path.  On an end token the step already
        # dispatched is dropped.
        for t in range(serve.max_new_tokens):
            cur = tok
            with telemetry.span("serve.decode"):
                pos = jnp.array(P + t, jnp.int32)
                if note and t == 0:
                    telemetry.note_program("serve.decode", self._lower["serve.decode"],
                                           self.params, self._kv, state, cur, pos,
                                           cache_args=(1, 2))
                logits, state = self._decode(self.params, state, cur, pos)
            with telemetry.span("serve.sample"):
                key, sub = jax.random.split(key)
                tok = self._sample(logits, serve, sub)
            with telemetry.span("serve.readback"):
                host_tok = np.asarray(cur)
            outs.append(host_tok)
            if serve.eos_id >= 0:
                done |= (host_tok.reshape(B, -1)[:, 0] == serve.eos_id)
                if done.all():
                    break
        return np.stack(outs, axis=1)

    def _sample(self, logits, serve: ServeConfig, key):
        lg = logits[:, -1] if logits.ndim == 3 else logits[:, -1, :, :]
        if serve.temperature <= 0.0:
            return jnp.argmax(lg, axis=-1).astype(jnp.int32)
        return jax.random.categorical(key, lg / serve.temperature, axis=-1).astype(jnp.int32)
