"""AdamW with cosine schedule, global-norm clipping, ZeRO-sharded state.

Optimizer moments are fp32 and inherit the parameter sharding (params carry
"fsdp"/"tp" logical axes => m/v are sharded the same way = ZeRO-3).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from .. import telemetry


@dataclasses.dataclass(frozen=True)
class OptConfig:
    peak_lr: float = 3e-4
    min_lr: float = 3e-5
    warmup_steps: int = 100
    decay_steps: int = 10000
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0


def schedule(step: jnp.ndarray, cfg: OptConfig) -> jnp.ndarray:
    warm = cfg.peak_lr * jnp.minimum(step / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    t = jnp.clip((step - cfg.warmup_steps) / jnp.maximum(cfg.decay_steps - cfg.warmup_steps, 1), 0.0, 1.0)
    cos = cfg.min_lr + 0.5 * (cfg.peak_lr - cfg.min_lr) * (1 + jnp.cos(jnp.pi * t))
    return jnp.where(step < cfg.warmup_steps, warm, cos)


def init_opt_state(params) -> Dict[str, Any]:
    zeros32 = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {
        "m": jax.tree.map(zeros32, params),
        "v": jax.tree.map(zeros32, params),
        "step": jnp.zeros((), jnp.int32),
    }


def abstract_opt_state(abstract_params):
    f32 = lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32)
    return {
        "m": jax.tree.map(f32, abstract_params),
        "v": jax.tree.map(f32, abstract_params),
        "step": jax.ShapeDtypeStruct((), jnp.int32),
    }


def opt_state_logical(param_logical):
    ident = lambda l: l
    return {
        "m": jax.tree.map(ident, param_logical, is_leaf=lambda x: isinstance(x, tuple)),
        "v": jax.tree.map(ident, param_logical, is_leaf=lambda x: isinstance(x, tuple)),
        "step": (),
    }


def global_norm(tree) -> jnp.ndarray:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves))


@telemetry.scope("optimizer")
def apply_updates(params, grads, state, cfg: OptConfig) -> Tuple[Any, Dict[str, Any], Dict[str, jnp.ndarray]]:
    step = state["step"] + 1
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.clip_norm / (gnorm + 1e-9))
    lr = schedule(step, cfg)
    b1, b2 = cfg.b1, cfg.b2
    bc1 = 1 - b1 ** step.astype(jnp.float32)
    bc2 = 1 - b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v):
        g = g.astype(jnp.float32) * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        mhat = m / bc1
        vhat = v / bc2
        delta = mhat / (jnp.sqrt(vhat) + cfg.eps) + cfg.weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * delta).astype(p.dtype), m, v

    flat_p, tdef = jax.tree.flatten(params)
    flat_g = tdef.flatten_up_to(grads)
    flat_m = tdef.flatten_up_to(state["m"])
    flat_v = tdef.flatten_up_to(state["v"])
    out = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
    new_p = tdef.unflatten([o[0] for o in out])
    new_m = tdef.unflatten([o[1] for o in out])
    new_v = tdef.unflatten([o[2] for o in out])
    metrics = {"grad_norm": gnorm, "lr": lr}
    return new_p, {"m": new_m, "v": new_v, "step": step}, metrics
