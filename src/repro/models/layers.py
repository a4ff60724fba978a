"""Shared neural building blocks: RMSNorm, RoPE, GQA attention (fused /
blockwise / naive / sequence-sharded decode), MLPs.

Attention implementations (``attention(impl=...)``, ``ModelConfig.attn_impl``):
  * flash      — the default: the fused causal flash kernel with its backward
                 (kernels/flash_attention, grouped kv, masked blocks skipped)
                 where it compiles — a TPU backend, causal with no query
                 offset, S a multiple of the kernel's block, hd a multiple of
                 64, no context parallelism — and the blockwise scan elsewhere.
  * pallas     — the fused kernel, forced (interpreted off a TPU).
  * blockwise  — lax.scan over query blocks with a bounded score tile; identical
                 math, memory O(q_block * S) instead of O(S^2).
  * naive      — full (S x S) scores; reference/oracle only.
  * decode     — one query position against a KV cache whose *sequence* dimension
                 is sharded over the `model` mesh axis ("seq" logical axis): XLA
                 partitions the contraction and inserts the psum — the TPU-native
                 flash-decode / sequence-parallel pattern of DESIGN.md Sec. 5,
                 which is what makes 500k-token decode representable.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from ..kernels import flash_attention as fa
from .sharding import Sharder


def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-5,
             fast: bool = False) -> jnp.ndarray:
    dt = x.dtype
    if fast:
        # beyond-paper §Perf knob: variance via a dot with fp32 accumulation —
        # no materialized fp32 copy of x (2x traffic) per norm; the scale
        # multiply stays in the input dtype (standard mixed-precision practice)
        var = jnp.einsum("...d,...d->...", x, x,
                         preferred_element_type=jnp.float32)[..., None] / x.shape[-1]
        inv = jax.lax.rsqrt(var + eps).astype(dt)
        return x * inv * scale.astype(dt)
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(dt)


def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., S, H, hd); positions: (..., S).

    (x1, x2) -> (x1 cos - x2 sin, x2 cos + x1 sin) in float32, written as
    x cos + rot(x) sin over the whole head, with rot(x) = (-x2, x1) a matmul
    by an exact signed permutation.  The result is the same; on a TPU the
    half-width split and concatenate, laid out for the fused attention
    kernel, cost 30 ms of a 0.70 s smollm-135m training step on a TPU v5e
    (PERF.md)."""
    hd = x.shape[-1]
    freqs = jnp.tile(rope_freqs(hd, theta), 2)          # (hd,): each half's
    ang = positions[..., :, None, None].astype(jnp.float32) * freqs  # (..., S, 1, hd)
    half = np.eye(hd // 2, dtype=np.float32)
    rot = np.block([[0 * half, half], [-half, 0 * half]])   # x @ rot = (-x2, x1)
    xr = jnp.einsum("...d,de->...e", x, jnp.asarray(rot, x.dtype),
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)
    out = x.astype(jnp.float32) * jnp.cos(ang) + xr * jnp.sin(ang)
    return out.astype(x.dtype)


def _repeat_kv(k: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """(B, S, K, hd) -> (B, S, K*n_rep, hd)."""
    if n_rep == 1:
        return k
    b, s, kh, hd = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, kh, n_rep, hd)).reshape(b, s, kh * n_rep, hd)


def _scale(scale: Optional[float], hd: int) -> float:
    return 1.0 / math.sqrt(hd) if scale is None else scale


def naive_attention(q, k, v, *, causal: bool = True, q_offset: int = 0,
                    shd: Optional[Sharder] = None,
                    scale: Optional[float] = None) -> jnp.ndarray:
    """q: (B, Sq, H, hd); k, v: (B, Sk, H, hd) (kv already repeated to H).
    Scores are scaled by ``scale`` (default 1/sqrt(hd))."""
    scale = _scale(scale, q.shape[-1])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        qpos = jnp.arange(sq) + q_offset
        mask = qpos[:, None] >= jnp.arange(sk)[None, :]
        scores = jnp.where(mask[None, None], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


def blockwise_attention(q, k, v, *, causal: bool = True, q_block: int = 256,
                        q_offset: int = 0, shd: Optional[Sharder] = None,
                        context_parallel: bool = False,
                        scale: Optional[float] = None) -> jnp.ndarray:
    """Memory-bounded attention: scan over query blocks (score tile q_block x Sk).

    context_parallel=True shards the *within-block* query dim over the `model`
    axis ("seq" logical) — the fallback when the head count does not divide the
    TP axis (smollm 9H, qwen 20H, musicgen 24H on a 16-way axis): compute still
    splits 16 ways, with kv replicated (the all-gathered kv of standard TP)."""
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    qb = min(q_block, sq)
    if sq % qb != 0:
        return naive_attention(q, k, v, causal=causal, q_offset=q_offset, shd=shd,
                               scale=scale)
    nb = sq // qb
    scale = _scale(scale, hd)
    qr = q.reshape(b, nb, qb, h, hd).transpose(1, 0, 2, 3, 4)   # (nb, B, qb, H, hd)
    kpos = jnp.arange(sk)

    def body(_, args):
        i, qi = args
        if shd is not None and context_parallel:
            qi = shd.constrain(qi, "batch", "seq", None, None)
        s = jnp.einsum("bqhd,bkhd->bhqk", qi, k,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = i * qb + jnp.arange(qb) + q_offset
            mask = qpos[:, None] >= kpos[None, :]
            s = jnp.where(mask[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
        if shd is not None and context_parallel:
            o = shd.constrain(o, "batch", "seq", None, None)  # (B, qb, H, hd)
        return None, o

    # Flash semantics: never materialize the (nb, B, H, qb, Sk) probability stack
    # for backward — recompute each block's scores in the backward pass.
    body = jax.checkpoint(body)
    _, out = jax.lax.scan(body, None, (jnp.arange(nb), qr))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, hd)


def decode_attention(q, k_cache, v_cache, pos, *, shd: Optional[Sharder] = None,
                     scale: Optional[float] = None) -> jnp.ndarray:
    """One-token attention against a (possibly sequence-sharded) KV cache.

    q: (B, 1, H, hd); caches: (B, S, K, hd); pos: scalar index of the current token
    (caches already contain it).  The cache's S dim carries the "seq" logical axis;
    the softmax/contraction over S is partitioned by XLA (partial max/sum + psum).

    GQA stays *grouped*: q is reshaped to (B, 1, K, G, hd) and contracted against
    the K-head cache directly — no materialized H-head repeat (12x for
    mistral-large), and `preferred_element_type` keeps the cache operand bf16
    with fp32 accumulation instead of upcasting the whole cache slice (measured:
    -0.9 GB/layer fused f32 transpose-copies on the 123B decode cell)."""
    b, s, kh, hd = k_cache.shape
    h = q.shape[2]
    g = h // kh
    qg = q.reshape(b, 1, kh, g, hd)
    if shd is not None:
        k_cache = shd.constrain(k_cache, "batch", "seq", None, None)
        v_cache = shd.constrain(v_cache, "batch", "seq", None, None)
    scale = _scale(scale, hd)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k_cache,
                        preferred_element_type=jnp.float32) * scale  # (B,K,G,1,S)
    mask = (jnp.arange(s) <= pos)[None, None, None, None, :]
    scores = jnp.where(mask, scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, h, hd).astype(q.dtype)


def _fused_specs(q, k, shd: Optional[Sharder]):
    """None where the fused kernel runs on the arrays as they are (no mesh,
    or one device); else the (q, kv) PartitionSpecs of its per-shard call:
    batch over the data axes, heads over `tp` where both H and K divide it.
    Raises ValueError where the mesh cannot be split that way."""
    if shd is None or shd.mesh is None or shd.mesh.size == 1:
        return None
    tp = shd.axis_size("tp")
    if tp > 1 and (q.shape[2] % tp or k.shape[2] % tp):
        raise ValueError(f"{q.shape[2]} / {k.shape[2]} heads do not divide tp={tp}")
    if q.shape[0] % shd.axis_size("batch"):
        raise ValueError(f"batch {q.shape[0]} does not divide the data axes")
    heads = "tp" if tp > 1 else None
    return (shd.spec(("batch", None, heads, None), q.shape),
            shd.spec(("batch", None, heads, None), k.shape))


def _fused(q, k, v, *, causal: bool, shd: Optional[Sharder],
           scale: Optional[float]):
    specs = _fused_specs(q, k, shd)
    if specs is None:
        return fa.flash_attention(q, k, v, causal=causal, scale=scale)
    from jax import shard_map
    q_spec, kv_spec = specs
    return shard_map(partial(fa.flash_attention, causal=causal, scale=scale),
                     mesh=shd.mesh,
                     in_specs=(q_spec, kv_spec, kv_spec), out_specs=q_spec,
                     check_vma=False)(q, k, v)


def _fused_fits(q, k, *, causal: bool, q_offset: int,
                shd: Optional[Sharder]) -> bool:
    """Whether the default impl takes the fused kernel: TPU backend, causal
    with no offset, a shape the kernel tiles, a mesh it can be split over."""
    if jax.default_backend() != "tpu" or not causal or q_offset:
        return False
    if q.shape[1] != k.shape[1] or fa.block_sizes(q.shape[1], q.shape[3]) is None:
        return False
    try:
        _fused_specs(q, k, shd)
    except ValueError:
        return False
    return True


def attention(q, k, v, *, impl: str = "flash", causal: bool = True,
              q_block: int = 256, q_offset: int = 0,
              shd: Optional[Sharder] = None,
              scale: Optional[float] = None) -> jnp.ndarray:
    """Dispatch over implementations; kv is (B, S, K, hd) with K | H; scores
    scaled by ``scale`` (default 1/sqrt(hd)).

    The fused kernel takes the K kv heads as they are; the scan and naive
    paths repeat them to H.  Each call counts its path in
    ``telemetry.ATTENTION_PATHS``.

    Sharding: heads over `model` when the head count divides the TP axis
    (Megatron-style); otherwise context-parallel query sharding inside the
    blockwise scan (see blockwise_attention)."""
    if impl == "pallas" or (impl == "flash" and _fused_fits(
            q, k, causal=causal, q_offset=q_offset, shd=shd)):
        if q_offset:
            raise ValueError("the fused kernel takes no query offset")
        telemetry.ATTENTION_PATHS["fused"] += 1
        return _fused(q, k, v, causal=causal, shd=shd, scale=scale)
    h = q.shape[2]
    kh = k.shape[2]
    k = _repeat_kv(k, h // kh)
    v = _repeat_kv(v, h // kh)
    tp = shd.axis_size("tp") if shd is not None else 1
    head_sharded = tp > 1 and h % tp == 0
    context_parallel = tp > 1 and not head_sharded
    if shd is not None and head_sharded:
        q = shd.constrain(q, "batch", None, "tp", None)
        k = shd.constrain(k, "batch", None, "tp", None)
        v = shd.constrain(v, "batch", None, "tp", None)
    elif shd is not None and context_parallel:
        # KV-sequence sharding: softmax stats and the output block are psum-merged
        # (tiny + one (B,qb,H,hd) block per layer); dk/dv gradients stay local —
        # unlike query sharding, whose backward all-reduces dk/dv per block.
        k = shd.constrain(k, "batch", "seq", None, None)
        v = shd.constrain(v, "batch", "seq", None, None)
    if impl == "naive":
        telemetry.ATTENTION_PATHS["naive"] += 1
        return naive_attention(q, k, v, causal=causal, q_offset=q_offset, shd=shd,
                               scale=scale)
    telemetry.ATTENTION_PATHS["scan"] += 1
    return blockwise_attention(q, k, v, causal=causal, q_block=q_block,
                               q_offset=q_offset, shd=shd,
                               context_parallel=False, scale=scale)


def layer_scan(step, x, stack, cache, first: int, last: int,
               remat: bool = False):
    """Layers first..last-1 as one ``lax.scan`` over their indices.

    ``step(x, l, lp, c) -> (x, new c)`` gets layer l's slice ``lp`` of the
    stacked parameters ``stack`` and its slice ``c`` of the stacked
    ``cache`` (arrays (L, ...); None without a cache).  The cache rides in
    the carry and each layer writes its new slice back in place, so a cache
    the caller donates is updated with no copy (read as the scan's ``xs`` and
    returned as its stacked ``ys``, it would be a new array that XLA copies
    into the donated buffer).  Returns (x, cache)."""

    def body(carry, l):
        x, c = carry
        x, new = step(x, l, jax.tree.map(lambda a: a[l], stack),
                      None if c is None else jax.tree.map(lambda a: a[l], c))
        if c is not None:
            c = jax.tree.map(lambda a, n: jax.lax.dynamic_update_index_in_dim(
                a, n.astype(a.dtype), l, 0), c, new)
        return (x, c), None

    if remat:
        body = jax.checkpoint(body)
    (x, cache), _ = jax.lax.scan(body, (x, cache), jnp.arange(first, last))
    return x, cache


def mlp(x: jnp.ndarray, params: dict, kind: str = "swiglu",
        shd: Optional[Sharder] = None,
        adapter: Optional[dict] = None) -> jnp.ndarray:
    """swiglu: silu(x@w1) * (x@w3) @ w2;  geglu: the same with the exact
    (erf) GELU;  gelu: gelu(x@w1) @ w2 (tanh approximation).

    ``adapter`` {"a": (d, r), "b1": (r, f), "b3": (r, f)} adds a low-rank
    term to a gated kind's input projections: x@w1 + (x@a)@b1 and
    x@w3 + (x@a)@b3, under the ``adapter`` scope."""
    h = jnp.einsum("...d,df->...f", x, params["w1"])
    if kind in ("swiglu", "geglu"):
        g = jnp.einsum("...d,df->...f", x, params["w3"])
        if adapter is not None:
            with telemetry.scope("adapter"):
                u = jnp.einsum("...d,dr->...r", x, adapter["a"])
                h = h + jnp.einsum("...r,rf->...f", u, adapter["b1"])
                g = g + jnp.einsum("...r,rf->...f", u, adapter["b3"])
        act = jax.nn.silu if kind == "swiglu" else partial(jax.nn.gelu, approximate=False)
        h = act(h) * g
    else:
        h = jax.nn.gelu(h)
    if shd is not None:
        h = shd.constrain(h, *(("batch",) + (None,) * (h.ndim - 2) + ("tp",)))
    return jnp.einsum("...f,fd->...d", h, params["w2"])
