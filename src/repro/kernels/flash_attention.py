"""Causal flash attention with a backward pass: the TPU splash-attention
kernels of the installed JAX (``jax.experimental.pallas.ops.tpu``), in their
MQA form.

One kernel call covers one KV head: its G = H / K query heads are the
kernel's head axis, and the call is ``vmap``ped over batch and KV heads, so
keys and values enter as (B, K, S, hd) and are never repeated to H heads.
The forward keeps float32 running max and normaliser in VMEM and skips every
key block above the diagonal (the causal mask's block info); the backward
recomputes the probabilities from the saved log-sum-exp, block by block.
Matmul operands stay in the input dtype with float32 accumulation, except
that the forward's P·V takes P and V in float32.

The kernel applies no scale: the softmax scale (1/sqrt(hd) unless given)
is folded into q, exactly for a power of two (hd 64, 256), else in float32
and rounded once to q's dtype (Zamba2's (224 / 2) ** -0.5 costs that one
rounding).  A head dim that is not a multiple of the 128 lanes (Zamba2's
224) goes to the kernel as it is: at B 4, S 3584, 32 heads of 224 on a v5e
its forward took 12.2 ms against 15.8 ms with q, k and v zero-padded to 256
(PERF.md).  Validated against ``ref.attention_ref`` in interpret mode on
the CPU; compiled by Mosaic for a described v5e in tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as splash

from .interpret import interpret_mode

#: the kernel's tile widths, largest first: a block is a multiple of 128
#: (the TPU lane width) that divides the sequence.  The largest that divides
#: it serves forward and backward alike: at S=4096, hd 64 on a v5e, 1024 beat
#: 512 in both (PERF.md, section 5)
BLOCKS = (1024, 512, 256, 128)
#: the TPU's lane width
LANES = 128


def block_sizes(seq: int, head_dim: int) -> Optional[splash.BlockSizes]:
    """The forward and backward tiles for a (seq, head_dim) problem, or None
    where the kernel cannot take it: seq not a multiple of 128, head_dim
    neither a multiple of 64 nor one of 32 from a lane's width up (Zamba2's
    224)."""
    if head_dim % 64 and (head_dim % 32 or head_dim < LANES):
        return None
    blk = next((b for b in BLOCKS if seq % b == 0), None)
    if blk is None:
        return None
    return splash.BlockSizes(
        block_q=blk, block_kv=blk, block_kv_compute=blk,
        block_q_dkv=blk, block_kv_dkv=blk, block_kv_dkv_compute=blk,
        use_fused_bwd_kernel=True)


@functools.lru_cache(maxsize=None)
def _kernel(seq: int, groups: int, blocks: splash.BlockSizes, causal: bool,
            interpret: bool):
    """The MQA kernel for ``groups`` query heads over one (seq, hd) key and
    value; the mask's block info is numpy work, done once per shape, and
    held as concrete arrays whatever trace first asks for it."""
    one = splash.CausalMask((seq, seq)) if causal else splash.FullMask((seq, seq))
    with jax.ensure_compile_time_eval():
        return splash.make_splash_mqa_single_device(
            splash.MultiHeadMask([one] * groups), block_sizes=blocks,
            interpret=interpret)


@functools.partial(jax.jit, static_argnames=("causal", "blocks", "interpret",
                                             "scale"))
def flash_attention(q, k, v, *, causal: bool = True,
                    blocks: Optional[splash.BlockSizes] = None,
                    interpret: Optional[bool] = None,
                    scale: Optional[float] = None) -> jnp.ndarray:
    """q: (B, S, H, hd); k, v: (B, S, K, hd) with K | H.  Returns (B, S, H, hd).

    ``blocks`` defaults to ``block_sizes(S, hd)``; ``interpret`` resolves
    through ``interpret_mode`` (compiled on a TPU, interpreted elsewhere);
    ``scale`` multiplies the scores (default 1/sqrt(hd))."""
    b, s, h, hd = q.shape
    kh = k.shape[2]
    g = h // kh
    blocks = blocks or block_sizes(s, hd)
    if blocks is None or h % kh or k.shape[1] != s:
        raise ValueError(f"flash_attention cannot take q {q.shape}, k {k.shape}")
    scale = 1.0 / math.sqrt(hd) if scale is None else scale
    if scale == 2.0 ** round(math.log2(scale)):     # exact in any float dtype
        q = q * jnp.asarray(scale, q.dtype)
    else:
        q = (q.astype(jnp.float32) * scale).astype(q.dtype)
    kernel = _kernel(s, g, blocks, causal, interpret_mode(interpret))
    qg = q.reshape(b, s, kh, g, hd).transpose(0, 2, 3, 1, 4)   # (B, K, G, S, hd)
    kt, vt = (t.transpose(0, 2, 1, 3) for t in (k, v))         # (B, K, S, hd)
    out = jax.vmap(jax.vmap(kernel))(qg, kt, vt)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s, h, hd)
