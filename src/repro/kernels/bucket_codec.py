"""Bucket wire codec: gradient pack/unpack + wire quantization over a static
address table.

The explicit-DP hot path used to materialize the gradient wire format with
O(leaves x buckets) HLO: one `concatenate` per bucket (each slicing spans out
of every overlapping leaf), a `stack` over buckets, and one `concatenate` per
leaf on the way back (`overlap.pack_buckets` / `unpack_buckets`).  The paper's
bottom line (Obs. 1/4/5) is that exactly this kind of software overhead — not
the interconnect — is what leaves bandwidth untapped.

This module replaces that path with a *codec*: a static address table computed
once per tree structure from `overlap.make_buckets`, plus

  * **pack** — gathers every gradient leaf into the stacked
    `(n_buckets, bucket_elems)` carrier and quantizes it to the wire dtype
    (fp32 / bf16 / int8 + per-bucket scales).  For int8 the error-feedback
    state (a carrier-shaped fp32 buffer) is added before quantization and the
    new error is returned alongside, so compression composes with the overlap
    scan schedule instead of excluding it.
  * **unpack** — dequantizes the reduced carrier and scatters it back into
    per-leaf fp32 arrays.

Both lower to XLA with O(1) `concatenate` ops regardless of leaf count (zero,
in fact): the address table makes every leaf a single contiguous carrier
range, so pack is one `dynamic_update_slice` per leaf into a flat buffer and
unpack is one slice per leaf.  There is no Pallas pack/unpack: a TPU DMA slice
must be aligned to the HBM tiling (1024 elements for a flat array), and the
spans start wherever the previous leaf ended (a 576-element norm scale puts
every later span off that grid), so a kernel would need in-VMEM lane rotation
and masked stores that nothing has measured to beat XLA's own copies.

  * **adamw_update_shard** — the ZeRO shard update, elementwise, with a
    Pallas kernel (``impl="pallas"``) tiled `(n_buckets, lanes)` over the
    shard columns, and an XLA implementation.  ``impl="auto"`` is XLA on every
    backend until a chip measurement shows the kernel faster.

Numerics: fp32 pack/unpack is exact (validated element-for-element against
`pack_buckets`/`unpack_buckets`); bf16 is a cast on the wire; int8 uses
symmetric per-bucket scales with error feedback (`new_err = packed -
dequant(q)`), the same scheme the per-tensor PR 4 wire used — now per bucket,
so bucketing no longer excludes compression.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.overlap import Bucket, make_buckets
from .interpret import interpret_mode

# wire name -> jnp dtype on the wire (byte/sideband accounting lives in
# core.wire.WIRE_FORMATS — the single source of truth the cost model shares)
WIRE_DTYPES = {
    "fp32": jnp.float32,
    "bf16": jnp.bfloat16,
    "int8": jnp.int8,
}


@dataclasses.dataclass(frozen=True)
class CodecTable:
    """Static address table of the fused codec, one per tree structure.

    `spans[k]` lists bucket k's copies as (leaf, src_lo, src_hi, dst_lo):
    carrier row k positions [dst_lo, dst_lo + (src_hi - src_lo)) hold leaf
    elements [src_lo, src_hi).  Because `make_buckets` walks leaves in a fixed
    order and splits them only at bucket boundaries, every leaf also occupies
    one *contiguous* range of the flattened carrier starting at
    `leaf_offsets[i]` — which is what lets pack write with a single
    `dynamic_update_slice` per leaf and unpack with a single slice per leaf.
    Zero-size leaves own no span and `leaf_offsets[i]` is -1.
    """

    sizes: Tuple[int, ...]
    bucket_elems: int
    reverse: bool
    spans: Tuple[Tuple[Tuple[int, int, int, int], ...], ...]
    leaf_offsets: Tuple[int, ...]

    @property
    def n_buckets(self) -> int:
        return len(self.spans)

    @property
    def total_elems(self) -> int:
        return sum(self.sizes)

    @property
    def carrier_elems(self) -> int:
        return self.n_buckets * self.bucket_elems

    def buckets(self) -> List[Bucket]:
        """The `overlap.Bucket` view of the table (for schedule arithmetic)."""
        return [Bucket(tuple((i, lo, hi) for i, lo, hi, _ in row),
                       self.bucket_elems) for row in self.spans]


def make_table(sizes: Sequence[int], bucket_elems: int,
               reverse: bool = True) -> CodecTable:
    """Build the address table from the overlap engine's bucket assignment —
    the codec and `core.overlap` share one boundary algorithm by construction."""
    sizes = tuple(int(s) for s in sizes)
    buckets = make_buckets(sizes, bucket_elems, reverse=reverse)
    cap = buckets[0].elems if buckets else max(int(bucket_elems), 1)
    offsets = [-1] * len(sizes)
    spans: List[Tuple[Tuple[int, int, int, int], ...]] = []
    for k, b in enumerate(buckets):
        dst = 0
        row = []
        for i, lo, hi in b.spans:
            row.append((i, lo, hi, dst))
            if lo == 0:
                offsets[i] = k * cap + dst
            dst += hi - lo
        spans.append(tuple(row))
    return CodecTable(sizes, cap, reverse, tuple(spans), tuple(offsets))


def _resolve_impl(impl: str) -> str:
    # "auto" is XLA on every backend: nothing picks the kernel by asking which
    # backend it is on, and no chip run has measured it against XLA yet
    if impl == "auto":
        return "xla"
    if impl not in ("pallas", "xla"):
        raise ValueError(f"impl must be auto/pallas/xla, got {impl!r}")
    return impl


def _quantize_rows(carrier: jnp.ndarray):
    """Symmetric per-bucket int8 quantization of a (n_buckets, cap) fp32
    carrier -> (q int8, scales fp32 (n_buckets,), new_err fp32)."""
    s = jnp.maximum(jnp.max(jnp.abs(carrier), axis=1), 1e-12) / 127.0
    q = jnp.clip(jnp.round(carrier / s[:, None]), -127, 127).astype(jnp.int8)
    new_err = carrier - q.astype(jnp.float32) * s[:, None]
    return q, s, new_err


# ------------------------------------------------- fused sharded AdamW update
#: VMEM budget of one (n_buckets, lanes) fp32 block of the shard update; the
#: kernel double-buffers 4 inputs and 3 outputs, ~4 MiB in all, well inside
#: the 16 MiB a TPU v5e kernel may use by default
_ADAMW_BLOCK_BYTES = 512 << 10


def _adamw_lanes(nb: int, sh: int) -> int:
    """Lane width of the shard update's `(nb, lanes)` blocks: the widest
    multiple of 128 that divides `sh` within `_ADAMW_BLOCK_BYTES`, or the
    whole row where `sh` is not a multiple of 128 (small shards only)."""
    if sh % 128:
        return sh
    rows = -(-nb // 8) * 8  # VMEM pads the sublane dim to 8
    lanes = 128
    while sh % (2 * lanes) == 0 and rows * 2 * lanes * 4 <= _ADAMW_BLOCK_BYTES:
        lanes *= 2
    return lanes


def _adamw_shard_xla(g, p, m, v, clip, lr, bc1, bc2, b1, b2, eps, wd):
    g = g * clip
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    mhat = m / bc1
    vhat = v / bc2
    delta = mhat / (jnp.sqrt(vhat) + eps) + wd * p
    return p - lr * delta, m, v


def _adamw_shard_kernel(sc_ref, g_ref, p_ref, m_ref, v_ref, p_out, m_out,
                        v_out, *, b1, b2, eps, wd):
    clip, lr, bc1, bc2 = sc_ref[0], sc_ref[1], sc_ref[2], sc_ref[3]
    g = g_ref[...] * clip
    m = b1 * m_ref[...] + (1 - b1) * g
    v = b2 * v_ref[...] + (1 - b2) * g * g
    delta = (m / bc1) / (jnp.sqrt(v / bc2) + eps) + wd * p_ref[...]
    p_out[...] = (p_ref[...] - lr * delta).astype(p_out.dtype)
    m_out[...] = m
    v_out[...] = v


def _adamw_shard_pallas(g, p, m, v, scalars, b1, b2, eps, wd, p_dtype,
                        interpret=None):
    """The shard update tiled `(nb, lanes)` over the shard columns: every
    block spans all bucket rows, so the row dim needs no 8-alignment, and the
    lane dim is a multiple of 128 (or the whole row)."""
    nb, sh = g.shape
    lanes = _adamw_lanes(nb, sh)
    block = pl.BlockSpec((nb, lanes), lambda j: (0, j))
    f32 = jax.ShapeDtypeStruct((nb, sh), jnp.float32)
    kernel = functools.partial(_adamw_shard_kernel, b1=b1, b2=b2, eps=eps,
                               wd=wd)
    return pl.pallas_call(
        kernel,
        grid=(sh // lanes,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] + [block] * 4,
        out_specs=[block] * 3,
        out_shape=[jax.ShapeDtypeStruct((nb, sh), p_dtype), f32, f32],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret_mode(interpret),
    )(scalars, g, p, m, v)


def adamw_update_shard(g: jnp.ndarray, p: jnp.ndarray, m: jnp.ndarray,
                       v: jnp.ndarray, *, clip, lr, bc1, bc2,
                       b1: float, b2: float, eps: float, weight_decay: float,
                       wire: str = "fp32", impl: str = "auto"):
    """Fused sharded AdamW: one device's `(n_buckets, shard_elems)` carrier
    shards of (reduced gradient, param, m, v) -> (p_wire, p_scales, new_m,
    new_v) — the ZeRO update between the reduce-scatter and the all-gather.

    Elementwise math is *identical* to `optim.adamw.apply_updates` (same op
    order, so fp32 results are bit-for-bit): `clip` is the global-norm clip
    factor (already psum-combined across shards by the caller), `lr` the
    scheduled rate, `bc1`/`bc2` the bias corrections — all traced scalars;
    `b1`/`b2`/`eps`/`weight_decay` are static.  Zero-padded carrier columns
    are stable: g = p = m = v = 0 gives delta = 0, so pads stay zero through
    any number of steps.

    `wire` is the all-gather leg's format: fp32/bf16 cast `p_new` (scales is
    None); int8 requantizes per bucket-shard with symmetric scales — the
    sideband the gather moves is one fp32 scale per (bucket, device) shard.
    The per-row scale needs the whole row, so the kernel (tiled over columns)
    hands back fp32 and the requantization is the pack's `_quantize_rows`.
    Moments always stay fp32 and carrier-sharded.
    """
    if wire not in WIRE_DTYPES:
        raise ValueError(f"unknown wire format {wire!r}; "
                         f"one of {sorted(WIRE_DTYPES)}")
    p_dtype = jnp.float32 if wire == "int8" else WIRE_DTYPES[wire]
    if _resolve_impl(impl) == "pallas":
        scalars = jnp.stack([jnp.asarray(x, jnp.float32)
                             for x in (clip, lr, bc1, bc2)])
        p_new, m, v = _adamw_shard_pallas(g, p, m, v, scalars, b1, b2, eps,
                                          weight_decay, p_dtype)
    else:
        p_new, m, v = _adamw_shard_xla(g, p, m, v, clip, lr, bc1, bc2, b1, b2,
                                       eps, weight_decay)
        p_new = p_new.astype(p_dtype)
    if wire == "int8":
        q, s, _ = _quantize_rows(p_new)
        return q, s, m, v
    return p_new, None, m, v


# ------------------------------------------------------------------- public
def pack(table: CodecTable, flat_g: Sequence[jnp.ndarray], *,
         scale: float = 1.0, wire: str = "fp32",
         err: Optional[jnp.ndarray] = None):
    """Gather + wire-quantize: leaves -> (carrier, scales, new_err).

    `carrier` is `(n_buckets, bucket_elems)` in the wire dtype; the final
    partial bucket is zero-padded (zeros are the reduction identity).  `scale`
    multiplies every element (the 1/n pre-division of a mean-reduce).

    For ``wire="int8"``, `scales` holds the per-bucket symmetric quantization
    scales; `err` — a carrier-shaped fp32 error-feedback buffer — is added
    *after* scaling and before quantization, and `new_err` is the residual
    `packed - dequant(q)`.  For fp32/bf16 wires `scales` is None and `err`
    passes through untouched.
    """
    if wire not in WIRE_DTYPES:
        raise ValueError(f"unknown wire format {wire!r}; "
                         f"one of {sorted(WIRE_DTYPES)}")
    if table.n_buckets == 0:
        raise ValueError("cannot pack an empty table (no gradient elements)")
    flat = jnp.zeros((table.carrier_elems,), jnp.float32)
    for i, size in enumerate(table.sizes):
        if size == 0:
            continue
        leaf = flat_g[i].reshape(-1).astype(jnp.float32)
        flat = lax.dynamic_update_slice(flat, leaf, (table.leaf_offsets[i],))
    carrier = (flat * scale).reshape(table.n_buckets, table.bucket_elems)
    if wire == "int8":
        if err is not None:
            carrier = carrier + err
        return _quantize_rows(carrier)
    return carrier.astype(WIRE_DTYPES[wire]), None, err


def unpack(table: CodecTable, carrier: jnp.ndarray,
           like: Sequence[jnp.ndarray],
           scales: Optional[jnp.ndarray] = None) -> List[jnp.ndarray]:
    """Dequantize + scatter: reduced carrier -> per-leaf fp32 arrays shaped
    like `like` (inverse of `pack` up to the wire dtype's rounding).
    Zero-size leaves come back as fp32 zeros.  `carrier` may also be a list of
    1-D rows (the eager reduction path); it is stacked once here."""
    if not isinstance(carrier, jnp.ndarray):
        carrier = jnp.stack(list(carrier))
    flat = carrier.astype(jnp.float32)
    if scales is not None:
        flat = flat * scales[:, None]
    flat = flat.reshape(-1)
    out = []
    for i, g in enumerate(like):
        if table.sizes[i] == 0:
            out.append(jnp.zeros(g.shape, jnp.float32))
            continue
        piece = lax.dynamic_slice(flat, (table.leaf_offsets[i],),
                                  (table.sizes[i],))
        out.append(piece.reshape(g.shape))
    return out


def wire_bytes(table: CodecTable, wire: str) -> int:
    """Bytes the carrier occupies on the wire (payload + int8 scale sideband).
    Delegates to `core.wire.bytes_on_wire` — one source of truth for the
    per-format accounting shared with the cost model."""
    from ..core.wire import bytes_on_wire

    return int(bytes_on_wire(table.carrier_elems * 4, wire, table.n_buckets))
