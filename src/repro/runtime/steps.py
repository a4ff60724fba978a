"""Step builders: jit-ready train/prefill/decode steps with shardings.

Two trainer mechanisms, mirroring the paper's software-layer axis (DESIGN.md §2):
  * `build_train_step`      — XLA SPMD chooses every collective (the *CCL analog);
  * `build_explicit_dp_step`— pure data parallelism under shard_map with *our*
    collective algorithms from core/ (the GPU-aware-MPI analog), with optional
    int8 gradient compression (error feedback) on the wire.

`build_train_step` supports gradient accumulation (microbatching): the batch is
split on the leading axis and grads are accumulated in fp32 by a lax.scan —
bounding activation memory and letting XLA overlap the per-microbatch
reduce-scatters with the next microbatch's backward (compute/comm overlap).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, ShapeConfig
from ..core import program as prg
from ..core.autotune import CollectivePolicy
from ..models.model import Model
from ..models.sharding import Sharder, tree_shardings, tree_shardings_shaped
from ..optim import adamw
from .. import telemetry


@dataclasses.dataclass
class StepBundle:
    """A jit-able step function plus its sharding pytrees."""
    fn: Callable
    in_shardings: Any
    out_shardings: Any
    donate_argnums: Tuple[int, ...] = ()


def _microbatch(batch, n: int):
    def split(a):
        if a.shape[0] % n:
            raise ValueError(
                f"batch leading axis {a.shape[0]} is not divisible by "
                f"microbatches={n}; choose a microbatch count that divides "
                f"the (per-shard) batch size")
        return a.reshape((n, a.shape[0] // n) + a.shape[1:])

    return jax.tree.map(split, batch)


def build_train_step(model: Model, opt: adamw.OptConfig,
                     microbatches: int = 1) -> Callable:
    """(params, opt_state, batch) -> (params, opt_state, metrics)."""

    def train_step(params, opt_state, batch):
        if microbatches > 1:
            mb = _microbatch(batch, microbatches)

            def acc_body(carry, b):
                gsum, lsum = carry
                loss, grads = jax.value_and_grad(model.loss)(params, b)
                with telemetry.scope("grad_accum"):
                    gsum = jax.tree.map(lambda a, g: a + g.astype(jnp.float32),
                                        gsum, grads)
                    return (gsum, lsum + loss), None

            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (gsum, lsum), _ = jax.lax.scan(acc_body, (zeros, 0.0), mb)
            with telemetry.scope("grad_accum"):
                grads = jax.tree.map(lambda g: g / microbatches, gsum)
                loss = lsum / microbatches
        else:
            loss, grads = jax.value_and_grad(model.loss)(params, batch)
        params, opt_state, metrics = adamw.apply_updates(params, grads, opt_state, opt)
        metrics["loss"] = loss
        return params, opt_state, metrics

    return train_step


def train_step_bundle(model: Model, shape: ShapeConfig, opt: adamw.OptConfig,
                      microbatches: int = 1) -> StepBundle:
    shd = model.shd
    p_log = model.param_logical()
    p_sh = tree_shardings_shaped(shd, p_log, model.abstract_params())
    o_log = adamw.opt_state_logical(p_log)
    o_abs = adamw.abstract_opt_state(model.abstract_params())
    o_sh = tree_shardings_shaped(shd, o_log, o_abs)
    b_sh = tree_shardings_shaped(shd, model.batch_logical(shape), model.input_specs(shape))
    none_sh = shd.sharding((), ()) if shd.mesh is not None else None
    m_sh = {"grad_norm": none_sh, "lr": none_sh, "loss": none_sh} if shd.mesh is not None else None
    fn = build_train_step(model, opt, microbatches)
    return StepBundle(fn, (p_sh, o_sh, b_sh), (p_sh, o_sh, m_sh), donate_argnums=(0, 1))


def _logits_sharding(model: Model, shape: ShapeConfig):
    """Last-position logits sharding with vocab-divisibility checked against the
    actual shape (mamba2's 50280 / internvl2's 92553 don't divide 16)."""
    shd = model.shd
    if shd.mesh is None:
        return None
    c = model.cfg
    if c.n_codebooks:
        dims = ("batch", None, None, "tp")
        lshape = (shape.global_batch, 1, c.n_codebooks, c.vocab)
    else:
        dims = ("batch", None, "tp")
        lshape = (shape.global_batch, 1, c.vocab)
    return shd.sharding(dims, lshape)


def decode_step_bundle(model: Model, shape: ShapeConfig) -> StepBundle:
    shd = model.shd
    p_sh = tree_shardings_shaped(shd, model.param_logical(), model.abstract_params())
    c_sh = tree_shardings_shaped(shd, model.cache_logical(shape), model.abstract_cache(shape))
    b_log = model.batch_logical(shape)
    b_abs = model.input_specs(shape)
    tok_sh = tree_shardings_shaped(shd, {"tokens": b_log["tokens"]}, {"tokens": b_abs["tokens"]})["tokens"] \
        if shd.mesh is not None else None
    pos_sh = shd.sharding((), ()) if shd.mesh is not None else None
    logits_sh = _logits_sharding(model, shape)

    def decode_step(params, cache, tokens, pos):
        return model.decode(params, cache, tokens, pos)

    return StepBundle(decode_step, (p_sh, c_sh, tok_sh, pos_sh), (logits_sh, c_sh),
                      donate_argnums=(1,))


def prefill_step_bundle(model: Model, shape: ShapeConfig) -> StepBundle:
    shd = model.shd
    p_sh = tree_shardings_shaped(shd, model.param_logical(), model.abstract_params())
    c_sh = tree_shardings_shaped(shd, model.cache_logical(shape), model.abstract_cache(shape))
    b_sh = tree_shardings_shaped(shd, model.batch_logical(shape), model.input_specs(shape))
    logits_sh = _logits_sharding(model, shape)

    def prefill_step(params, batch, cache):
        return model.prefill(params, batch, cache)

    return StepBundle(prefill_step, (p_sh, b_sh, c_sh), (logits_sh, c_sh),
                      donate_argnums=(2,))


# --------------------------------------------------------------- explicit DP
def build_explicit_dp_step(model: Model, opt: adamw.OptConfig, mesh, axis: str = "data",
                           policy: Optional[CollectivePolicy] = None,
                           compress_bits: int = 0,
                           bucket_bytes: Optional[int] = None,
                           dcn_axis: Optional[str] = None,
                           overlap: bool = False,
                           microbatches: int = 1,
                           chunks: Optional[int] = None,
                           zero: bool = False,
                           step_program: Optional[prg.StepProgram] = None) \
        -> Callable:
    """Pure-DP train step under shard_map with explicit gradient collectives.

    Params/opt state replicated; batch sharded on `axis` (and `dcn_axis` when
    given).  Gradients are reduced with the CommPlan/CollectivePolicy algorithm
    choice (paper Obs. 1/4 applied), with optional int8 error-feedback
    compression on the wire (4x fewer DP bytes).

    Bucketing (the paper's message-aggregation optimization): the flat gradient
    list is packed by the fused wire codec (`kernels.bucket_codec`) into fixed
    `bucket_bytes` rows before reduction, so small tensors stop paying
    per-message latency — one fused pack and one fused unpack per step, O(1)
    concatenate ops regardless of leaf count (the old path emitted one
    concatenate per bucket and per leaf).  The default bucket size comes from
    the plan's latency/bandwidth crossover; pass `bucket_bytes=0` to reduce
    per-tensor.  `dcn_axis` on a two-pod mesh routes every bucket through the
    hierarchical intra-RS / inter-AR / intra-AG schedule (selected whenever
    the plan was built from a two-level topology).

    Compression (`compress_bits=8`) now *composes* with bucketing and overlap:
    the codec quantizes to the int8 wire inside the pack kernel with
    per-bucket scales, and the error-feedback state is a carrier-shaped
    `(n_buckets, bucket_elems)` fp32 buffer carried per bucket through the
    scan schedule (donated through the jit, so steady-state steps reuse the
    buffer).  Reduction of a quantized bucket all-gathers the int8 payload +
    scales and sums after dequant (`overlap.quantized_all_reduce`); on a
    two-level mesh the inter leg stays fp32 (requantizing partial sums would
    add error outside the error-feedback loop) and `chunks > 1` pipelines the
    intra gather of chunk t against the inter psum of chunk t-1.  Without
    bucketing/overlap, `compress_bits=8` keeps the legacy per-tensor wire
    (per-tensor scales).

    Overlap (`overlap=True`, paper Sec. VI / Obs. 1): buckets are built in
    *reverse layer order* (the order backward materializes gradients) and
    reduced through `core.overlap`'s scan-carried issue schedule — one bucket
    in flight at a time instead of one post-hoc blob.  With `microbatches > 1`
    the scan carries the previous microbatch's unreduced buckets, so each
    bucket's all-reduce is issued *inside the same scan step* as the next
    microbatch's backward and overlaps it.  With `dcn_axis`, each bucket runs
    the chunked double-buffered hierarchical pipeline; `chunks=None` takes the
    pipeline depth from the plan's per-tier alpha-beta fits
    (`plan.pipeline_chunks`).

    ZeRO (`zero=True`): the bucket schedule switches from all-reduce +
    replicated AdamW to the three-phase **reduce-scatter of the packed carrier
    -> sharded AdamW over each device's carrier shard -> all-gather of updated
    params** — the reduce leg moves each gradient byte once per shard instead
    of twice, and the fp32 moments live carrier-sharded (optimizer memory
    divided by the DP degree; the returned step exposes
    `step.init_opt_state(params)` / `step.abstract_opt_state(params)` for the
    sharded state).  Global-norm clipping stays exact: the per-shard sum of
    squares is psum-combined over the dp axes before the clip factor forms —
    which also makes all-RS-before-any-update a semantic barrier, so the
    overlap the schedule can legally express is the RS stream against the
    backward (scan-carried, microbatch-pipelined) and the chunked two-tier
    interleave inside each leg, not AG(k) against RS(k+1).  The update itself
    is the fused dequant+AdamW+requantize shard kernel
    (`bucket_codec.adamw_update_shard`); with `compress_bits=8` the AG leg
    carries int8 + one scale per bucket-shard and every device (including the
    shard owner) uses the dequantized values, keeping params bit-identically
    replicated.  The codec is the single gradient *and* parameter
    materialization point; `err` passes through untouched (no error feedback
    on the param leg — the same payload rides every tier, so the only error
    is the single quantization step).

    The returned step exposes `step.init_error_state(params)` — carrier-shaped
    zeros when compression rides buckets, per-leaf zeros otherwise.
    """
    from jax.sharding import PartitionSpec as P
    from ..core import overlap as ov
    from ..kernels import bucket_codec as codec

    policy = policy or CollectivePolicy.from_model()
    n = mesh.shape[axis]
    n_total = n * (mesh.shape[dcn_axis] if dcn_axis is not None else 1)
    if compress_bits not in (0, 8):
        raise ValueError(f"compress_bits must be 0 or 8, got {compress_bits}")
    if microbatches > 1 and not overlap:
        raise ValueError("explicit-DP microbatching is implemented by the "
                         "overlap schedule; pass overlap=True")
    if overlap and bucket_bytes == 0:
        # the overlap scan needs equal-size packed buckets — refuse the
        # documented per-tensor mode instead of silently re-bucketing
        raise ValueError("overlap=True requires bucketing; per-tensor "
                         "reduction (bucket_bytes=0) is not supported — omit "
                         "bucket_bytes to use the plan's crossover")
    if zero and bucket_bytes == 0:
        raise ValueError("zero=True shards the packed carrier; per-tensor "
                         "reduction (bucket_bytes=0) is not supported — omit "
                         "bucket_bytes to use the plan's crossover")
    # normalize through the StepProgram IR: the program (given directly or
    # built from the legacy flag combination) is the single description of
    # this step — the knobs below are *lowered* from it, and the same object
    # is what the cost model prices (exposed_comm_time(program=)) and the
    # plan persists.  The boolean kwargs are retained as a shim.
    if step_program is None:
        step_program = prg.train_step_program(
            overlap=overlap, zero=zero, compress_bits=compress_bits,
            chunks=chunks, microbatches=microbatches,
            bucket_bytes=bucket_bytes)
    kw = step_program.validate().step_kwargs()
    overlap, zero = kw["overlap"], kw["zero"]
    compress_bits, chunks = kw["compress_bits"], kw["chunks"]
    microbatches, bucket_bytes = kw["microbatches"], kw["bucket_bytes"]
    if bucket_bytes is None:
        # plain compress_bits (no overlap, no explicit bucket size) keeps the
        # legacy per-tensor wire; bucketed compression opts in via
        # bucket_bytes/overlap (zero is always bucketed: the carrier is the
        # thing being sharded)
        bucket_bytes = 0 if (compress_bits and not overlap and not zero) \
            else getattr(policy, "bucket_bytes", 0)
    if (overlap or zero) and not bucket_bytes:
        bucket_bytes = 4 << 20  # policy carried no crossover (legacy tables)
    bucketed = bucket_bytes > 0
    loss_axes = (dcn_axis, axis) if dcn_axis is not None else axis
    plan_hier = bool(getattr(policy, "hierarchical", False))
    if chunks is None:
        chunks_fn = getattr(policy, "pipeline_chunks", None)
        chunks = chunks_fn(bucket_bytes) if (chunks_fn is not None and
                                             dcn_axis is not None) else 1
    chunks = max(int(chunks), 1)
    bucket_elems = max(bucket_bytes // 4, 1)

    # ----------------------------------------------------------------- zero
    # carrier geometry of the three-phase schedule: rows are column-padded so
    # every bucket splits evenly into n_chunks chunks of n_ici * n_dcn shard
    # blocks (zeros are the reduction identity AND an AdamW fixed point, so
    # the pad stays zero forever).  The device at (axis=i, dcn=j) owns block
    # i * n_dcn + j of each chunk; its shard is the concatenation of its
    # per-chunk blocks (shard-major layout, mirrored exactly by the AG).
    n_dcn = mesh.shape[dcn_axis] if dcn_axis is not None else 1
    zero_chunks = chunks if dcn_axis is not None else 1
    shard_unit = zero_chunks * n * n_dcn
    shard_axes = (axis,) if dcn_axis is None else (axis, dcn_axis)
    zero_wire = "int8" if compress_bits == 8 else "fp32"

    def zero_geometry(sizes):
        table = codec.make_table(sizes, bucket_elems, reverse=bool(overlap))
        padded = -(-table.bucket_elems // shard_unit) * shard_unit
        return table, padded

    def pad_cols(carrier, padded):
        if padded > carrier.shape[1]:
            carrier = jnp.concatenate(
                [carrier, jnp.zeros((carrier.shape[0],
                                     padded - carrier.shape[1]),
                                    carrier.dtype)], axis=1)
        return carrier

    def zero_rs(row):
        return ov.two_tier_reduce_scatter(
            row, axis, dcn_axis, n_chunks=zero_chunks,
            rs=lambda v, ax: policy.reduce_scatter(v, ax, mesh.shape[ax]))

    def zero_ag(shard):
        return ov.two_tier_all_gather(
            shard, axis, dcn_axis, n_chunks=zero_chunks,
            ag=lambda v, ax: policy.all_gather(v, ax, mesh.shape[ax]))

    def zero_ag_q(shard_and_scale):
        q_row, s_row = shard_and_scale
        return ov.quantized_all_gather(q_row, s_row, axis, dcn_axis=dcn_axis,
                                       n_chunks=zero_chunks)

    def zero_step(params, opt_state, batch, err):
        flat_p, tdef = jax.tree.flatten(params)
        table, padded = zero_geometry([p.size for p in flat_p])
        nb = table.n_buckets
        step_no = opt_state["step"] + 1
        lr = adamw.schedule(step_no, opt)
        if nb == 0:  # every parameter leaf is zero-size: nothing on the wire
            loss = jax.lax.pmean(model.loss(params, batch), loss_axes)
            metrics = {"grad_norm": jnp.zeros((), jnp.float32), "lr": lr,
                       "loss": loss}
            return params, {"m": opt_state["m"], "v": opt_state["v"],
                            "step": step_no}, metrics, err
        cap = table.bucket_elems
        shard_elems = padded // (n * n_dcn)
        inv = 1.0 / (n_total * microbatches)

        def grads_of(b):
            loss, grads = jax.value_and_grad(model.loss)(params, b)
            flat, _ = jax.tree.flatten(grads)
            # same canonical-materialization barrier as the allreduce paths
            return loss, jax.lax.optimization_barrier(flat)

        @telemetry.scope("exchange")
        def pack_pad(flat):
            carrier, _, _ = codec.pack(table, flat, scale=inv)
            return pad_cols(carrier, padded)

        if microbatches == 1:
            loss, flat_g = grads_of(batch)
            carrier = pack_pad(flat_g)
            with telemetry.scope("exchange"):
                if overlap:
                    # scan-carried RS stream: one bucket's reduce-scatter in
                    # flight at a time, in backward materialization order
                    g_shard = ov.scan_bucket_reduce(carrier, zero_rs)
                else:
                    g_shard = jnp.stack([zero_rs(carrier[k]) for k in range(nb)])
        else:
            mb = _microbatch(batch, microbatches)
            mb0 = jax.tree.map(lambda a: a[0], mb)
            rest = jax.tree.map(lambda a: a[1:], mb)
            loss0, flat0 = grads_of(mb0)
            pending0 = pack_pad(flat0)

            def body(carry, b):
                acc, pending, lsum = carry
                # previous microbatch's reduce-scatters are issued FIRST (no
                # dependency on this backward) so they overlap it; shards are
                # accumulated — 1/n of the accumulator an all-reduce carries
                with telemetry.scope("exchange"):
                    red = jnp.stack([zero_rs(pending[k]) for k in range(nb)])
                loss, flat = grads_of(b)
                with telemetry.scope("grad_accum"):
                    acc, lsum = acc + red, lsum + loss
                return (acc, pack_pad(flat), lsum), None

            init = (jnp.zeros((nb, shard_elems), jnp.float32), pending0,
                    loss0)
            (acc, pending, lsum), _ = jax.lax.scan(body, init, rest)
            with telemetry.scope("exchange"):
                final = jnp.stack([zero_rs(pending[k]) for k in range(nb)])
            with telemetry.scope("grad_accum"):
                g_shard = acc + final
                loss = lsum / microbatches
        with telemetry.scope("exchange"):
            loss = jax.lax.pmean(loss, loss_axes)

        with telemetry.scope("optimizer"):
            # exact global-norm clipping: the per-shard sum of squares is
            # psum-combined over the dp axes before the clip factor forms.  This
            # is also the schedule's semantic barrier — no shard may update until
            # every bucket's reduce-scatter has landed.
            gsq = jax.lax.psum(jnp.sum(jnp.square(g_shard)), loss_axes)
            gnorm = jnp.sqrt(gsq)
            clip = jnp.minimum(1.0, opt.clip_norm / (gnorm + 1e-9))
            bc1 = 1 - opt.b1 ** step_no.astype(jnp.float32)
            bc2 = 1 - opt.b2 ** step_no.astype(jnp.float32)

            # params ride the same codec: pack (casts to fp32), pad, slice this
            # device's shard-major blocks out of each chunk
            p_carrier = pad_cols(codec.pack(table, flat_p)[0], padded)
            ix = jax.lax.axis_index(axis) * n_dcn + (
                jax.lax.axis_index(dcn_axis) if dcn_axis is not None else 0)
            sub = shard_elems // zero_chunks
            p_shard = jax.lax.dynamic_slice(
                p_carrier.reshape(nb, zero_chunks, padded // zero_chunks),
                (0, 0, ix * sub), (nb, zero_chunks, sub)).reshape(nb, shard_elems)

            p_wire, p_scales, new_m, new_v = codec.adamw_update_shard(
                g_shard, p_shard, opt_state["m"], opt_state["v"],
                clip=clip, lr=lr, bc1=bc1, bc2=bc2, b1=opt.b1, b2=opt.b2,
                eps=opt.eps, weight_decay=opt.weight_decay, wire=zero_wire)

        with telemetry.scope("exchange"):
            if zero_wire == "int8":
                if overlap:
                    full = ov.scan_bucket_reduce((p_wire, p_scales), zero_ag_q)
                else:
                    full = jnp.stack([zero_ag_q((p_wire[k], p_scales[k]))
                                      for k in range(nb)])
            elif overlap:
                full = ov.scan_bucket_reduce(p_wire, zero_ag)
            else:
                full = jnp.stack([zero_ag(p_wire[k]) for k in range(nb)])
            new_flat = codec.unpack(table, full[:, :cap], flat_p)
        new_params = tdef.unflatten(
            [r.astype(p.dtype) for r, p in zip(new_flat, flat_p)])
        metrics = {"grad_norm": gnorm, "lr": lr, "loss": loss}
        return new_params, {"m": new_m, "v": new_v, "step": step_no}, \
            metrics, err

    def reduce_bucket(buf):
        """One packed fp32 bucket through the planned reduction: the chunked
        hierarchical pipeline on a two-level mesh, else the plan's algorithm."""
        if dcn_axis is not None and plan_hier and chunks > 1:
            return ov.chunked_hierarchical_all_reduce(buf, axis, dcn_axis,
                                                      n_chunks=chunks)
        return policy.all_reduce(buf, axis, n, dcn_axis=dcn_axis)

    def reduce_q(row_and_scale, n_chunks=1):
        """One int8 bucket (row + per-bucket scale) over the wire: intra
        all-gather of the payload + scales, local dequant-sum, fp32 inter leg;
        chunked double-buffered across the two tiers when `n_chunks > 1`."""
        q_row, s_row = row_and_scale
        return ov.quantized_all_reduce(q_row, s_row, axis, dcn_axis=dcn_axis,
                                       n_chunks=n_chunks)

    @telemetry.scope("exchange")
    def reduce_bucketed(flat_g, err):
        """Pack the flat gradient stream into bucket_bytes rows (tensors split
        at bucket boundaries, forward order) and reduce each eagerly — exactly
        ceil(total_bytes / bucket_bytes) all-reduce calls, post-backward.  The
        codec shares span construction with the overlap engine; only the issue
        schedule differs.  With compression, quantization (and the per-bucket
        error feedback) happens in the pack."""
        table = codec.make_table([g.size for g in flat_g], bucket_elems,
                                 reverse=False)
        if table.n_buckets == 0:
            return [g.astype(jnp.float32) for g in flat_g], err
        cap = table.bucket_elems
        tail = table.total_elems - (table.n_buckets - 1) * cap

        def reduce_row(k, row, fn):
            # the final partial bucket keeps its exact wire size: the zero pad
            # is never sent (and the size-dependent algorithm dispatch sees
            # the true payload)
            if k == table.n_buckets - 1 and tail < cap:
                red = fn(row[:tail])
                return jnp.concatenate(
                    [red, jnp.zeros((cap - tail,), red.dtype)])
            return fn(row)

        if compress_bits == 8:
            q, s, new_err = codec.pack(table, flat_g, scale=1.0 / n_total,
                                       wire="int8", err=err)
            rows = [reduce_row(k, q[k],
                               lambda r, kk=k: reduce_q((r, s[kk]),
                                                        n_chunks=chunks))
                    for k in range(table.n_buckets)]
            return codec.unpack(table, rows, flat_g), new_err
        carrier, _, _ = codec.pack(table, flat_g, scale=1.0 / n_total)
        rows = [reduce_row(k, carrier[k],
                           lambda r: policy.all_reduce(r, axis, n,
                                                       dcn_axis=dcn_axis))
                for k in range(table.n_buckets)]
        return codec.unpack(table, rows, flat_g), err

    def overlap_grads(params, batch, err):
        """Reverse-layer-order bucketed gradients under the overlap issue
        schedule.  Returns (mean loss over microbatches, reduced flat grads in
        fp32, tree def, new error state)."""
        inv = 1.0 / (n_total * microbatches)

        def grads_of(b):
            loss, grads = jax.value_and_grad(model.loss)(params, b)
            flat, tdef = jax.tree.flatten(grads)
            # pin one canonical materialization of the (rematted) backward:
            # without the barrier XLA re-fuses the grad computation per wire
            # consumer graph, so different wire paths see bf16-ulp-different
            # gradient bits and step numerics depend on the wire configuration
            return loss, jax.lax.optimization_barrier(flat), tdef

        if microbatches == 1:
            loss, flat_g, tdef = grads_of(batch)
            table = codec.make_table([g.size for g in flat_g], bucket_elems)
            if table.n_buckets == 0:  # every gradient leaf is zero-size
                return loss, [g.astype(jnp.float32) for g in flat_g], tdef, err
            with telemetry.scope("exchange"):
                if compress_bits == 8:
                    q, s, new_err = codec.pack(table, flat_g, scale=inv,
                                               wire="int8", err=err)
                    # scan-carried issue schedule over the quantized carrier: one
                    # int8 bucket (+ scale) in flight at a time
                    reduced = ov.scan_bucket_reduce(
                        (q, s), partial(reduce_q, n_chunks=chunks))
                    return loss, codec.unpack(table, reduced, flat_g), tdef, new_err
                carrier, _, _ = codec.pack(table, flat_g, scale=inv)
                # scan-carried issue schedule: one bucket in flight at a time, in
                # the order backward materializes them
                reduced = ov.scan_bucket_reduce(carrier, reduce_bucket)
                return loss, codec.unpack(table, reduced, flat_g), tdef, err

        mb = _microbatch(batch, microbatches)
        mb0 = jax.tree.map(lambda a: a[0], mb)
        rest = jax.tree.map(lambda a: a[1:], mb)
        loss0, flat0, tdef = grads_of(mb0)
        table = codec.make_table([g.size for g in flat0], bucket_elems)
        nb = table.n_buckets
        if nb == 0:
            raise ValueError("overlap microbatching found no gradient "
                             "elements to reduce (all leaves zero-size)")

        if compress_bits == 8:
            with telemetry.scope("exchange"):
                q0, s0, err1 = codec.pack(table, flat0, scale=inv, wire="int8",
                                          err=err)

            def body_q(carry, b):
                acc, q_p, s_p, err_c, lsum = carry
                # issue the previous microbatch's quantized bucket reductions
                # FIRST: no data dependency on this microbatch's backward
                with telemetry.scope("exchange"):
                    reduced = jnp.stack([reduce_q((q_p[k], s_p[k]),
                                                  n_chunks=chunks)
                                         for k in range(nb)])
                loss, flat, _ = grads_of(b)
                with telemetry.scope("exchange"):
                    # per-bucket error feedback carried through the scan
                    q_n, s_n, err_c = codec.pack(table, flat, scale=inv,
                                                 wire="int8", err=err_c)
                with telemetry.scope("grad_accum"):
                    acc, lsum = acc + reduced, lsum + loss
                return (acc, q_n, s_n, err_c, lsum), None

            init = (jnp.zeros((nb, table.bucket_elems), jnp.float32),
                    q0, s0, err1, loss0)
            (acc, q_p, s_p, err_c, lsum), _ = jax.lax.scan(body_q, init, rest)
            with telemetry.scope("exchange"):
                final = jnp.stack([reduce_q((q_p[k], s_p[k]), n_chunks=chunks)
                                   for k in range(nb)])
            with telemetry.scope("grad_accum"):
                reduced = acc + final
                loss = lsum / microbatches
            with telemetry.scope("exchange"):
                return (loss, codec.unpack(table, reduced, flat0), tdef, err_c)

        with telemetry.scope("exchange"):
            pending0, _, _ = codec.pack(table, flat0, scale=inv)

        def body(carry, b):
            acc, pending, lsum = carry
            # issue the previous microbatch's bucket reductions FIRST: they
            # have no data dependency on this microbatch's backward, so the
            # scheduler overlaps the reduction stream with the backward compute
            with telemetry.scope("exchange"):
                reduced = jnp.stack([reduce_bucket(pending[k])
                                     for k in range(nb)])
            loss, flat, _ = grads_of(b)
            with telemetry.scope("exchange"):
                nxt, _, _ = codec.pack(table, flat, scale=inv)
            with telemetry.scope("grad_accum"):
                acc, lsum = acc + reduced, lsum + loss
            return (acc, nxt, lsum), None

        init = (jnp.zeros_like(pending0), pending0, loss0)
        (acc, pending, lsum), _ = jax.lax.scan(body, init, rest)
        # flush: the last microbatch's buckets have no backward left to hide
        # behind — this is the exposed tail the predictor charges for
        with telemetry.scope("exchange"):
            final = jnp.stack([reduce_bucket(pending[k]) for k in range(nb)])
        with telemetry.scope("grad_accum"):
            reduced = acc + final
            loss = lsum / microbatches
        with telemetry.scope("exchange"):
            return loss, codec.unpack(table, reduced, flat0), tdef, err

    def local_step(params, opt_state, batch, err):
        if overlap:
            loss, red_flat, tdef, new_err = overlap_grads(params, batch, err)
            with telemetry.scope("exchange"):
                loss = jax.lax.pmean(loss, loss_axes)
            grads = tdef.unflatten(red_flat)
            params, opt_state, metrics = adamw.apply_updates(params, grads,
                                                             opt_state, opt)
            metrics["loss"] = loss
            return params, opt_state, metrics, new_err
        loss, grads = jax.value_and_grad(model.loss)(params, batch)
        with telemetry.scope("exchange"):
            loss = jax.lax.pmean(loss, loss_axes)

        @telemetry.scope("exchange")
        def reduce_one(g, e):
            g32 = g.astype(jnp.float32) / n_total
            if compress_bits == 8:
                g32 = g32 + e
                scale = jnp.maximum(jnp.max(jnp.abs(g32)), 1e-12) / 127.0
                q = jnp.clip(jnp.round(g32 / scale), -127, 127).astype(jnp.int8)
                deq = q.astype(jnp.float32) * scale
                new_e = g32 - deq
                # wire format: int8 payload + per-tensor fp32 scale, summed
                # after dequant — the all-gather moves s/4 + 4 bytes per peer,
                # not the 4x dequantized fp32 tensor
                qg = jax.lax.all_gather(q, axis)          # (n, ...) int8 wire
                sg = jax.lax.all_gather(scale, axis)      # (n,) fp32 scales
                summed = jnp.tensordot(sg, qg.astype(jnp.float32),
                                       axes=((0,), (0,)))
                if dcn_axis is not None:
                    # DCN leg stays fp32: re-quantizing the partial sum would
                    # add error outside the error-feedback loop
                    summed = jax.lax.psum(summed, dcn_axis)
                return summed, new_e
            return policy.all_reduce(g32, axis, n, dcn_axis=dcn_axis), e

        flat_g, tdef = jax.tree.flatten(grads)
        # same canonical-materialization barrier as the overlap path: the
        # reduced gradients must not depend on which wire path consumes them
        flat_g = jax.lax.optimization_barrier(flat_g)
        if bucketed:
            # err is carrier-shaped (compression) or passed through (fp32)
            reduced, new_err = reduce_bucketed(flat_g, err)
            grads = tdef.unflatten(reduced)
        else:
            flat_e = tdef.flatten_up_to(err)
            out = [reduce_one(g, e) for g, e in zip(flat_g, flat_e)]
            grads = tdef.unflatten([o[0] for o in out])
            new_err = tdef.unflatten([o[1] for o in out])
        params, opt_state, metrics = adamw.apply_updates(params, grads, opt_state, opt)
        metrics["loss"] = loss
        return params, opt_state, metrics, new_err

    def specs_like(tree, spec):
        return jax.tree.map(lambda _: spec, tree)

    def make(params, opt_state, batch, err):
        from jax import shard_map
        batch_axes = (dcn_axis, axis) if dcn_axis is not None else axis
        p_spec = specs_like(params, P())
        if zero:
            # fp32 moments are carrier-sharded on their column axis: each
            # device holds (n_buckets, padded / (n * n_dcn)) — optimizer
            # memory divided by the DP degree — and steady-state steps pass
            # the sharded arrays straight back in (no resharding)
            mv_spec = P(None, shard_axes)
            o_spec = {"m": mv_spec, "v": mv_spec, "step": P()}
        else:
            o_spec = specs_like(opt_state, P())
        b_spec = specs_like(batch, P(batch_axes))
        e_spec = specs_like(err, P())
        m_spec = {"grad_norm": P(), "lr": P(), "loss": P()}
        return shard_map(zero_step if zero else local_step, mesh=mesh,
                         in_specs=(p_spec, o_spec, b_spec, e_spec),
                         out_specs=(p_spec, o_spec, m_spec, e_spec),
                         check_vma=False)

    # remat inside the loss emits closed_call, which shard_map can't evaluate
    # eagerly — jit around the shard_map is required.  The shard_map specs
    # depend only on the pytree structures, so cache the built jit per
    # flattened tree-structure tuple: repeat calls with the same structures
    # reuse one jit (no per-step retrace), while a call with a different
    # batch/params structure gets fresh specs instead of silently reusing the
    # first call's stale shard_map specs.  Under compression the error state
    # (the codec's carrier-shaped buffer) is donated through the jit: it is
    # consumed and replaced every step, so steady-state training reuses the
    # carrier buffer instead of allocating a fresh one each step.  The fp32
    # paths pass err through untouched, where donation would only forbid
    # callers from reusing it for no win — so it is gated on compress_bits.
    cache: Dict[Tuple, Callable] = {}
    # zero mode never donates: err passes through untouched (no error
    # feedback on the param leg), and the parity tests legitimately reuse one
    # opt_state across several step builders
    donate = (3,) if (compress_bits and not zero) else ()

    def step(params, opt_state, batch, err):
        key = tuple(jax.tree.structure(t)
                    for t in (params, opt_state, batch, err))
        fn = cache.get(key)
        if fn is None:
            fn = cache[key] = jax.jit(make(params, opt_state, batch, err),
                                      donate_argnums=donate)
        return fn(params, opt_state, batch, err)

    def make_error_state(params):
        """Zeros of this step's error-feedback state: a carrier-shaped
        (n_buckets, bucket_elems) fp32 buffer when compression rides buckets,
        per-leaf zeros otherwise (the per-tensor legacy wire).  The zero path
        carries no error feedback (the param leg's payload rides every tier
        unchanged), so its state is a placeholder scalar."""
        if zero:
            return jnp.zeros((), jnp.float32)
        if compress_bits == 8 and bucketed:
            sizes = [p.size for p in jax.tree.leaves(params)]
            table = codec.make_table(sizes, bucket_elems,
                                     reverse=bool(overlap))
            return jnp.zeros((max(table.n_buckets, 1), table.bucket_elems),
                             jnp.float32)
        return init_error_state(params)

    def _param_sizes(params):
        import math as _math
        return [int(_math.prod(p.shape)) for p in jax.tree.leaves(params)]

    def make_opt_state(params):
        """Carrier-sharded optimizer state of the zero path: fp32 moments of
        shape (n_buckets, padded_bucket_elems) whose columns the step's
        in_specs shard over the dp axes (memory per device = full / DP)."""
        if not zero:
            return adamw.init_opt_state(params)
        table, padded = zero_geometry(_param_sizes(params))
        nb = max(table.n_buckets, 1)
        return {"m": jnp.zeros((nb, padded), jnp.float32),
                "v": jnp.zeros((nb, padded), jnp.float32),
                "step": jnp.zeros((), jnp.int32)}

    def make_abstract_opt_state(params):
        """ShapeDtypeStructs of `make_opt_state` (checkpoint restore target).
        `params` may be abstract or concrete."""
        if not zero:
            return adamw.abstract_opt_state(params)
        table, padded = zero_geometry(_param_sizes(params))
        mv = jax.ShapeDtypeStruct((max(table.n_buckets, 1), padded),
                                  jnp.float32)
        return {"m": mv, "v": mv,
                "step": jax.ShapeDtypeStruct((), jnp.int32)}

    def lower(params, opt_state, batch, err):
        """Lowered (pre-compile) artifact of this step's jit — the same
        cached jit the step itself runs, donation included, so the post-SPMD
        HLO `launch.lint --hlo` analyzes is exactly what executes."""
        key = tuple(jax.tree.structure(t)
                    for t in (params, opt_state, batch, err))
        fn = cache.get(key)
        if fn is None:
            fn = cache[key] = jax.jit(make(params, opt_state, batch, err),
                                      donate_argnums=donate)
        return fn.lower(params, opt_state, batch, err)

    step.lower = lower
    step._cache = cache  # introspectable by tests
    step.program = step_program
    step.donate_argnums = donate  # read by analysis.trace.trace_step
    step.init_error_state = make_error_state
    step.init_opt_state = make_opt_state
    step.abstract_opt_state = make_abstract_opt_state
    step.zero = zero
    # checkpoint shard-spec tag of the carrier-sharded moments: records the
    # sharded layout in the manifest so a replicated restore fails loudly
    step.opt_shard_spec = "zero-carrier:" + ",".join(shard_axes) if zero \
        else None
    return step


def build_program_step(model: Model, opt: adamw.OptConfig, mesh,
                       program: prg.StepProgram, axis: str = "data",
                       policy: Optional[CollectivePolicy] = None,
                       dcn_axis: Optional[str] = None) -> Callable:
    """Compile a StepProgram to the shard_map step.

    The program-first entry point: dense-gradient programs (AllReduce or the
    ZeRO sequence) lower onto the explicit-DP engine via
    ``program.step_kwargs()``; an AllToAll-bearing program compiles to the
    expert-parallel MoE step (`runtime.moe_step`), whose token
    dispatch/combine routes through the plan's per-tier alltoall tables.
    Either way ``step.program`` is the object that built the step — the same
    one ``exposed_comm_time(program=...)`` prices.
    """
    program.validate()
    if program.has("all_to_all"):
        from .moe_step import build_moe_ep_step
        return build_moe_ep_step(model, opt, mesh, axis=axis, policy=policy,
                                 program=program)
    return build_explicit_dp_step(model, opt, mesh, axis, policy=policy,
                                  dcn_axis=dcn_axis, step_program=program,
                                  **program.step_kwargs())


def init_error_state(params):
    """Per-leaf error-feedback zeros (the per-tensor wire's state shape).
    Steps built by `build_explicit_dp_step` expose `step.init_error_state`,
    which returns the carrier-shaped buffer when compression is bucketed."""
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
