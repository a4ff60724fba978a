"""Fused bucket wire codec + wire-format planning: round-trip properties vs
the unfused `overlap` pack/unpack, in-kernel quantization + error feedback,
per-tier wire selection/persistence/pricing, and the O(1)-concatenate jaxpr
regression on the packed explicit-DP step."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.core import overlap as ov
from repro.core import wire as wr
from repro.core.commplan import CommPlan
from repro.core.costmodel import exposed_comm_time, make_comm_model
from repro.core.topology import make_paper_systems
from repro.kernels import bucket_codec as bc

from .helpers import run_devices


def _leaves(rng, shapes, dtype=np.float32):
    return [jnp.asarray(rng.randn(*s).astype(np.float32)).astype(dtype)
            for s in shapes]


# --------------------------------------------------------------- round trips
RAGGED_SHAPE_SETS = [
    [(3, 2), (5,), (1,)],              # ragged small leaves
    [(2, 2), (0,), (3,)],              # zero-size leaf in the middle
    [(0,), (0, 4)],                    # all leaves zero-size (no buckets)
    [(7, 3), (1000,), (13,)],          # bucket-spanning large leaf
    [(1,)],                            # single element
]


def _codec_fns(jit):
    """pack/unpack called eagerly, or under `jax.jit` as the steps call them
    (the table is static, so it rides in the closure)."""
    if not jit:
        return bc.pack, bc.unpack

    def pack(table, flat, **kw):
        return jax.jit(lambda f: bc.pack(table, f, **kw))(flat)

    def unpack(table, carrier, like, **kw):
        return jax.jit(lambda c: bc.unpack(table, c, like, **kw))(carrier)

    return pack, unpack


@pytest.mark.parametrize("shapes", RAGGED_SHAPE_SETS)
@pytest.mark.parametrize("jit", [False, True])
@pytest.mark.parametrize("reverse", [True, False])
def test_fp32_roundtrip_matches_unfused(shapes, jit, reverse):
    """Codec pack/unpack must be element-for-element identical to the unfused
    `overlap.pack_buckets`/`unpack_buckets` across ragged, zero-size, and
    bucket-spanning leaves, in both bucket orders, eager and jitted."""
    pack, unpack = _codec_fns(jit)
    rng = np.random.RandomState(0)
    flat = _leaves(rng, shapes)
    sizes = [g.size for g in flat]
    for cap in (4, 1, 0, 10_000):  # incl. sub-element (0 -> clamps to 1)
        table = bc.make_table(sizes, cap, reverse=reverse)
        buckets = ov.make_buckets(sizes, cap, reverse=reverse)
        assert table.n_buckets == len(buckets)
        if table.n_buckets == 0:
            with pytest.raises(ValueError, match="empty table"):
                pack(table, flat)
            continue
        ref = ov.pack_buckets(flat, buckets, scale=2.0)
        carrier, scales, _ = pack(table, flat, scale=2.0)
        assert scales is None
        assert carrier.shape == (table.n_buckets, table.bucket_elems)
        np.testing.assert_allclose(np.asarray(carrier), np.asarray(ref),
                                   rtol=1e-6)
        back = unpack(table, carrier, flat)
        ref_back = ov.unpack_buckets(ref, buckets, flat)
        for a, b, g in zip(back, ref_back, flat):
            assert a.shape == g.shape and a.dtype == jnp.float32
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_roundtrip_input_dtypes(dtype):
    """bf16 gradient leaves round-trip through the fp32 carrier exactly (the
    pack casts up); the bf16 *wire* round-trips within bf16 resolution."""
    rng = np.random.RandomState(1)
    flat = _leaves(rng, [(17,), (4, 5)], dtype)
    table = bc.make_table([g.size for g in flat], 8)
    carrier, _, _ = bc.pack(table, flat)
    back = bc.unpack(table, carrier, flat)
    for a, g in zip(back, flat):
        np.testing.assert_array_equal(np.asarray(a),
                                      np.asarray(g.astype(jnp.float32)))
    c16, _, _ = bc.pack(table, flat, wire="bf16")
    assert c16.dtype == jnp.bfloat16
    for a, g in zip(bc.unpack(table, c16, flat), flat):
        np.testing.assert_allclose(np.asarray(a),
                                   np.asarray(g.astype(jnp.float32)),
                                   rtol=1e-2, atol=1e-2)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 6), st.integers(1, 64), st.integers(0, 1))
def test_roundtrip_property(n_leaves, cap, rev):
    """Property: for random leaf sets and bucket sizes, unpack(pack(x)) == x
    (fp32 wire) and the carrier layout matches the unfused reference."""
    rng = np.random.RandomState(n_leaves * 1000 + cap)
    shapes = [tuple(rng.randint(0, 9, size=rng.randint(1, 3)))
              for _ in range(n_leaves)]
    flat = _leaves(rng, shapes)
    sizes = [g.size for g in flat]
    table = bc.make_table(sizes, cap, reverse=bool(rev))
    if table.n_buckets == 0:
        return
    buckets = ov.make_buckets(sizes, cap, reverse=bool(rev))
    ref = ov.pack_buckets(flat, buckets, scale=0.5)
    carrier, _, _ = bc.pack(table, flat, scale=0.5)
    np.testing.assert_allclose(np.asarray(carrier), np.asarray(ref), rtol=1e-6)
    for a, g in zip(bc.unpack(table, carrier, flat), flat):
        np.testing.assert_allclose(np.asarray(a), 0.5 * np.asarray(g),
                                   rtol=1e-5, atol=1e-7)


# ------------------------------------------------------------- int8 + errors
@pytest.mark.parametrize("jit", [False, True])
def test_int8_pack_error_feedback_identity(jit):
    """The quantization must satisfy the error-feedback identity
    q * scale + new_err == packed + err exactly (that is the convergence
    guarantee), and eager and jitted packs must agree bit-for-bit."""
    pack, unpack = _codec_fns(jit)
    rng = np.random.RandomState(2)
    flat = _leaves(rng, [(33,), (5, 5), (0,), (7,)])
    table = bc.make_table([g.size for g in flat], 16)
    err = jnp.asarray(rng.randn(table.n_buckets, table.bucket_elems)
                      .astype(np.float32)) * 1e-3
    q, s, new_err = pack(table, flat, scale=0.25, wire="int8", err=err)
    assert q.dtype == jnp.int8 and s.shape == (table.n_buckets,)
    packed, _, _ = bc.pack(table, flat, scale=0.25)
    lhs = np.asarray(q).astype(np.float32) * np.asarray(s)[:, None] \
        + np.asarray(new_err)
    np.testing.assert_allclose(lhs, np.asarray(packed + err), rtol=1e-5,
                               atol=1e-7)
    # eager and jitted agree exactly on the wire payload
    q2, s2, e2 = bc.pack(table, flat, scale=0.25, wire="int8", err=err)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q2))
    np.testing.assert_allclose(np.asarray(s), np.asarray(s2), rtol=1e-7)
    np.testing.assert_allclose(np.asarray(new_err), np.asarray(e2), atol=1e-7)
    # dequantized unpack stays within one quantization step of the source
    deq = unpack(table, q, flat, scales=s)
    for a, g in zip(deq, flat):
        if g.size:
            tol = float(np.asarray(s).max())
            np.testing.assert_allclose(np.asarray(a), 0.25 * np.asarray(g),
                                       atol=tol * 1.01)


def test_int8_all_zero_bucket_stable():
    """An all-zero bucket must quantize with the clamped scale, not divide by
    zero (NaN on the wire)."""
    flat = [jnp.zeros((8,), jnp.float32)]
    table = bc.make_table([8], 4)
    q, s, e = bc.pack(table, flat, wire="int8",
                      err=jnp.zeros((2, 4), jnp.float32))
    assert np.all(np.isfinite(np.asarray(s)))
    assert np.all(np.asarray(q) == 0) and np.all(np.asarray(e) == 0.0)


def test_wire_bytes_accounting():
    table = bc.make_table([100], 32)  # 4 buckets of 32 elems
    assert bc.wire_bytes(table, "fp32") == 4 * 32 * 4
    assert bc.wire_bytes(table, "bf16") == 4 * 32 * 2
    assert bc.wire_bytes(table, "int8") == 4 * 32 * 1 + 4 * 4
    assert wr.bytes_on_wire(1024.0, "int8", n_buckets=2) == 256.0 + 8.0
    assert wr.bytes_on_wire(1024.0, "fp32") == 1024.0


# --------------------------------------------------------- wire-format plans
def test_choose_format_thresholds():
    """Compress where bandwidth-bound, fp32 where alpha-bound."""
    assert wr.choose_format(1e-5, 1e-3) == "int8"     # beta >> alpha
    assert wr.choose_format(1e-5, 3e-5) == "bf16"     # middle regime
    assert wr.choose_format(1e-5, 1e-6) == "fp32"     # alpha-bound
    assert wr.choose_format(1e-5, 1e-3, allow_lossy=False) == "fp32"


def test_choose_wire_inter_compresses_intra_paced_stays_fp32():
    """The pacing rule: a bandwidth-bound inter tier compresses, and an intra
    tier that never paces the pipeline stays fp32 even if its own beta term
    dominates its alpha term."""
    p = ov.PipelineParams(n_ici=4, alpha_ici=2e-6, bw_ici=300e9,
                          alpha_dcn=1e-5, bw_dcn=25e9)
    spec = wr.choose_wire(p, float(16 << 20))
    assert spec.inter == "int8"
    assert spec.intra == "fp32"
    # a starved intra tier that paces the pipeline is allowed to compress...
    slow = ov.PipelineParams(n_ici=4, alpha_ici=2e-6, bw_ici=1e9,
                             alpha_dcn=1e-5, bw_dcn=25e9)
    assert wr.choose_wire(slow, float(16 << 20)).intra == "int8"
    # ...but only while the realized int8 gather wire ((n-1)/4 per peer) beats
    # the fp32 allreduce (2(n-1)/n): at n >= 8 the gather moves MORE bytes,
    # so the planner must not turn compression on where it slows the step
    slow8 = ov.PipelineParams(n_ici=8, alpha_ici=2e-6, bw_ici=1e9,
                              alpha_dcn=1e-5, bw_dcn=25e9)
    assert wr.choose_wire(slow8, float(16 << 20)).intra == "fp32"
    assert wr.gather_wins(4) and not wr.gather_wins(8)
    # pricing uses the realized gather multiplier, not the idealized 0.25
    assert wr.realized_multiplier("int8", 4) == pytest.approx(0.5)
    assert wr.realized_multiplier("int8", 32) == 1.0
    assert wr.realized_multiplier("bf16", 32) == pytest.approx(0.5)


def test_plan_wire_persisted_and_exposed():
    """plan.wire survives the JSON round-trip, reaches CollectivePolicy, and
    the paper systems land where the paper points (inter tier compresses)."""
    from repro.core.autotune import CollectivePolicy

    plan = CommPlan.from_topology(make_paper_systems()["leonardo"])
    assert plan.wire and plan.wire["inter"] == "int8"
    assert plan.wire["intra"] == "fp32"
    back = CommPlan.from_blob(plan.to_blob())
    assert back.wire == plan.wire
    assert back.wire_spec() == plan.wire_spec()
    pol = CollectivePolicy.from_plan(plan)
    assert pol.wire.inter == "int8" and pol.wire.compresses
    # legacy blobs (no wire key) mean fp32 everywhere
    legacy = CommPlan.from_blob({"all_reduce": {}, "all_to_all": {}})
    assert legacy.wire_spec() == wr.WireSpec()
    assert not legacy.wire_spec().compresses
    with pytest.raises(ValueError, match="unknown wire format"):
        wr.WireSpec(intra="fp7")


def test_exposed_comm_time_prices_wire():
    """Wire-aware pricing: a compressing plan strictly shrinks the predicted
    comm time vs the fp32 wire, and never increases it."""
    plan = CommPlan.from_topology(make_paper_systems()["leonardo"])
    model = make_comm_model("leonardo")
    from repro.core.scenarios import synthetic_grad_sizes

    sizes = synthetic_grad_sizes(256 << 20)
    fp = exposed_comm_time(0.05, plan, sizes, n_endpoints=512, model=model)
    priced = exposed_comm_time(0.05, plan, sizes, n_endpoints=512, model=model,
                               wire="plan")
    assert fp.wire == "fp32/fp32"
    assert priced.wire == "fp32/int8"
    assert priced.total_comm_s < fp.total_comm_s
    assert priced.exposed_s <= fp.exposed_s + 1e-12
    # explicit spec and dict forms are accepted
    byspec = exposed_comm_time(0.05, plan, sizes, n_endpoints=512, model=model,
                               wire=wr.WireSpec(inter="int8"))
    bydict = exposed_comm_time(0.05, plan, sizes, n_endpoints=512, model=model,
                               wire={"inter": "int8"})
    assert byspec.total_comm_s == pytest.approx(bydict.total_comm_s)


def test_sweep_overlap_wire_param():
    from repro.core.scenarios import sweep_overlap

    fp = sweep_overlap("leonardo", (512,))
    pr = sweep_overlap("leonardo", (512,), wire="plan")
    assert fp[0].wire == "fp32/fp32" and pr[0].wire == "fp32/int8"
    assert pr[0].total_comm_s < fp[0].total_comm_s


# --------------------------------------------------- jaxpr op-count regression
from .helpers import count_eqns as _count_eqns


def _count_prim(closed, name):
    return _count_eqns(closed, name)


class _ToyModel:
    @staticmethod
    def loss(params, batch):
        s = sum(jnp.sum(p) for p in jax.tree.leaves(params))
        return (s - 1.0) ** 2 + 0.0 * jnp.mean(batch["x"])


def _toy_step_jaxpr(n_leaves, **kw):
    from jax.sharding import AxisType
    from repro.optim import adamw
    from repro.runtime import steps as rsteps

    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    opt = adamw.OptConfig(peak_lr=1e-2, warmup_steps=0, decay_steps=10)
    params = {f"w{i}": jnp.ones((65,), jnp.float32) for i in range(n_leaves)}
    batch = {"x": jnp.ones((2,), jnp.float32)}
    step = rsteps.build_explicit_dp_step(_ToyModel(), opt, mesh, "data", **kw)
    err = step.init_error_state(params)
    return jax.make_jaxpr(lambda p, o, b, e: step(p, o, b, e))(
        params, adamw.init_opt_state(params), batch, err)


@pytest.mark.parametrize("kw", [dict(overlap=True, bucket_bytes=4 * 128),
                                dict(overlap=True, bucket_bytes=4 * 128,
                                     compress_bits=8),
                                dict(bucket_bytes=4 * 128)])
def test_packed_step_has_o1_concatenates(kw):
    """The packed explicit-DP step must contain O(1) concatenate ops — not one
    per bucket and one per leaf like the unfused pack/unpack emitted.  Checked
    at two leaf counts: the count must not grow with the tree."""
    c_small = _count_prim(_toy_step_jaxpr(4, **kw), "concatenate")
    c_big = _count_prim(_toy_step_jaxpr(24, **kw), "concatenate")
    assert c_big <= 2, (c_small, c_big)
    assert c_big == c_small, "concatenate count grew with the leaf count"


def test_overlap_step_single_fused_pack_and_unpack():
    """Jaxpr-level acceptance: one fused pack (dynamic_update_slice chain into
    a single carrier) and one fused unpack (slice per leaf), with the
    reductions in a single scan over the carrier rows."""
    jx = _toy_step_jaxpr(8, overlap=True, bucket_bytes=4 * 128)
    assert _count_prim(jx, "concatenate") == 0
    # one dus per leaf (the fused pack), not per (leaf x bucket)
    assert _count_prim(jx, "dynamic_update_slice") == 8
    assert _count_prim(jx, "scan") >= 1


# ------------------------------------------------ runtime numerics (multi-dev)
INT8_OVERLAP = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.models import build_model
from repro.optim import adamw
from repro.runtime import steps as rsteps

cfg = get_config("smollm-135m").reduced()
shape = ShapeConfig("t", 32, 8, "train")
mesh = jax.make_mesh((4,), ("data",), axis_types=(AxisType.Auto,))
model = build_model(cfg)
opt = adamw.OptConfig(peak_lr=1e-3, warmup_steps=1, decay_steps=20)
params = model.init(jax.random.PRNGKey(0))
ostate = adamw.init_opt_state(params)
batch = model.make_batch(shape)
delta = lambda a, b: max(
    float(jnp.max(jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32))))
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))

base = rsteps.build_explicit_dp_step(model, opt, mesh, "data")
bp, _, bm, _ = base(params, ostate, batch, base.init_error_state(params))

# unfused baseline: per-tensor int8 (the legacy wire)
pt = rsteps.build_explicit_dp_step(model, opt, mesh, "data", compress_bits=8)
pp, _, pm, _ = pt(params, ostate, batch, pt.init_error_state(params))

# int8 + overlap: previously raised ValueError by construction
bb = 1 << 20
ovl = rsteps.build_explicit_dp_step(model, opt, mesh, "data", compress_bits=8,
                                    overlap=True, bucket_bytes=bb)
err = ovl.init_error_state(params)
assert err.ndim == 2, err.shape  # carrier-shaped error state
from repro.analysis import expected_trace, lint_trace, trace_jaxpr
jx = jax.make_jaxpr(lambda p, o, b, e: ovl(p, o, b, e))(
    params, ostate, batch, err)
tr = trace_jaxpr(jx, donate_argnums=ovl.donate_argnums)
# the wire is per-bucket int8 inside a scan: i8 gathers appear once (in the
# scan body), not once per leaf like the per-tensor baseline
n_leaves = len(jax.tree.leaves(params))
i8 = [r for r in tr.records if r.kind == "all_gather" and r.dtype == "int8"]
assert 1 <= len(i8) < n_leaves, (len(i8), n_leaves)
assert all(r.scan_depth >= 1 for r in i8), i8
# and the full CommLint rule catalog agrees the step matches its program
grad_bytes = sum(p.size * 4 for p in jax.tree.leaves(params))
fs = lint_trace(tr, expected_trace(ovl.program, n_devices=4,
                                   grad_bytes=grad_bytes))
assert not fs, [str(f) for f in fs]
op, _, om, oe = ovl(params, ostate, batch, err)
assert oe.ndim == 2
d_fp = delta(bp, op); d_pt = delta(pp, op)
print("int8+overlap vs fp32:", d_fp, "vs unfused int8:", d_pt)
# documented error-feedback tolerance: one int8 quantization step of the
# bucket scale on top of the fp32 baseline after one optimizer step
assert d_fp < 5e-2 and d_pt < 5e-2

# microbatched: error feedback carried per bucket through the scan
mbs = rsteps.build_explicit_dp_step(model, opt, mesh, "data", compress_bits=8,
                                    overlap=True, bucket_bytes=bb,
                                    microbatches=2)
mp, _, mm, me = mbs(params, ostate, batch, mbs.init_error_state(params))
assert delta(bp, mp) < 5e-2

# two-level mesh: int8 intra gather + fp32 inter leg, chunked pipeline
mesh2 = jax.make_mesh((2, 2), ("pod", "data"), axis_types=(AxisType.Auto,)*2)
hier = rsteps.build_explicit_dp_step(model, opt, mesh2, "data",
                                     dcn_axis="pod", compress_bits=8,
                                     overlap=True, bucket_bytes=bb, chunks=3)
hp, _, hm, he = hier(params, ostate, batch, hier.init_error_state(params))
assert delta(bp, hp) < 5e-2

# error feedback converges: a second step with the carried error state stays
# finite and keeps tracking the fp32 trajectory
bp2, bo2, bm2, _ = base(bp, ostate, batch, base.init_error_state(params))
op2, _, om2, _ = ovl(op, ostate, batch, oe)
assert jnp.isfinite(om2["loss"]) and delta(bp2, op2) < 1e-1
print("ALL_OK")
"""


@pytest.mark.slow
def test_int8_composes_with_overlap_numerics():
    assert "ALL_OK" in run_devices(INT8_OVERLAP, 4, timeout=560)


def test_compress_no_longer_excludes_overlap():
    """The ValueError barring compress_bits + bucketing/overlap is gone; the
    remaining guards (bad bits, per-tensor overlap, mb without overlap) hold."""
    from jax.sharding import AxisType
    from repro.optim import adamw
    from repro.runtime import steps as rsteps

    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    opt = adamw.OptConfig()
    # composes now: no raise at build time
    rsteps.build_explicit_dp_step(_ToyModel(), opt, mesh, "data",
                                  compress_bits=8, overlap=True)
    rsteps.build_explicit_dp_step(_ToyModel(), opt, mesh, "data",
                                  compress_bits=8, bucket_bytes=1 << 20)
    with pytest.raises(ValueError, match="compress_bits"):
        rsteps.build_explicit_dp_step(_ToyModel(), opt, mesh, "data",
                                      compress_bits=4)
    with pytest.raises(ValueError, match="per-tensor"):
        rsteps.build_explicit_dp_step(_ToyModel(), opt, mesh, "data",
                                      overlap=True, bucket_bytes=0)
    with pytest.raises(ValueError, match="overlap"):
        rsteps.build_explicit_dp_step(_ToyModel(), opt, mesh, "data",
                                      microbatches=2)


def test_init_error_state_shapes():
    """Carrier-shaped zeros when compression rides buckets; per-leaf zeros on
    the per-tensor wire."""
    from jax.sharding import AxisType
    from repro.optim import adamw
    from repro.runtime import steps as rsteps

    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    opt = adamw.OptConfig()
    params = {"a": jnp.ones((100,)), "b": jnp.ones((30,))}
    bb = 4 * 64
    s = rsteps.build_explicit_dp_step(_ToyModel(), opt, mesh, "data",
                                      compress_bits=8, overlap=True,
                                      bucket_bytes=bb)
    err = s.init_error_state(params)
    assert err.shape == (3, 64) and err.dtype == jnp.float32  # ceil(130/64)
    s_pt = rsteps.build_explicit_dp_step(_ToyModel(), opt, mesh, "data",
                                         compress_bits=8)
    err_pt = s_pt.init_error_state(params)
    assert jax.tree.structure(err_pt) == jax.tree.structure(params)


# --------------------------------------------------- ZeRO fused shard update
def test_adamw_update_shard_matches_adamw_reference():
    """The fused dequant+AdamW+requantize shard kernel must reproduce
    `adamw.apply_updates` exactly (same op order) on a flat fp32 shard, for
    both implementations."""
    from repro.optim import adamw

    rng = np.random.RandomState(3)
    nb, sh = 3, 64
    g = jnp.asarray(rng.randn(nb, sh).astype(np.float32))
    p = jnp.asarray(rng.randn(nb, sh).astype(np.float32))
    m = jnp.asarray(rng.randn(nb, sh).astype(np.float32)) * 0.1
    v = jnp.abs(jnp.asarray(rng.randn(nb, sh).astype(np.float32))) * 0.01
    cfg = adamw.OptConfig(peak_lr=1e-2, warmup_steps=0, decay_steps=10)
    state = {"m": {"w": m}, "v": {"w": v}, "step": jnp.zeros((), jnp.int32)}
    ref_p, ref_s, ref_metrics = adamw.apply_updates({"w": p}, {"w": g}, state,
                                                    cfg)
    step = jnp.ones((), jnp.float32)
    clip = jnp.minimum(1.0, cfg.clip_norm / (adamw.global_norm({"w": g}) + 1e-9))
    kw = dict(clip=clip, lr=adamw.schedule(1, cfg),
              bc1=1 - cfg.b1 ** step, bc2=1 - cfg.b2 ** step,
              b1=cfg.b1, b2=cfg.b2, eps=cfg.eps,
              weight_decay=cfg.weight_decay)
    # the eager xla impl (what the CPU/GPU trainer runs) is bit-for-bit; the
    # pallas kernel body goes through jit, where XLA may fuse a*b+c into an
    # FMA — 1-ulp slack covers exactly that
    pw, ps, nm, nv = bc.adamw_update_shard(g, p, m, v, wire="fp32",
                                           impl="xla", **kw)
    assert ps is None
    np.testing.assert_array_equal(np.asarray(pw), np.asarray(ref_p["w"]))
    np.testing.assert_array_equal(np.asarray(nm), np.asarray(ref_s["m"]["w"]))
    np.testing.assert_array_equal(np.asarray(nv), np.asarray(ref_s["v"]["w"]))
    pw2, _, nm2, nv2 = bc.adamw_update_shard(g, p, m, v, wire="fp32",
                                             impl="pallas", **kw)
    np.testing.assert_allclose(np.asarray(pw2), np.asarray(ref_p["w"]),
                               rtol=3e-7, atol=1e-9)
    np.testing.assert_allclose(np.asarray(nm2), np.asarray(ref_s["m"]["w"]),
                               rtol=3e-7, atol=1e-9)
    np.testing.assert_allclose(np.asarray(nv2), np.asarray(ref_s["v"]["w"]),
                               rtol=3e-7, atol=1e-9)


def test_adamw_update_shard_int8_wire():
    """int8 wire: per-row scales, xla/pallas agree bit-for-bit on the payload,
    dequantized params land within one quantization step; an all-zero row
    quantizes with the clamped scale (no NaN)."""
    from repro.optim import adamw

    rng = np.random.RandomState(4)
    nb, sh = 2, 32
    g = jnp.asarray(rng.randn(nb, sh).astype(np.float32))
    p = jnp.asarray(rng.randn(nb, sh).astype(np.float32))
    m = jnp.zeros((nb, sh), jnp.float32)
    v = jnp.zeros((nb, sh), jnp.float32)
    kw = dict(clip=jnp.float32(1.0), lr=jnp.float32(1e-2),
              bc1=jnp.float32(0.1), bc2=jnp.float32(0.05),
              b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1)
    ref, _, _, _ = bc.adamw_update_shard(g, p, m, v, wire="fp32", impl="xla",
                                         **kw)
    outs = {}
    for impl in ("xla", "pallas"):
        q, s, nm, nv = bc.adamw_update_shard(g, p, m, v, wire="int8",
                                             impl=impl, **kw)
        assert q.dtype == jnp.int8 and s.shape == (nb,)
        deq = np.asarray(q, np.float32) * np.asarray(s)[:, None]
        np.testing.assert_allclose(deq, np.asarray(ref),
                                   atol=float(np.asarray(s).max()) * 1.01)
        outs[impl] = (np.asarray(q), np.asarray(s))
    np.testing.assert_array_equal(outs["xla"][0], outs["pallas"][0])
    np.testing.assert_allclose(outs["xla"][1], outs["pallas"][1], rtol=1e-7)
    # all-zero state at g=p=0 is an AdamW fixed point with wd=0: stays zero
    z = jnp.zeros((1, 8), jnp.float32)
    q0, s0, m0, v0 = bc.adamw_update_shard(z, z, z, z, wire="int8", impl="xla",
                                           clip=jnp.float32(1.0),
                                           lr=jnp.float32(1e-2),
                                           bc1=jnp.float32(0.1),
                                           bc2=jnp.float32(0.05),
                                           b1=0.9, b2=0.95, eps=1e-8,
                                           weight_decay=0.1)
    assert np.all(np.isfinite(np.asarray(s0)))
    assert np.all(np.asarray(q0) == 0)
    assert np.all(np.asarray(m0) == 0) and np.all(np.asarray(v0) == 0)


def _toy_zero_steps(shapes, **kw):
    """Baseline + zero step pair over a params tree with `shapes` leaves on a
    1-device mesh (collectives degenerate to identity, numerics stay real)."""
    from jax.sharding import AxisType
    from repro.optim import adamw
    from repro.runtime import steps as rsteps

    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    # clip_norm high enough that clip == 1.0 exactly on both paths: the
    # sum-of-squares reduction order differs (per-leaf vs padded carrier
    # rows), so the norm itself can differ in the last ulp — which must not
    # leak into the update for the bit-parity claim.  An *active* clip with
    # exactly-representable norms is covered by
    # test_zero_step_bit_parity_active_clip.
    opt = adamw.OptConfig(peak_lr=1e-2, warmup_steps=0, decay_steps=10,
                          clip_norm=1e9)
    rng = np.random.RandomState(7)
    # dyadic values: every fp32 sum order is exact, so parity is bit-for-bit
    params = {f"w{i}": jnp.asarray(
        rng.randint(-8, 9, size=s).astype(np.float32) * 0.25)
        for i, s in enumerate(shapes)}
    batch = {"x": jnp.ones((2,), jnp.float32)}
    base = rsteps.build_explicit_dp_step(_ToyModel(), opt, mesh, "data")
    z = rsteps.build_explicit_dp_step(_ToyModel(), opt, mesh, "data",
                                      zero=True, **kw)
    return base, z, params, batch


@pytest.mark.parametrize("shapes", RAGGED_SHAPE_SETS)
@pytest.mark.parametrize("kw", [dict(bucket_bytes=4 * 64),
                                dict(bucket_bytes=4 * 64, overlap=True)])
def test_zero_step_bit_parity_fp32(shapes, kw):
    """fp32 ZeRO (RS -> sharded AdamW -> AG) must be bit-for-bit identical to
    the replicated baseline across ragged / zero-size / sub-element bucket
    layouts, for two consecutive steps (the second exercises carried m/v)."""
    from repro.optim import adamw

    base, z, params, batch = _toy_zero_steps(shapes, **kw)
    bo = adamw.init_opt_state(params)
    zo = z.init_opt_state(params)
    ze = z.init_error_state(params)
    bp, bo, bm = params, bo, None
    zp, zo, zm = params, zo, None
    for _ in range(2):
        bp, bo, bm, _ = base(bp, bo, batch, base.init_error_state(params))
        zp, zo, zm, ze = z(zp, zo, batch, ze)
        for k in bp:
            np.testing.assert_array_equal(np.asarray(bp[k]), np.asarray(zp[k]))
        # the psum-combined global norm equals the replicated one up to
        # summation order: the baseline adds per-leaf sums of squares, ZeRO
        # sums the padded carrier shard in one reduction.  Each fp32 sum of
        # n terms is within (n - 1) * 2**-24 of exact (relative, the terms
        # being squares), and the sqrt halves that, so the two norms differ
        # by at most (n - 1) * 2**-24.  The params above stay bit-for-bit.
        # Exact-bit norms with controlled values:
        # test_zero_step_bit_parity_active_clip
        n = sum(int(np.prod(s)) for s in shapes)
        np.testing.assert_allclose(np.asarray(bm["grad_norm"]),
                                   np.asarray(zm["grad_norm"]),
                                   rtol=max(n - 1, 1) * 2.0 ** -24)
        assert int(zo["step"]) == int(bo["step"])


def test_zero_step_bit_parity_active_clip():
    """Global-norm clipping regression (satellite): with exactly-representable
    sums of squares the psum-combined shard norm is bit-identical to the
    replicated norm, the clip factor *actively* rescales (gnorm >> clip_norm),
    and two steps of clipped updates stay bit-for-bit."""
    from jax.sharding import AxisType
    from repro.optim import adamw
    from repro.runtime import steps as rsteps

    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    opt = adamw.OptConfig(peak_lr=1e-2, warmup_steps=0, decay_steps=10,
                          clip_norm=1.0)
    # s = 12 -> every grad element is 2*(s-1) = 22, gnorm = sqrt(4*484) = 44
    # exactly; all partial sums are small integers, so any reduction order
    # produces the same bits and the clip factor matches bitwise
    params = {"w0": jnp.full((4,), 3.0, jnp.float32)}
    batch = {"x": jnp.ones((2,), jnp.float32)}
    base = rsteps.build_explicit_dp_step(_ToyModel(), opt, mesh, "data")
    z = rsteps.build_explicit_dp_step(_ToyModel(), opt, mesh, "data",
                                      zero=True, bucket_bytes=4 * 8)
    bp, bo, bm = params, adamw.init_opt_state(params), None
    zp, zo, ze = params, z.init_opt_state(params), z.init_error_state(params)
    for i in range(2):
        bp, bo, bm, _ = base(bp, bo, batch, base.init_error_state(params))
        zp, zo, zm, ze = z(zp, zo, batch, ze)
        np.testing.assert_array_equal(np.asarray(bp["w0"]),
                                      np.asarray(zp["w0"]))
        np.testing.assert_array_equal(np.asarray(bm["grad_norm"]),
                                      np.asarray(zm["grad_norm"]))
        if i == 0:
            assert float(bm["grad_norm"]) == 44.0  # clip active: 44 >> 1.0


def test_zero_step_int8_ag_close():
    """int8 AG leg: params stay within one quantization step of the fp32
    baseline (<5e-2 on O(1) toy values)."""
    from repro.optim import adamw

    base, z, params, batch = _toy_zero_steps([(7, 3), (1000,), (13,)],
                                             bucket_bytes=4 * 64,
                                             overlap=True, compress_bits=8)
    bp, _, _, _ = base(params, adamw.init_opt_state(params), batch,
                       base.init_error_state(params))
    zp, _, _, _ = z(params, z.init_opt_state(params), batch,
                    z.init_error_state(params))
    d = max(float(jnp.max(jnp.abs(bp[k] - zp[k]))) for k in params
            if bp[k].size)
    assert d < 5e-2, d


def test_zero_rejects_per_tensor():
    from jax.sharding import AxisType
    from repro.optim import adamw
    from repro.runtime import steps as rsteps

    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    with pytest.raises(ValueError, match="per-tensor"):
        rsteps.build_explicit_dp_step(_ToyModel(), adamw.OptConfig(), mesh,
                                      "data", zero=True, bucket_bytes=0)


def test_zero_opt_state_shapes_and_spec():
    """Carrier-sharded m/v geometry: (n_buckets, padded) fp32, padded to a
    multiple of the shard unit; the step advertises the shard spec tag and the
    abstract state mirrors the concrete one."""
    from jax.sharding import AxisType
    from repro.optim import adamw
    from repro.runtime import steps as rsteps

    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    params = {"a": jnp.ones((100,)), "b": jnp.ones((30,))}
    s = rsteps.build_explicit_dp_step(_ToyModel(), adamw.OptConfig(), mesh,
                                      "data", zero=True, bucket_bytes=4 * 64)
    o = s.init_opt_state(params)
    assert o["m"].shape == (3, 64) and o["m"].dtype == jnp.float32
    assert o["v"].shape == o["m"].shape
    assert o["step"].shape == () and o["step"].dtype == jnp.int32
    a = s.abstract_opt_state(params)
    assert a["m"].shape == o["m"].shape and a["m"].dtype == o["m"].dtype
    assert s.zero and s.opt_shard_spec == "zero-carrier:data"
    # err is a placeholder scalar (no error feedback on the param leg)
    assert s.init_error_state(params).shape == ()
    # non-zero steps keep the replicated adamw state and no spec tag
    s0 = rsteps.build_explicit_dp_step(_ToyModel(), adamw.OptConfig(), mesh,
                                       "data")
    assert not s0.zero and s0.opt_shard_spec is None
    o0 = s0.init_opt_state(params)
    assert jax.tree.structure(o0["m"]) == jax.tree.structure(params)


def test_zero_step_dispatches_rs_ag_no_gradient_allreduce():
    """The acceptance jaxpr property, trace-time: a zero step dispatches
    reduce_scatter + all_gather through the plan and *no* gradient allreduce —
    every remaining psum in the jaxpr is scalar-only (the loss pmean and the
    clip-norm combine)."""
    from jax.sharding import AxisType
    from repro.core.autotune import CollectivePolicy
    from repro.optim import adamw
    from repro.runtime import steps as rsteps

    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    policy = CollectivePolicy.from_model()
    plan = policy._as_plan()
    params = {f"w{i}": jnp.ones((65,), jnp.float32) for i in range(4)}
    batch = {"x": jnp.ones((2,), jnp.float32)}
    opt = adamw.OptConfig()
    step = rsteps.build_explicit_dp_step(_ToyModel(), opt, mesh, "data",
                                         zero=True, policy=policy,
                                         bucket_bytes=4 * 64)
    plan.reset_stats()
    jx = jax.make_jaxpr(lambda p, o, b, e: step(p, o, b, e))(
        params, step.init_opt_state(params), batch,
        step.init_error_state(params))
    assert plan.stats.get("reduce_scatter_calls", 0) > 0
    assert plan.stats.get("all_gather_calls", 0) > 0
    assert plan.stats.get("all_reduce_calls", 0) == 0

    # every psum operand is scalar: no full-gradient allreduce anywhere —
    # the CommLint non-scalar-psum / full-gradient-allreduce-under-zero rules
    # over the structured trace (analysis.trace replaces the hand-rolled walk)
    from repro.analysis import expected_trace, lint_trace, trace_jaxpr

    tr = trace_jaxpr(jx, donate_argnums=step.donate_argnums)
    assert all(r.scalar for r in tr.of_kind("psum"))
    findings = lint_trace(tr, expected_trace(step.program, plan=policy))
    assert not findings, [str(f) for f in findings]

    # the replicated baseline, for contrast, does allreduce gradients
    plan.reset_stats()
    base = rsteps.build_explicit_dp_step(_ToyModel(), opt, mesh, "data",
                                         policy=policy)
    jax.make_jaxpr(lambda p, o, b, e: base(p, o, b, e))(
        params, adamw.init_opt_state(params), batch,
        base.init_error_state(params))
    assert plan.stats.get("all_reduce_calls", 0) > 0


# ------------------------------------------------------ ZeRO wire accounting
def test_zero_wire_bytes_ratio():
    """Planned DP wire bytes of the three-phase schedule: fp32 legs land at
    (n-1)/n of the allreduce baseline, and the int8 AG leg at n=8 crosses the
    <=0.6x acceptance line (the asymmetry is documented: logical 2x baseline
    vs realized ring legs)."""
    acc = wr.zero_wire_bytes(1 << 30, 8, ag_fmt="fp32")
    assert acc["ratio"] == pytest.approx(7 / 8)
    assert acc["reduce_scatter"] == acc["all_gather"]
    acc8 = wr.zero_wire_bytes(1 << 30, 8, ag_fmt="int8", n_buckets=64)
    assert acc8["ratio"] <= 0.6
    assert acc8["ratio"] == pytest.approx(
        (7 / 8 + 7 / 8 * 0.25) / 2, rel=1e-3)
    assert acc8["total"] < acc["total"] < acc["allreduce_fp32"]


def test_choose_zero_ag_format_no_gather_gate():
    """The ZeRO AG leg realizes the idealized multiplier at any n, so a
    bandwidth-bound intra tier compresses even at n >= 8 — exactly where
    `choose_wire`'s realized-gather gate keeps the allreduce wire fp32."""
    slow8 = ov.PipelineParams(n_ici=8, alpha_ici=2e-6, bw_ici=1e9,
                              alpha_dcn=1e-5, bw_dcn=25e9)
    assert wr.choose_wire(slow8, float(16 << 20)).intra == "fp32"
    zspec = wr.choose_zero_ag_format(slow8, float(16 << 20))
    assert zspec.intra == "int8" and zspec.inter == "int8"
    assert wr.choose_zero_ag_format(slow8, float(16 << 20),
                                    allow_lossy=False) == wr.WireSpec()
