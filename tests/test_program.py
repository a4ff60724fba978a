"""StepProgram IR: construction, validation, JSON round-trip, plan/policy
persistence, program-vs-schedule pricing parity, and the bit-parity matrix of
program-built vs legacy flag-built steps."""
import dataclasses
import json

import pytest

from repro.core import program as prg
from repro.core.commplan import CommPlan
from repro.core.costmodel import exposed_comm_time, make_comm_model
from repro.core.scenarios import synthetic_grad_sizes
from repro.core.topology import make_paper_systems, make_tpu_multipod, make_tpu_pod

from .helpers import run_devices


# ------------------------------------------------------------ construction
def test_named_programs_validate_and_roundtrip():
    for name in sorted(prg.NAMED_PROGRAMS):
        p = prg.named_program(name)
        assert p.validate() is p
        back = prg.StepProgram.from_dict(json.loads(json.dumps(p.to_dict())))
        assert back == p, name
    with pytest.raises(ValueError, match="unknown program"):
        prg.named_program("ring")


def test_schedule_strings():
    assert prg.train_step_program().schedule == "allreduce"
    assert prg.train_step_program(zero=True).schedule == "zero"
    assert prg.moe_step_program().schedule == "moe_alltoall"


def test_train_program_mirrors_engine_defaulting():
    """The flag->node defaulting the engine used is now pinned in the builder:
    compress-only stays per-tensor (no Bucketize node), everything else
    buckets at the plan crossover."""
    assert not prg.train_step_program(compress_bits=8).has("bucketize")
    assert prg.train_step_program().has("bucketize")
    assert prg.train_step_program(overlap=True, compress_bits=8).has("bucketize")
    assert not prg.train_step_program(bucket_bytes=0).has("bucketize")
    bz = prg.train_step_program(overlap=True, bucket_bytes=1 << 20).node("bucketize")
    assert bz.reverse and bz.bucket_bytes == 1 << 20
    assert prg.train_step_program(zero=True).schedule == "zero"


def test_step_kwargs_roundtrip():
    """train_step_program(**p.step_kwargs()) rebuilds the same program — the
    lowering the runtime shim relies on."""
    cases = [
        dict(),
        dict(bucket_bytes=0),
        dict(compress_bits=8),
        dict(overlap=True),
        dict(overlap=True, compress_bits=8, bucket_bytes=1 << 20),
        dict(overlap=True, microbatches=4, chunks=2),
        dict(zero=True),
        dict(zero=True, compress_bits=8),
    ]
    for case in cases:
        p = prg.train_step_program(**case)
        assert prg.train_step_program(**p.step_kwargs()) == p, case


# -------------------------------------------------------------- validation
def test_validate_rejections():
    with pytest.raises(ValueError, match="bits"):
        prg.StepProgram("p", (prg.QuantizeWire(4), prg.AllReduce())).validate()
    with pytest.raises(ValueError, match="overlap schedule"):
        prg.StepProgram("p", (prg.MicrobatchLoop(2), prg.AllReduce())).validate()
    with pytest.raises(ValueError, match="per-tensor"):
        prg.StepProgram("p", (prg.Bucketize(0, reverse=True),
                              prg.AllReduce())).validate()
    with pytest.raises(ValueError, match="ZeRO"):
        prg.StepProgram("p", (prg.Bucketize(), prg.ShardedOptimUpdate())).validate()
    with pytest.raises(ValueError, match="ZeRO"):
        prg.StepProgram("p", (prg.ReduceScatter(), prg.AllGather())).validate()
    with pytest.raises(ValueError, match="dispatch"):
        prg.StepProgram("p", (prg.AllToAll("dispatch"),
                              prg.AllReduce())).validate()
    with pytest.raises(ValueError, match="router"):
        prg.StepProgram("p", (prg.AllToAll("dispatch"),
                              prg.AllToAll("combine"))).validate()
    with pytest.raises(ValueError, match="reduction"):
        prg.StepProgram("p", (prg.Bucketize(),)).validate()
    with pytest.raises(ValueError, match="unknown"):
        prg.StepProgram.from_dict({"name": "p", "nodes": [{"kind": "warp"}]})


# -------------------------------------------------------- plan persistence
def test_commplan_carries_default_program():
    plan = CommPlan.from_topology(make_tpu_pod())
    p = plan.step_program()
    assert p is not None and p.has("all_reduce")
    blob = plan.to_blob()
    assert blob["program"] == p.to_dict()
    assert CommPlan.from_blob(blob).step_program() == p


def test_policy_program_roundtrip(tmp_path):
    """Programs persist in the policy JSON: save -> load returns the same
    StepProgram object value (satellite: one artifact for all consumers)."""
    from repro.core.autotune import CollectivePolicy

    pol = CollectivePolicy.from_model(make_comm_model("leonardo"))
    pol.set_program(prg.named_program("zero_int8"))
    path = tmp_path / "policy.json"
    pol.save(str(path))
    loaded = CollectivePolicy.load(str(path))
    assert loaded.program == prg.named_program("zero_int8")
    # legacy table-only policies stay program-less
    legacy = CollectivePolicy({2: []}, {2: []}, {"source": "measured"})
    assert legacy.program is None


# ---------------------------------------------------------------- pricing
def test_program_pricing_matches_schedule_shim():
    """One IR, two consumers: pricing a program must equal the legacy
    schedule-string branch it replaced, for both dense schedules, on flat and
    hierarchical plans."""
    sizes = synthetic_grad_sizes(64 << 20)
    for topo, n in ((make_tpu_pod(), 8), (make_tpu_multipod(), 512)):
        plan = CommPlan.from_topology(topo)
        for schedule, program in [
            ("allreduce", prg.train_step_program()),
            ("zero", prg.train_step_program(zero=True)),
        ]:
            a = exposed_comm_time(0.01, plan, sizes, n_endpoints=n,
                                  schedule=schedule)
            b = exposed_comm_time(0.01, plan, sizes, n_endpoints=n,
                                  program=program)
            assert a == b, (schedule, n)


def test_program_pricing_node_overrides():
    """Program nodes carry the knobs: an explicit Bucketize size overrides the
    plan's crossover, and QuantizeWire implies the int8 wire."""
    plan = CommPlan.from_topology(make_paper_systems()["leonardo"])
    sizes = synthetic_grad_sizes(64 << 20)
    base = exposed_comm_time(0.01, plan, sizes, n_endpoints=512)
    p8 = prg.train_step_program(compress_bits=8, bucket_bytes=1 << 20)
    est8 = exposed_comm_time(0.01, plan, sizes, n_endpoints=512, program=p8)
    # QuantizeWire implies the lossy intra wire; Bucketize(1 MiB) repacks the
    # 64 MiB gradient into 64 buckets instead of the plan's crossover
    assert est8.wire == "int8/fp32" and base.wire == "fp32/fp32"
    assert est8.n_buckets == 64 and base.n_buckets != est8.n_buckets
    with pytest.raises(ValueError, match="schedule"):
        exposed_comm_time(0.01, plan, sizes, n_endpoints=8, schedule="ring")


def test_moe_program_priced_finite_at_scale():
    plan = CommPlan.from_topology(make_paper_systems()["alps"])
    est = exposed_comm_time(0.0, plan, [4 << 20, 4 << 20, 1 << 20],
                            n_endpoints=4096, model=make_comm_model("alps"),
                            program=prg.moe_step_program())
    assert est.schedule == "moe_alltoall"
    assert 0.0 < est.total_comm_s < float("inf")
    assert est.exposed_s == est.total_comm_s  # token exchanges gate the forward


# ----------------------------------------------------- launcher resolution
def test_resolve_step_program_flags():
    """The consolidated launcher resolution: implications, error messages, and
    the XLA path returning no program."""
    import argparse

    from repro.launch.train import resolve_step_program

    def ns(**kw):
        base = dict(explicit_dp=False, overlap=False, zero=False,
                    compress_bits="0", chunks=None, microbatches=1,
                    bucket_bytes=None)
        base.update(kw)
        return argparse.Namespace(**base)

    assert resolve_step_program(ns(), None, None) == (None, None)
    with pytest.raises(SystemExit, match="multiple devices"):
        resolve_step_program(ns(overlap=True), None, None)
    with pytest.raises(SystemExit, match="want 0, 8, or auto"):
        resolve_step_program(ns(compress_bits="bf16"), None, None)
    with pytest.raises(SystemExit, match="needs --explicit-dp"):
        resolve_step_program(ns(compress_bits="8"), None, None)


# ----------------------------------------------- bit-parity matrix (multi-dev)
PARITY = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.core import program as prg
from repro.models import build_model
from repro.optim import adamw
from repro.runtime import steps as rsteps

from repro.analysis import expected_trace, lint_trace, trace_jaxpr

cfg = get_config("smollm-135m").reduced()
shape = ShapeConfig("t", 32, 8, "train")
mesh = jax.make_mesh((4,), ("data",), axis_types=(AxisType.Auto,))
mesh2 = jax.make_mesh((2, 2), ("pod", "data"), axis_types=(AxisType.Auto,)*2)
model = build_model(cfg)
opt = adamw.OptConfig(peak_lr=1e-3, warmup_steps=1, decay_steps=20)
params = model.init(jax.random.PRNGKey(0))
batch = model.make_batch(shape)

CASES = [
    (dict(), None),
    (dict(bucket_bytes=0), None),
    (dict(compress_bits=8), None),
    (dict(overlap=True, bucket_bytes=1 << 20), None),
    (dict(overlap=True, compress_bits=8, bucket_bytes=1 << 20), None),
    (dict(overlap=True, microbatches=2, bucket_bytes=1 << 20), None),
    (dict(zero=True, bucket_bytes=1 << 20), None),
    (dict(zero=True, compress_bits=8, bucket_bytes=1 << 20), None),
    (dict(overlap=True, chunks=2, bucket_bytes=1 << 20), "pod"),
]

for flags, dcn in CASES:
    m = mesh2 if dcn else mesh
    legacy = rsteps.build_explicit_dp_step(model, opt, m, "data",
                                           dcn_axis=dcn, **flags)
    program = prg.train_step_program(**flags)
    built = rsteps.build_program_step(model, opt, m, program, axis="data",
                                      dcn_axis=dcn)
    assert built.program == program and legacy.program == program, flags
    outs = []
    for step in (legacy, built):
        if getattr(step, "zero", False):
            ostate = step.init_opt_state(params)
        else:
            ostate = adamw.init_opt_state(params)
        err = step.init_error_state(params)
        # CommLint: both builds honour the shared program's collective contract
        jx = jax.make_jaxpr(lambda p, o, b, e: step(p, o, b, e))(
            params, ostate, batch, err)
        tr = trace_jaxpr(jx, donate_argnums=getattr(step, "donate_argnums", ()))
        fs = lint_trace(tr, expected_trace(program, n_devices=4, dcn_axis=dcn))
        assert not fs, (flags, [str(f) for f in fs])
        p2, _, metrics, _ = step(params, ostate, batch, err)
        outs.append((jax.device_get(p2), float(metrics["loss"])))
    (pa, la), (pb, lb) = outs
    assert la == lb, (flags, la, lb)
    la_, lb_ = jax.tree.leaves(pa), jax.tree.leaves(pb)
    if flags.get("compress_bits", 0) == 0:
        ok = all(np.array_equal(np.asarray(a), np.asarray(b))
                 for a, b in zip(la_, lb_))
        assert ok, ("fp32 wire must be bit-identical", flags)
    else:
        d = max(float(np.max(np.abs(np.asarray(a, np.float32)
                                    - np.asarray(b, np.float32))))
                for a, b in zip(la_, lb_))
        assert d < 5e-2, (flags, d)
    print("parity ok", flags, "dcn" if dcn else "flat")
print("ALL_OK")
"""


@pytest.mark.slow
def test_program_vs_flag_step_parity_matrix():
    """Program-built and legacy flag-built steps are the same step: bit-equal
    params on the fp32 wire across (overlap x zero x compress x chunks), and
    within codec tolerance at int8."""
    assert "ALL_OK" in run_devices(PARITY, 4, timeout=560)
