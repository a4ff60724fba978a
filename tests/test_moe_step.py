"""Expert-parallel MoE step: planned alltoall dispatch/combine through the
StepProgram IR — payload accounting, the at-scale sweep oracles, and the live
multi-device step (jaxpr + plan-stats + executed-path asserts)."""
import pytest

from repro.core import program as prg
from repro.core import scenarios as sc
from repro.core.topology import make_paper_systems

from .helpers import run_devices


# ------------------------------------------------------------ payload math
def test_expert_dims_rejects_dense_config():
    from repro.configs.base import get_config
    from repro.runtime.moe_step import expert_dims

    with pytest.raises(ValueError, match="not a MoE config"):
        expert_dims(get_config("smollm-135m"))


def test_dispatch_bytes_is_the_table_key():
    """The sweep, the oracle, and the runtime must consult the plan with the
    same number: one (E, b*C, D) fp32 buffer."""
    from repro.configs.base import get_config
    from repro.models.moe import _capacity
    from repro.runtime.moe_step import dispatch_bytes

    cfg = get_config("deepseek-moe-16b").reduced()
    b, S = 2, 16
    C = _capacity(S, cfg)
    assert dispatch_bytes(cfg, b, S) == cfg.n_experts * b * C * cfg.d_model * 4


# ------------------------------------------------------------- sweep oracles
@pytest.mark.parametrize("system", sc.PAPER_SYSTEMS)
def test_check_moe_shapes(system):
    shapes = sc.check_moe_shapes(system)
    bad = [k for k, v in shapes.items() if not v]
    assert not bad, (system, shapes)


def test_moe_sweep_forces_pairwise_at_scale():
    """Obs. 7 through the sweep: every point beyond 512 endpoints (or across a
    group boundary) dispatches the bounded-state pairwise schedule."""
    pts = sc.sweep_moe_alltoall("alps")
    assert pts[-1].n_endpoints == 4096
    assert all(p.algo == "pairwise" for p in pts if p.n_endpoints > 512)
    assert all(p.algo == "pairwise" for p in pts if p.tier == "diff_group")
    assert all(p.step_comm_s >= 4.0 * p.exchange_s * (1 - 1e-9) for p in pts)


def test_moe_expert_placement_confines_to_group():
    topo = make_paper_systems()["alps"]
    group, replicas = sc.moe_expert_placement(topo, 4096)
    assert group * replicas == 4096
    assert replicas > 1, "4096 endpoints span dragonfly groups: must replicate"
    assert topo.tier_for_scale(group) != "diff_group"
    # small jobs fit in one group: no replication
    g8, r8 = sc.moe_expert_placement(topo, 8)
    assert (g8, r8) == (8, 1)
    # confined sweep never leaves the group tier
    conf = sc.sweep_moe_alltoall("alps", confine=True)
    assert all(p.tier != "diff_group" for p in conf)
    assert all(p.ep_group * p.n_replicas == n
               for p, n in zip(conf, sc.DEFAULT_ENDPOINTS))


def test_moe_program_shape():
    p = prg.moe_step_program()
    roles = [nd.role for nd in p.nodes if nd.kind == "all_to_all"]
    assert roles == ["dispatch", "combine"]
    assert p.has("all_reduce") and p.schedule == "moe_alltoall"
    assert prg.moe_step_program(compress_bits=8).name == "moe_alltoall_int8"


# ------------------------------------------------------- runtime (multi-dev)
MOE_STEP = r"""
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType
from repro.configs import get_config
from repro.core import scenarios as sc
from repro.core.autotune import CollectivePolicy
from repro.optim import adamw
from repro.runtime import moe_step as ms
from repro.runtime import steps as rsteps

# the shared walker (analysis.trace) replaced this file's hand-rolled copy
from repro.analysis import expected_trace, lint_trace, prims_of, trace_jaxpr

cfg = get_config("deepseek-moe-16b").reduced()
mesh = jax.make_mesh((4,), ("data",), axis_types=(AxisType.Auto,))
opt = adamw.OptConfig(peak_lr=1e-3, warmup_steps=1, decay_steps=20)
params = ms.moe_ep_params(cfg, jax.random.PRNGKey(0))
batch = ms.moe_ep_batch(cfg, jax.random.PRNGKey(1), 8, 16)
ostate = adamw.init_opt_state(params)

# --- planned alltoall in the jaxpr + per-algo plan stats (default: xla) ---
policy = CollectivePolicy.from_model()
plan = policy._as_plan()
plan.reset_stats()
step = ms.build_moe_ep_step(cfg, opt, mesh, policy=policy)
err = step.init_error_state(params)
jx = jax.make_jaxpr(lambda p, o, b, e: step(p, o, b, e))(
    params, ostate, batch, err)
prims = prims_of(jx)
assert "all_to_all" in prims, prims
assert plan.stats.get("all_to_all_calls") == 2, plan.stats
assert plan.stats.get("all_to_all_algo/xla") == 2, plan.stats
assert plan.stats.get("all_reduce_calls", 0) >= 1, plan.stats
# CommLint: the traced MoE step stays inside its program's collective set
# (dispatch + combine, plus the vjp's transposed exchanges)
tr = trace_jaxpr(jx)
assert len(tr.of_kind("all_to_all")) >= 2, tr.counts()
fs = lint_trace(tr, expected_trace(step.program, n_devices=4, plan=policy))
assert not fs, [str(f) for f in fs]
print("ok jaxpr xla", sorted(k for k in plan.stats))

# --- group boundary forces pairwise: ppermute rotations, no fused alltoall ---
plan_pw = dataclasses.replace(plan, tiers={4: "diff_group"})
plan_pw.reset_stats()
pol_pw = CollectivePolicy.from_plan(plan_pw)
step_pw = ms.build_moe_ep_step(cfg, opt, mesh, policy=pol_pw)
jx_pw = jax.make_jaxpr(lambda p, o, b, e: step_pw(p, o, b, e))(
    params, ostate, batch, err)
prims_pw = prims_of(jx_pw)
assert "ppermute" in prims_pw, prims_pw
assert "all_to_all" not in prims_pw, prims_pw
assert plan_pw.stats.get("all_to_all_algo/pairwise") == 2, plan_pw.stats
# pairwise lowers to ppermute rotations — still within the program's set
fs_pw = lint_trace(trace_jaxpr(jx_pw),
                   expected_trace(step_pw.program, n_devices=4, plan=pol_pw))
assert not fs_pw, [str(f) for f in fs_pw]
print("ok jaxpr pairwise")

# --- numerics: loss decreases, and n=4 matches n=1 (same global batch) ---
p1, o1, m1, _ = step(params, ostate, batch, err)
p2, o2, m2, _ = step(p1, o1, batch, err)
assert float(m2["loss"]) < float(m1["loss"]), (m1["loss"], m2["loss"])
assert np.isfinite(float(m1["aux_loss"]))
mesh1 = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
step1 = ms.build_moe_ep_step(cfg, opt, mesh1, policy=CollectivePolicy.from_model())
q1, _, n1, _ = step1(params, ostate, batch, err)
assert abs(float(n1["loss"]) - float(m1["loss"])) < 1e-5
d = max(float(np.max(np.abs(np.asarray(jax.device_get(a), np.float32)
                            - np.asarray(jax.device_get(b), np.float32))))
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(q1)))
assert d < 1e-5, d
print("ok numerics", float(m1["loss"]), "->", float(m2["loss"]), "d:", d)

# --- the program-first entry point routes AllToAll programs to this step ---
routed = rsteps.build_program_step(cfg, opt, mesh, ms.prg.moe_step_program(),
                                   axis="data",
                                   policy=CollectivePolicy.from_model())
rp, _, rm, _ = routed(params, ostate, batch, err)
assert abs(float(rm["loss"]) - float(m1["loss"])) < 1e-6
assert routed.program.name == "moe_alltoall"
assert step.program.schedule == "moe_alltoall"
print("ok routing")

# --- executed path matches the sweep's table ranking (satellite oracle) ---
out = sc.moe_executed_path_oracle(cfg, mesh)
assert out["match"], out
print("ok oracle", out)

# --- expert count must divide the EP axis ---
try:
    ms.build_moe_ep_step(dataclasses.replace(cfg, n_experts=6), opt, mesh)
except ValueError as e:
    assert "divide" in str(e)
else:
    raise AssertionError("n_experts=6 over 4 devices must be rejected")
print("ALL_OK")
"""


@pytest.mark.slow
def test_moe_ep_step_live():
    assert "ALL_OK" in run_devices(MOE_STEP, 4, timeout=560)
