"""CommPlan: topology-derived tables, persistence compat, bucketed explicit DP."""
import json
import math

import pytest

from repro.core import collectives as coll
from repro.core.autotune import CollectivePolicy, PolicyEntry
from repro.core.commplan import (CommPlan, MAX_BUCKET_BYTES, MIN_BUCKET_BYTES,
                                 PlanEntry)
from repro.core.topology import make_paper_node_graphs, make_tpu_multipod, make_tpu_pod

from .helpers import run_devices


# ------------------------------------------------------------------- registry
def test_registry_has_all_algorithms():
    ar = coll.registered("all_reduce")
    assert {"ring", "bidir_ring", "rabenseifner", "recursive_doubling", "tree",
            "one_shot", "xla", "hierarchical"} <= set(ar)
    assert ar["hierarchical"].multi_axis
    assert ar["rabenseifner"].pow2_only
    # single-axis views exclude multi-axis variants
    assert "hierarchical" not in coll.ALL_REDUCE_ALGOS
    assert "bidir_ring" in coll.ALL_REDUCE_ALGOS
    assert set(coll.REDUCE_SCATTER_ALGOS) == {"ring", "xla"}
    assert set(coll.ALL_GATHER_ALGOS) == {"ring", "xla"}


def test_registry_unknown_name_raises():
    with pytest.raises(KeyError, match="no 'all_reduce' collective"):
        coll.get_collective("all_reduce", "nope")


# ------------------------------------------------------- topology -> tables
def test_plans_distinct_across_topologies():
    lumi = CommPlan.from_topology(make_paper_node_graphs()["lumi"])
    mp = CommPlan.from_topology(make_tpu_multipod())
    assert lumi.all_reduce_table != mp.all_reduce_table
    assert not lumi.hierarchical and mp.hierarchical
    assert lumi.meta["topology"] == "lumi_node"
    assert mp.meta["topology"].startswith("v5e_pod")


def test_tables_shaped_like_obs1():
    """Latency-optimal small, bandwidth-optimal large, for every axis size."""
    plan = CommPlan.from_topology(make_tpu_pod())
    for n, entries in plan.all_reduce_table.items():
        assert entries[-1].max_bytes == 1 << 62
        if n >= 8:
            small = plan.all_reduce_algo(256, n)
            large = plan.all_reduce_algo(1 << 28, n)
            assert small in ("one_shot", "recursive_doubling", "tree")
            assert large in ("ring", "bidir_ring", "rabenseifner")


def test_hierarchical_dispatch_selection():
    mp = CommPlan.from_topology(make_tpu_multipod())
    assert mp.all_reduce_algo(1 << 20, 256, dcn=True) == "hierarchical"
    # single-level plans never pick it, even when asked about the dcn path
    lumi = CommPlan.from_topology(make_paper_node_graphs()["lumi"])
    assert lumi.all_reduce_algo(1 << 20, 8, dcn=True) != "hierarchical"


def test_pow2_fallback_on_odd_axis():
    plan = CommPlan.from_topology(make_tpu_pod())
    algo = plan.all_reduce_algo(1 << 28, 6)
    spec = coll.registered("all_reduce")[algo]
    assert not spec.pow2_only


def test_alltoall_forced_pairwise_beyond_512():
    plan = CommPlan.from_topology(make_tpu_multipod())
    assert plan.all_to_all_algo(1 << 20, 1024) == "pairwise"


def test_bucket_bytes_from_crossover():
    for topo in (make_paper_node_graphs()["lumi"], make_tpu_multipod()):
        plan = CommPlan.from_topology(topo)
        assert MIN_BUCKET_BYTES <= plan.bucket_bytes <= MAX_BUCKET_BYTES
        assert plan.bucket_bytes & (plan.bucket_bytes - 1) == 0  # power of two


# ---------------------------------------------------------------- persistence
def test_plan_json_roundtrip(tmp_path):
    plan = CommPlan.from_topology(make_tpu_multipod())
    f = tmp_path / "plan.json"
    plan.save(str(f))
    back = CommPlan.load(str(f))
    assert back.all_reduce_table == plan.all_reduce_table
    assert back.reduce_scatter_table == plan.reduce_scatter_table
    assert back.bucket_bytes == plan.bucket_bytes
    assert back.hierarchical == plan.hierarchical


def test_policy_roundtrip_new_format(tmp_path):
    p = CollectivePolicy.from_model()
    f = tmp_path / "policy.json"
    p.save(str(f))
    q = CollectivePolicy.load(str(f))
    for n in p.all_reduce_table:
        for nbytes in (1024, 1 << 20, 1 << 28):
            assert p.all_reduce_algo(nbytes, n) == q.all_reduce_algo(nbytes, n)
    assert q.bucket_bytes == p.bucket_bytes
    assert q.plan.hierarchical == p.plan.hierarchical


def test_policy_load_legacy_format(tmp_path):
    """Old (pre-CommPlan) policy files: all_reduce/all_to_all/meta only."""
    legacy = {
        "meta": {"source": "model"},
        "all_reduce": {"8": [{"max_bytes": 65536, "algorithm": "recursive_doubling"},
                             {"max_bytes": 1 << 62, "algorithm": "ring"}]},
        "all_to_all": {"8": [{"max_bytes": 1 << 62, "algorithm": "xla"}]},
    }
    f = tmp_path / "legacy.json"
    f.write_text(json.dumps(legacy))
    p = CollectivePolicy.load(str(f))
    assert p.all_reduce_algo(1024, 8) == "recursive_doubling"
    assert p.all_reduce_algo(1 << 28, 8) == "ring"
    assert p.all_to_all_algo(1024, 8) == "xla"
    # plan-only fields come back as safe defaults
    assert not p.plan.hierarchical
    assert p.bucket_bytes > 0
    assert isinstance(p.all_reduce_table[8][0], PolicyEntry)


def test_legacy_entry_alias():
    # PolicyEntry must remain the same dataclass as PlanEntry (shared tables)
    assert PolicyEntry is PlanEntry


# -------------------------------------------------- bucketing + dispatch e2e
BUCKETED_DP = r"""
import math
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.models import build_model
from repro.optim import adamw
from repro.runtime import steps as rsteps
from repro.core.autotune import CollectivePolicy
from repro.core.commplan import CommPlan
from repro.core.topology import make_tpu_multipod

cfg = get_config("smollm-135m").reduced()
shape = ShapeConfig("t", 32, 8, "train")
model = build_model(cfg)
opt = adamw.OptConfig(peak_lr=1e-3, warmup_steps=1, decay_steps=20)
params = model.init(jax.random.PRNGKey(0))
ostate = adamw.init_opt_state(params)
batch = model.make_batch(shape)
err = rsteps.init_error_state(params)
tonp = lambda t: [np.asarray(jax.device_get(a)).astype(np.float32)
                  for a in jax.tree.leaves(t)]

mesh = jax.make_mesh((4,), ("data",), axis_types=(AxisType.Auto,))
pol = CollectivePolicy.from_model()
total_bytes = sum(p.size for p in jax.tree.leaves(params)) * 4
bucket = 1 << 20

step0 = rsteps.build_explicit_dp_step(model, opt, mesh, "data", policy=pol,
                                      bucket_bytes=0)
p0, o0, m0, _ = step0(params, ostate, batch, err)
pol.plan.reset_stats()
step1 = rsteps.build_explicit_dp_step(model, opt, mesh, "data", policy=pol,
                                      bucket_bytes=bucket)
p1, o1, m1, _ = step1(params, ostate, batch, err)

# bucketing is a pure re-chunking: identical numerics
assert abs(float(m0["loss"]) - float(m1["loss"])) < 1e-6
d = max(np.max(np.abs(a - b)) for a, b in zip(tonp(p0), tonp(p1)))
assert d < 1e-6, d
# and <= ceil(total/bucket) + 1 all-reduces (trace-time counter)
calls = pol.plan.stats["all_reduce_calls"]
assert calls <= math.ceil(total_bytes / bucket) + 1, calls
print("bucketed ok", calls)

# hierarchical dispatch on a (pod, data) mesh with a two-level plan
mesh2 = jax.make_mesh((2, 4), ("pod", "data"), axis_types=(AxisType.Auto,)*2)
plan2 = CommPlan.from_topology(make_tpu_multipod())
plan2.reset_stats()
step2 = rsteps.build_explicit_dp_step(model, opt, mesh2, "data",
                                      policy=CollectivePolicy.from_plan(plan2),
                                      bucket_bytes=bucket, dcn_axis="pod")
p2, o2, m2, _ = step2(params, ostate, batch, err)
assert plan2.stats["hierarchical_calls"] > 0
assert np.isfinite(float(m2["loss"]))
# same global batch, 8-way vs 4-way mean: grads agree modulo reassociation
d2 = max(np.max(np.abs(a - b)) for a, b in zip(tonp(p0), tonp(p2)))
assert d2 < 5e-2, d2
print("ALL_OK")
"""


@pytest.mark.slow
def test_bucketed_explicit_dp_8dev():
    assert "ALL_OK" in run_devices(BUCKETED_DP, 8, timeout=560)
