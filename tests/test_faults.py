"""FaultGuard: fault plans, the injector, the drift guard, degradation sweeps.

Unit layers run in-process; the live multi-device paths (drift-triggered
mid-run re-plan, node-loss elastic re-mesh) run in subprocesses with forced
host device counts (tests/helpers.py).
"""
import dataclasses

import numpy as np
import pytest

from repro.core.faults import (FaultEvent, FaultInjector, FaultPlan,
                               NodeLossFault, TransientFault)
from repro.runtime.guard import DriftGuard, GuardConfig

from .helpers import run_devices


# ---------------------------------------------------------------- fault plans
def test_fault_event_validation():
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultEvent(step=0, kind="gremlin")
    with pytest.raises(ValueError, match="timing"):
        FaultEvent(step=-1, kind="straggler")
    with pytest.raises(ValueError, match="timing"):
        FaultEvent(step=0, kind="straggler", duration=0)
    with pytest.raises(ValueError, match="severity"):
        FaultEvent(step=0, kind="straggler", severity=0.0)


def test_fault_event_windowing():
    win = FaultEvent(step=4, kind="link_degrade", duration=3)
    assert [s for s in range(10) if win.active_at(s)] == [4, 5, 6]
    pt = FaultEvent(step=4, kind="transient_fail")
    assert [s for s in range(10) if pt.active_at(s)] == [4]


def test_fault_plan_roundtrip_and_determinism(tmp_path):
    plan = FaultPlan.messy_fabric(seed=3, steps=24)
    # seeded builder is deterministic, and distinct across seeds
    assert plan == FaultPlan.messy_fabric(seed=3, steps=24)
    assert plan != FaultPlan.messy_fabric(seed=4, steps=24)
    # events come back sorted regardless of input order
    shuffled = FaultPlan(events=tuple(reversed(plan.events)), seed=3,
                         comm_fraction=plan.comm_fraction)
    assert shuffled == plan
    # JSON round-trip through dict and through disk
    assert FaultPlan.from_dict(plan.to_dict()) == plan
    path = tmp_path / "plan.json"
    plan.save(str(path))
    assert FaultPlan.load(str(path)) == plan
    with pytest.raises(ValueError, match="version"):
        FaultPlan.from_dict({"version": 99})


def test_fault_plan_resolve(tmp_path):
    assert FaultPlan.resolve("messy:5").seed == 5
    kinds = {e.kind for e in FaultPlan.resolve("nodeloss", steps=24).events}
    assert "node_loss" in kinds
    assert "node_loss" not in {e.kind for e in
                               FaultPlan.resolve("messy", steps=24).events}
    path = tmp_path / "p.json"
    FaultPlan.messy_fabric(seed=9).save(str(path))
    assert FaultPlan.resolve(str(path)).seed == 9
    with pytest.raises(ValueError, match="not a file and not a builtin"):
        FaultPlan.resolve("no_such_thing")


# ------------------------------------------------------------------ injector
def test_injector_deterministic_and_windowed():
    plan = FaultPlan(events=(
        FaultEvent(step=4, kind="link_degrade", duration=4, severity=3.0),
        FaultEvent(step=6, kind="latency_spike", duration=2, severity=3.0),
        FaultEvent(step=9, kind="straggler", severity=2.5),
    ), seed=7, comm_fraction=0.5)
    a, b = FaultInjector(plan), FaultInjector(plan)
    for step in range(12):
        assert a.slowdown(step) == b.slowdown(step)  # bit-identical replay
    assert a.slowdown(0) == 1.0                      # clean before any event
    assert a.slowdown(4) > 1.0                       # inside the window
    assert a.slowdown(8) == 1.0 or a.slowdown(8) > 1.0
    assert a.slowdown(20) == 1.0                     # clean after it
    # the latency spike compounds on top of the degrade where they overlap
    assert a.slowdown(6) > a.slowdown(5)
    # straggler factor is separate from the fabric factor
    fabric, straggler = a.factors(9)
    assert straggler == pytest.approx(2.5) and fabric == 1.0


def test_injector_mitigation_scales_fabric_not_straggler():
    plan = FaultPlan(events=(
        FaultEvent(step=0, kind="link_degrade", duration=4, severity=4.0),
        FaultEvent(step=2, kind="straggler", severity=3.0),
    ), comm_fraction=0.5)
    inj = FaultInjector(plan)
    before_fabric = inj.perturb(0, 1.0)
    before_both = inj.perturb(2, 1.0)
    inj.on_replan(recovered=0.6)
    # fabric excess shrinks by exactly the recovered fraction...
    assert inj.perturb(0, 1.0) == pytest.approx(1.0 + (before_fabric - 1.0) * 0.4)
    # ...while the straggler multiplier is untouched (a slow device is not a
    # routing problem)
    fabric, straggler = inj.factors(2)
    assert straggler == pytest.approx(3.0)
    assert inj.perturb(2, 1.0) < before_both
    # full recovery floors the fabric factor at 1
    inj.on_replan(recovered=1.0)
    assert inj.perturb(0, 1.0) == pytest.approx(1.0)


def test_injector_point_faults_fire_once():
    plan = FaultPlan(events=(FaultEvent(step=3, kind="transient_fail"),
                             FaultEvent(step=5, kind="node_loss", device=2)))
    inj = FaultInjector(plan)
    inj.before_step(0)
    with pytest.raises(TransientFault, match="step 3"):
        inj.before_step(3)
    inj.before_step(3)  # replayed step after restore: already fired
    with pytest.raises(NodeLossFault) as ei:
        inj.before_step(5)
    assert ei.value.lost == (2,)
    inj.before_step(5)
    assert [r["kind"] for r in inj.log] == ["transient_fail", "node_loss"]


# --------------------------------------------------------------- drift guard
def test_guard_in_band_stays_quiet():
    g = DriftGuard(GuardConfig(band=0.3, patience=2), reference_s=1.0)
    for step in range(20):
        assert g.observe(step, 1.0 + 0.1 * (step % 3)) is None
    assert g.report()["n_events"] == 0


def test_guard_self_calibrates_from_warmup_median():
    g = DriftGuard(GuardConfig(warmup=3))
    # compile-heavy first step must not inflate the reference
    for step, dt in enumerate((9.0, 1.0, 1.1)):
        g.observe(step, dt)
    assert g.reference == pytest.approx(1.1)


def test_guard_sustained_drift_replans_once_then_cools_down():
    calls = []

    def replanner(step):
        calls.append(step)
        return True, {"swapped": True}

    g = DriftGuard(GuardConfig(band=0.2, ewma=1.0, patience=3, cooldown=100,
                               warmup=1), reference_s=1.0, replanner=replanner)
    g.observe(0, 1.0)
    events = [g.observe(s, 2.0) for s in range(1, 12)]
    replans = [e for e in events if e is not None and e.kind == "replan"]
    assert len(replans) == 1 and calls == [replans[0].step]
    assert g.n_replans == 1
    # committed swap re-seeded the reference from the next warmup window:
    # the post-swap step time (2.0) is the new normal, so no further events
    assert g.reference == pytest.approx(2.0)
    assert [e for e in events if e is not None] == replans


def test_guard_rejected_swap_keeps_old_plan():
    g = DriftGuard(GuardConfig(band=0.2, ewma=1.0, patience=2, cooldown=3,
                               warmup=1),
                   reference_s=1.0,
                   replanner=lambda step: (False, {"lint": {"findings": ["x"]}}))
    events = [g.observe(s, 3.0) for s in range(10)]
    rejected = [e for e in events if e is not None and e.kind == "replan_rejected"]
    assert rejected and g.n_replans == 0
    assert g.reference == 1.0          # no rebaseline on a rejected swap
    rep = g.report()
    assert rep["n_rejected"] == len(rejected)
    assert rep["events"][0]["detail"]["lint"]["findings"] == ["x"]


def test_guard_without_replanner_emits_drift():
    g = DriftGuard(GuardConfig(band=0.2, ewma=1.0, patience=2, cooldown=1,
                               warmup=1), reference_s=1.0)
    events = [g.observe(s, 3.0) for s in range(4)]
    kinds = [e.kind for e in events if e is not None]
    assert kinds and set(kinds) == {"drift"}


def test_guard_max_replans_cap():
    g = DriftGuard(GuardConfig(band=0.2, ewma=1.0, patience=1, cooldown=1,
                               warmup=1, max_replans=1),
                   reference_s=1.0, replanner=lambda s: (True, {}))
    g.observe(0, 3.0)          # replan #1; reference re-seeds
    assert g.n_replans == 1
    for s in range(1, 8):
        g.observe(s, 3.0)      # warmup re-seed absorbs 3.0 as the new normal
    g.reference = 1.0          # force drift again against a clean reference
    events = [g.observe(s, 3.0) for s in range(8, 12)]
    assert g.n_replans == 1    # capped
    drifts = [e for e in events if e is not None]
    assert drifts and drifts[0].detail["suppressed"] == "max_replans"


# ------------------------------------------------------- degradation pricing
def test_degradation_oracles_all_pass():
    from repro.core.scenarios import check_degradation_shapes

    for system in ("leonardo", "alps"):
        oracles = check_degradation_shapes(system, endpoints=(8, 64, 1024))
        assert all(oracles.values()), (system, oracles)


def test_degradation_rejects_unknown_scenario():
    from repro.core.scenarios import sweep_degradation

    with pytest.raises(ValueError, match="unknown scenario"):
        sweep_degradation("leonardo", "solar_flare")


# ------------------------------------------------------------- live runtime
def test_guard_replan_live_multidevice():
    """Acceptance: under the canonical messy plan the guarded trainer commits
    a lint-clean mid-run re-plan and ends with strictly fewer straggler-
    exposed steps than the oblivious trainer on the same seeded fabric."""
    out = run_devices("""
import tempfile
import jax
from jax.sharding import AxisType
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.core.faults import FaultPlan
from repro.runtime.guard import GuardConfig
from repro.runtime.train import Trainer, TrainConfig

cfg = get_config("smollm-135m").reduced()
shape = ShapeConfig("t", 64, 4, "train")

def run(guard):
    mesh = jax.make_mesh((4,), ("data",), axis_types=(AxisType.Auto,))
    tc = TrainConfig(steps=24, ckpt_every=8, ckpt_async=False,
                     ckpt_dir=tempfile.mkdtemp(), log_every=100,
                     explicit_dp=True, bucket_bytes=1 << 16,
                     straggler_threshold=2.0,
                     faults=FaultPlan.messy_fabric(seed=0, steps=24),
                     guard=guard,
                     guard_cfg=GuardConfig(patience=3, cooldown=6, lint=True,
                                           max_replans=2))
    return Trainer(cfg, shape, train_cfg=tc, mesh=mesh).run()

obl = run(False)
grd = run(True)
g = grd["guard"]
replans = [e for e in g["events"] if e["kind"] == "replan"]
assert g["n_replans"] >= 1, g
for e in replans:
    lint = e["detail"].get("lint", {})
    assert lint, e                       # the swap went through the lint gate
    assert not lint["findings"], e
    assert e["detail"].get("swapped"), e
    assert e["detail"]["probe"]["records"] > 0, e
assert grd["straggler_events"] < obl["straggler_events"], (
    grd["straggler_events"], obl["straggler_events"])
print("REPLAN_OK", g["n_replans"], grd["straggler_events"],
      obl["straggler_events"])
""", n_devices=8)
    assert "REPLAN_OK" in out


def test_node_loss_elastic_remesh_live():
    """A node-loss fault mid-run rebuilds the mesh on the survivors (DP
    degree shrinks to the largest batch divisor) and finishes from the last
    checkpoint."""
    out = run_devices("""
import tempfile
import jax
from jax.sharding import AxisType
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.core.faults import FaultEvent, FaultPlan
from repro.runtime.train import Trainer, TrainConfig

cfg = get_config("smollm-135m").reduced()
shape = ShapeConfig("t", 64, 4, "train")
mesh = jax.make_mesh((4,), ("data",), axis_types=(AxisType.Auto,))
plan = FaultPlan(events=(FaultEvent(step=6, kind="node_loss", device=1),))
tc = TrainConfig(steps=10, ckpt_every=4, ckpt_async=False,
                 ckpt_dir=tempfile.mkdtemp(), log_every=100,
                 explicit_dp=True, bucket_bytes=1 << 16,
                 straggler_threshold=50.0, faults=plan)
res = Trainer(cfg, shape, train_cfg=tc, mesh=mesh).run()
assert res["final_step"] == 10, res["final_step"]
assert res["final_devices"] == 2, res["final_devices"]   # 3 survivors -> dp 2
assert [r["kind"] for r in res["fault_log"]] == ["node_loss"]
print("REMESH_OK", res["final_devices"])
""", n_devices=4)
    assert "REMESH_OK 2" in out


def test_node_loss_without_checkpoint_or_under_zero():
    """No checkpoint -> the loss surfaces; ZeRO -> the shrink refuses (the
    carrier layout depends on the DP degree)."""
    out = run_devices("""
import tempfile
import jax
from jax.sharding import AxisType
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.core.faults import FaultEvent, FaultPlan, NodeLossFault
from repro.runtime.train import Trainer, TrainConfig

cfg = get_config("smollm-135m").reduced()
shape = ShapeConfig("t", 64, 4, "train")
plan = FaultPlan(events=(FaultEvent(step=2, kind="node_loss", device=1),))

def make(**kw):
    mesh = jax.make_mesh((4,), ("data",), axis_types=(AxisType.Auto,))
    return Trainer(cfg, shape,
                   train_cfg=TrainConfig(steps=8, ckpt_async=False,
                                         ckpt_dir=tempfile.mkdtemp(),
                                         log_every=100, explicit_dp=True,
                                         bucket_bytes=1 << 16,
                                         straggler_threshold=50.0,
                                         faults=plan, **kw),
                   mesh=mesh)

try:
    make(ckpt_every=0).run()     # nothing to restore into
    raise SystemExit("expected NodeLossFault")
except NodeLossFault:
    pass
try:
    make(ckpt_every=2, zero=True).run()
    raise SystemExit("expected RuntimeError")
except RuntimeError as e:
    assert "zero=True" in str(e), e
print("NODELOSS_GUARDRAILS_OK")
""", n_devices=4)
    assert "NODELOSS_GUARDRAILS_OK" in out
