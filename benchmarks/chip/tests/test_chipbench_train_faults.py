"""A whole tiny training run through the harness (no chip): correct when
sound, not correct with a fault planted in the timed path, and not correct
for the control (the reference in float8 put in the program's place)."""
import pytest

import chipbench_tiny
import control
from chipbench import precision, spec, train


@pytest.fixture()
def bench(tmp_path, monkeypatch):
    b, traffic = chipbench_tiny.setup(tmp_path)
    monkeypatch.setattr(spec, "TRAFFIC", traffic)
    return b


def test_sound_run_is_correct_and_reports_its_metrics(bench):
    out = chipbench_tiny.run(bench, "smollm-train4k-1chip")
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"train_tokens_per_s_per_chip", "setup_s"}
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch"])
def test_planted_fault_is_not_correct(bench, fault):
    with control.TRAIN_FAULTS[fault]():
        out = chipbench_tiny.run(bench, "smollm-train4k-1chip")
    assert not out["correct"], out["checks"]


def test_control_in_float8_is_not_correct(bench):
    cell = spec.resolve(bench, "smollm-train4k-1chip")
    fam = spec.family(cell.config["reference"])
    import jax

    seed = 2**31 + 9
    ref = train.reference(fam, cell.config, cell.traffic, seed, jax.devices()[:1])
    ctrl = train.reference(fam, cell.config, cell.traffic, seed, jax.devices()[:1],
                           rnd=precision.fp8)
    ctrl["replica_diff"] = 0.0
    nums = train.numbers(ctrl, ref, 1)
    assert any(nums[k] > cell.traffic["limits"][k] for k in nums), nums
