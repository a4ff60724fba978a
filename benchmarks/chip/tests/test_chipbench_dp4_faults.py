"""The four-chip training cell at tiny size on four host devices (a child
process, since JAX fixes its device count at start): correct when sound,
not correct with the gradient exchange left out."""
import json
import os
import subprocess
import sys

import pytest

import chipbench_tiny

CHILD = r"""
import json, sys, pathlib
sys.path.insert(0, {tests!r})
import chipbench_tiny, control
from chipbench import spec
bench, traffic = chipbench_tiny.setup(pathlib.Path({tmp!r}))
spec.TRAFFIC = traffic
if {fault!r}:
    with control.TRAIN_FAULTS[{fault!r}]():
        out = chipbench_tiny.run(bench, "smollm-train4k-dp4", trace=1)
else:
    out = chipbench_tiny.run(bench, "smollm-train4k-dp4", trace=1)
print(json.dumps({{"correct": out["correct"], "checks": out["checks"],
                   "metrics": sorted(out["metrics"]), "count": out["device"]["count"]}}))
"""


def _child(tmp_path, fault):
    # one compute thread: the suite runs in parallel with timing-sensitive tests
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4 "
                         "--xla_cpu_multi_thread_eigen=false")
    code = CHILD.format(tests=str(chipbench_tiny.CHIP / "tests"), tmp=str(tmp_path),
                        fault=fault)
    r = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault,correct", [("", True), ("no_exchange", False)])
def test_dp4_exchange(tmp_path, fault, correct):
    out = _child(tmp_path, fault)
    assert out["count"] == 4
    assert out["correct"] is correct, out["checks"]
    if not correct:
        assert out["checks"]["replica_max_abs_diff"]["value"] > 0
