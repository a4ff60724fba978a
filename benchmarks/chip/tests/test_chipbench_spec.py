"""The benchmark finds its cells, configurations, traffic and metrics by name
from files, refuses what it cannot find, and refuses to run without a chip."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import chipbench_tiny  # noqa: F401  (puts the harness on sys.path)
from chipbench import device, spec

CHIP = chipbench_tiny.CHIP
REPO = chipbench_tiny.REPO


def bench():
    return json.loads((REPO / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("cell", [w["name"] for w in bench()["workloads"]])
def test_every_cell_resolves_from_its_files(cell):
    c = spec.resolve(bench(), cell)
    assert c.traffic["kind"] in ("train", "serve")
    assert {"setup_s"} <= {m["name"] for m in c.end_to_end}
    assert len(c.end_to_end) >= 2 and c.per_layer
    for m in c.per_layer:
        assert callable(spec.metric_reader(m["name"]))
    fam = spec.family(c.config["reference"])
    assert callable(fam.make_weights) and callable(fam.program_config)
    assert set(c.traffic["limits"]) >= {"loss_rel_gap"} or "served_gap" in c.traffic["limits"]


def test_unknown_configuration_is_an_error():
    b = bench()
    b["workloads"][0]["config"] = "no-such-model"
    with pytest.raises(spec.SpecError, match="unknown configuration"):
        spec.resolve(b, b["workloads"][0]["name"])


def test_unknown_metric_is_an_error():
    b = bench()
    b["per_layer"].append({"name": "no_such_metric", "unit": "%", "better": "lower",
                           "source": "device_trace", "layer": "device",
                           "moves": "setup_s", "workloads": [b["workloads"][0]["name"]]})
    with pytest.raises(spec.SpecError, match="no reader"):
        spec.resolve(b, b["workloads"][0]["name"])


def test_unknown_workload_and_traffic_are_errors(tmp_path, monkeypatch):
    b = bench()
    with pytest.raises(spec.SpecError, match="unknown workload"):
        spec.resolve(b, "no-such-cell")
    monkeypatch.setattr(spec, "TRAFFIC", tmp_path)
    with pytest.raises(spec.SpecError, match="traffic"):
        spec.resolve(b, b["workloads"][0]["name"])


def test_new_cell_config_and_metric_are_new_files_only(tmp_path, monkeypatch):
    """A cell, a configuration and a metric added as files and entries,
    with no existing file edited."""
    (tmp_path / "traffic").mkdir()
    (tmp_path / "metrics").mkdir()
    for f in (CHIP / "traffic").glob("*.json"):
        shutil.copy(f, tmp_path / "traffic")
    for f in (CHIP / "metrics").glob("*.py"):
        shutil.copy(f, tmp_path / "metrics")
    (tmp_path / "traffic" / "train4k-b4-mb1.json").write_text(json.dumps(
        dict(json.loads((CHIP / "traffic" / "train4k-b8-mb2.json").read_text()),
             global_batch=4, launch=["--shape", "train_4k", "--steps", "100000"])))
    (tmp_path / "metrics" / "loss_drop.py").write_text(
        "def read(ctx):\n    return None\n")
    cfg = json.loads((CHIP / "configs" / "smollm-135m.json").read_text())
    cfg["name"] = "smollm-135m-16l"
    cfg["num_hidden_layers"] = 16
    (tmp_path / "smollm-135m-16l.json").write_text(json.dumps(cfg))
    monkeypatch.setattr(spec, "TRAFFIC", tmp_path / "traffic")
    monkeypatch.setattr(spec, "METRICS", tmp_path / "metrics")
    b = bench()
    b["configs"].append({"name": "smollm-135m-16l", "file": str(tmp_path / "smollm-135m-16l.json")})
    b["workloads"].append({"name": "smollm-16l-train4k-b4", "config": "smollm-135m-16l",
                           "traffic": "train4k-b4-mb1", "chips": 1, "why": "test"})
    b["end_to_end"][0]["workloads"].append("smollm-16l-train4k-b4")
    b["per_layer"].append({"name": "loss_drop", "unit": "%", "better": "higher",
                           "source": "host_clock", "layer": "step",
                           "moves": "train_tokens_per_s_per_chip",
                           "workloads": ["smollm-16l-train4k-b4"]})
    c = spec.resolve(b, "smollm-16l-train4k-b4")
    assert c.config["num_hidden_layers"] == 16 and c.traffic["global_batch"] == 4
    assert "loss_drop" in {m["name"] for m in c.per_layer}
    assert spec.metric_reader("loss_drop")({}) is None


def test_peaks_table_knows_v5e_and_refuses_unknown_devices():
    p = device.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(device.DeviceError, match="not in peaks.json"):
        device.peaks("TPU v99")


def _run(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run([sys.executable] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def test_no_tpu_exits_nonzero_without_a_result():
    r = _run([str(CHIP / "run.py"), "--workload", "smollm-train4k-1chip",
              "--seed", str(2**31 + 1), "--seconds", "1", "--trace", "0"], REPO)
    assert r.returncode != 0
    assert '"correct"' not in r.stdout
    assert "no tpu" in r.stderr.lower()


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run([str(tmp_path / "benchmarks" / "chip" / "run.py"), "--workload",
              "mamba2-serve-b16", "--seed", "1", "--seconds", "1", "--trace", "0"],
             tmp_path)
    assert r.returncode != 0 and '"correct"' not in r.stdout
