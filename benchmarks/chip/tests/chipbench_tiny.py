"""Tiny copies of the benchmark's cells for CPU tests: the same runners,
configuration files cut to a few thousand weights, and traffic files of a
few short rows.  ``setup(tmp)`` writes them under ``tmp`` and returns a
BENCHMARK dict whose cells point at them; the lookup directories of
``chipbench.spec`` are pointed there while a test runs (``monkeypatch``)."""
from __future__ import annotations

import json
import sys
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]
REPO = CHIP.parents[1]
for p in (str(CHIP), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

OPT = {"min_lr": 3e-05, "b1": 0.9, "b2": 0.95, "eps": 1e-08,
       "weight_decay": 0.1, "clip_norm": 1.0}
# set from these tiny cells' own readings on the CPU: sound runs read at most
# 1.2e-5 / 2.1e-3 / 3.3e-3 / 7.8e-3, the float8 control at least 7.6e-5 /
# 8.0e-3 / 7.7e-3 / 0.12, half of the batch left out 2.9e-3 / 0.19 / 0.02 / 0.88
TRAIN_LIMITS = {"loss_rel_gap": 3e-5, "grad_norm_gap": 4e-3,
                "change_norm_gap": 5e-3, "grad_rel_err": 0.03}


def llama():
    c = json.loads((CHIP / "configs" / "smollm-135m.json").read_text())
    c.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
             num_key_value_heads=2, num_hidden_layers=2, vocab_size=256,
             program={"arch": "smollm-135m", "overrides": {
                 "n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
                 "d_ff": 128, "vocab": 256, "q_block": 16}})
    return c


def mamba2():
    c = json.loads((CHIP / "configs" / "mamba2-2.7b.json").read_text())
    c.update(d_model=64, n_layer=2, vocab_size=256, d_state=16, headdim=16,
             chunk_size=8, program={"arch": "mamba2-2.7b", "overrides": {
                 "tie_embeddings": True, "n_layers": 2, "d_model": 64,
                 "vocab": 256, "ssm_state": 16, "ssm_headdim": 16,
                 "ssm_chunk": 8}})
    return c


TRAFFIC = {
    "t1": {"kind": "train", "global_batch": 4, "seq": 64, "optimizer": OPT,
           "launch": ["--shape", "train_4k", "--microbatches", "2", "--steps", "1000"],
           "limits": TRAIN_LIMITS},
    "t4": {"kind": "train", "global_batch": 8, "seq": 64, "optimizer": OPT,
           "launch": ["--shape", "train_4k", "--mesh", "4x1", "--overlap",
                      "--microbatches", "2", "--steps", "1000"],
           "limits": dict(TRAIN_LIMITS, replica_max_abs_diff=0.0)},
    "s1": {"kind": "serve", "batch": 4, "prompt": 16, "new_tokens": 8,
           "sample_requests": 4,
           # sound runs read about 1e-4, the float8 control 0.03, the faults 0.24+
           "limits": {"served_gap": 0.01}},
}


CELLS = [("smollm-train4k-1chip", "smollm-135m", "t1", 1),
         ("smollm-train4k-dp4", "smollm-135m", "t4", 4),
         ("mamba2-serve-b16", "mamba2-2.7b", "s1", 1)]


def setup(tmp: Path):
    """Write the tiny files under ``tmp``; return (bench, traffic_dir)."""
    tmp = Path(tmp)
    (tmp / "traffic").mkdir(parents=True, exist_ok=True)
    (tmp / "llama.json").write_text(json.dumps(llama()))
    (tmp / "mamba2.json").write_text(json.dumps(mamba2()))
    for name, t in TRAFFIC.items():
        (tmp / "traffic" / f"{name}.json").write_text(json.dumps(t))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "smollm-135m", "file": str(tmp / "llama.json")},
                        {"name": "mamba2-2.7b", "file": str(tmp / "mamba2.json")}]
    bench["workloads"] = [
        {"name": n, "config": c, "traffic": t, "chips": k, "why": "tiny"}
        for n, c, t, k in CELLS]
    cells = {n for n, *_ in CELLS}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [w for w in m["workloads"] if w in cells]
    return bench, tmp / "traffic"


def run(bench, workload: str, seed: int = 2**31 + 5, seconds: float = 0.3,
        trace: int = 0):
    """One tiny run through the harness, skipping its look for a chip."""
    import time
    import types

    import run as harness

    args = types.SimpleNamespace(workload=workload, seed=seed, seconds=seconds,
                                 trace=trace)
    return harness.run(args, bench, require_chip=False, t_start=time.perf_counter())
