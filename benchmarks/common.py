"""Shared benchmark plumbing: subprocess multi-device runs + CSV artifacts."""
from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
SRC = str(REPO / "src")
OUT = REPO / "artifacts" / "bench"


def run_devices(code: str, n_devices: int = 8, timeout: int = 560) -> str:
    """Run `code` in a fresh process with forced host devices; return stdout.
    The child is pinned to the CPU backend: these are host-device figure
    reproductions, and the parent may already hold the accelerator."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                         text=True, timeout=timeout, cwd=str(REPO))
    if res.returncode != 0:
        raise RuntimeError(f"bench subprocess failed:\n{res.stderr[-2000:]}")
    return res.stdout


def out_path(name: str) -> Path:
    OUT.mkdir(parents=True, exist_ok=True)
    return OUT / name


def emit(name: str, rows: list, cols: list) -> None:
    """Print `name,us_per_call,derived` style CSV rows + save full CSV artifact."""
    import csv

    p = out_path(name + ".csv")
    with open(p, "w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=cols)
        w.writeheader()
        w.writerows(rows)
    for r in rows[: min(len(rows), 100)]:
        print(",".join(str(r.get(c, "")) for c in cols))
