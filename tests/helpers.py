"""Test helpers: spawn subprocesses with forced host device counts.

Multi-device tests must run in fresh processes because jax locks the device
count at first init (the dry-run forces 512 only inside its own process).
"""
from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
SRC = str(REPO / "src")


def count_eqns(closed, name: str = None) -> int:
    """Count jaxpr equations (all of them, or those of primitive `name`) —
    thin shim; the shared walker lives in `repro.analysis.trace`."""
    from repro.analysis.trace import count_eqns as _count

    return _count(closed, name)


def run_devices(code: str, n_devices: int = 8, timeout: int = 420) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["JAX_PLATFORMS"] = os.environ.get("JAX_PLATFORMS", "cpu")
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                         text=True, timeout=timeout, cwd=str(REPO))
    assert res.returncode == 0, f"subprocess failed:\nSTDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr[-3000:]}"
    return res.stdout
