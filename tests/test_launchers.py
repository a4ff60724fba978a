"""Launcher CLIs + HLO accounting end-to-end validation."""
import subprocess
import sys

import pytest

from .helpers import REPO, run_devices


def _run_cli(args, timeout=400):
    import os
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src") + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run([sys.executable, "-m"] + args, env=env, cwd=str(REPO),
                         capture_output=True, text=True, timeout=timeout)
    assert res.returncode == 0, res.stderr[-1500:]
    return res.stdout


@pytest.mark.slow
def test_train_launcher_cli(tmp_path):
    out = _run_cli(["repro.launch.train", "--arch", "smollm-135m", "--shape",
                    "train_4k", "--steps", "4", "--reduced",
                    "--ckpt-dir", str(tmp_path), "--ckpt-every", "2"])
    assert "done: step 4" in out


@pytest.mark.slow
@pytest.mark.parametrize("arch", [["smollm-135m"],
                                  # the hybrid's first 2 layers: one application
                                  ["zamba2-7b", "--layers", "2"]])
def test_serve_launcher_cli(arch):
    out = _run_cli(["repro.launch.serve", "--arch", *arch, "--reduced",
                    "--batch", "2", "--prompt-len", "8", "--new-tokens", "2"])
    assert "tok/s" in out and "0 compiles" in out


@pytest.mark.slow
def test_train_launcher_rejects_decode_shape():
    import os
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    res = subprocess.run([sys.executable, "-m", "repro.launch.train", "--arch",
                          "smollm-135m", "--shape", "decode_32k", "--reduced"],
                         env=env, cwd=str(REPO), capture_output=True, text=True,
                         timeout=120)
    assert res.returncode != 0


MULTIPLIER_VALIDATION = r"""
import jax, jax.numpy as jnp
from repro.launch.hlo_analysis import analyze_cost

# The trip-weighted HLO pass must make scan == unroll (XLA's own cost_analysis
# counts while bodies once — the bug the pass exists to fix).
L, D = 7, 256
w = jax.ShapeDtypeStruct((L, D, D), jnp.float32)
x = jax.ShapeDtypeStruct((D, D), jnp.float32)

def scanned(w, x):
    def body(c, wi):
        return jnp.tanh(c @ wi), None
    return jax.lax.scan(body, x, w)[0].sum()

def unrolled(w, x):
    for i in range(L):
        x = jnp.tanh(x @ w[i])
    return x.sum()

fs = analyze_cost(jax.jit(scanned).lower(w, x).compile().as_text()).flops
fu = analyze_cost(jax.jit(unrolled).lower(w, x).compile().as_text()).flops
ca = jax.jit(scanned).lower(w, x).compile().cost_analysis()
xla_s = (ca[0] if isinstance(ca, (list, tuple)) else ca)["flops"]
print("scan:", fs, "unrolled:", fu, "xla_scan:", xla_s)
assert abs(fs - fu) / fu < 0.05, (fs, fu)
assert abs(fs - L * 2 * D**3) / (L * 2 * D**3) < 0.05
assert xla_s < fs / 2  # demonstrates the XLA under-count the pass corrects
print("OK")
"""


@pytest.mark.slow
def test_hlo_cost_scan_equals_unrolled():
    assert "OK" in run_devices(MULTIPLIER_VALIDATION, 2)
