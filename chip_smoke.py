#!/usr/bin/env python3
"""Chip smoke test: the train and serve entry points at smollm-135m's
published widths, on TPU.

  python chip_smoke.py             # one chip: train_4k for 3 steps, then serve
  python chip_smoke.py --chips 4   # four chips: the overlap and zero explicit-DP
                                   # programs against the XLA SPMD step

Every phase runs in this one process through the launchers a user calls
(`repro.launch.train.main`, `repro.launch.serve.main`), with random weights
from seed 0; a chip belongs to one process, so no child is started.  Any
failure exits nonzero.  The lines before the last are smoke figures (wall
seconds, peak device bytes), not metrics.  The last line of stdout is

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

The script refuses to run when JAX finds no TPU, and needs the repo's `src/`
beside it.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import io
import json
import math
import re
import shutil
import sys
import tempfile
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"
ARCH = "smollm-135m"
#: relative per-step loss bound between an explicit-DP program and the SPMD step
LOSS_RTOL = 1e-2

_STEP_RE = re.compile(r"^step\s+(\d+) loss (\S+) gnorm (\S+)", re.M)
_SERVE_RE = re.compile(r"\(batch (\d+), (\d+) new,")


class _Tee(io.TextIOBase):
    """Write through to the real stdout and keep a copy to parse."""

    def __init__(self, out):
        self.out, self.buf = out, io.StringIO()

    def write(self, s):
        self.buf.write(s)
        return self.out.write(s)

    def flush(self):
        self.out.flush()


def _run(main, argv):
    """Run a launcher's `main(argv)` in-process; return (stdout, wall s)."""
    tee = _Tee(sys.stdout)
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(tee):
        rc = main(argv)
    wall = time.perf_counter() - t0
    if rc:
        raise RuntimeError(f"{main.__module__}.main{argv} returned {rc}")
    return tee.buf.getvalue(), wall


def _peak_bytes(devices):
    """`peak_bytes_in_use` of each device since the process started (None
    where the backend keeps no statistics)."""
    return [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]


def train_phase(extra, steps=3):
    """Train through `launch.train.main` with a scratch checkpoint directory;
    return the per-step losses, which must all be finite."""
    from repro.launch import train

    ckpt = tempfile.mkdtemp(prefix="chip_smoke_ckpt_")
    argv = ["--arch", ARCH, "--steps", str(steps), "--ckpt-dir", ckpt] + extra
    try:
        out, wall = _run(train.main, argv)
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)
    losses = [float(m.group(2)) for m in _STEP_RE.finditer(out)]
    if len(losses) != steps or not all(math.isfinite(x) for x in losses):
        raise RuntimeError(f"train {extra}: want {steps} finite losses, "
                           f"got {losses}")
    gc.collect()
    return losses, wall


def serve_phase(extra, batch, new_tokens):
    """Serve one batch through `launch.serve.main`; every token must come."""
    from repro.launch import serve

    argv = ["--arch", ARCH, "--batch", str(batch),
            "--new-tokens", str(new_tokens)] + extra
    out, wall = _run(serve.main, argv)
    m = _SERVE_RE.search(out)
    if not m or (int(m.group(1)), int(m.group(2))) != (batch, new_tokens):
        raise RuntimeError(f"serve: want batch {batch} x {new_tokens} new "
                           f"tokens, launcher said {out.strip()!r}")
    gc.collect()
    return wall


def _figure(phase, **kw):
    print("smoke figure (not a metric): " + json.dumps({"phase": phase, **kw}),
          flush=True)


def one_chip(reduced=False):
    """Train train_4k (global batch 256 x 4096 tokens) for 3 steps, then
    serve a batch of 8 prompts of 1024 tokens for 64 new tokens."""
    import jax

    cut = ["--reduced"] if reduced else []
    args = ["--shape", "train_4k", "--microbatches",
            "2" if reduced else "32"] + cut
    losses, wall = train_phase(args)
    _figure("train", args=args, losses=losses, wall_s=wall,
            peak_bytes_since_start=_peak_bytes(jax.devices()[:1]))
    batch, prompt, new = (2, 16, 4) if reduced else (8, 1024, 64)
    args = ["--prompt-len", str(prompt)] + cut
    wall = serve_phase(args, batch, new)
    _figure("serve", args=args + ["--batch", str(batch), "--new-tokens",
                                  str(new)],
            wall_s=wall, peak_bytes_since_start=_peak_bytes(jax.devices()[:1]))


def four_chips(reduced=False):
    """The explicit-DP `overlap` and `zero` programs on a 4x1 mesh, fp32 wire,
    each step's loss within LOSS_RTOL of the XLA SPMD step on the same mesh
    and batch."""
    import jax
    from repro.launch.train import parse_mesh

    devices = jax.devices()
    mesh = parse_mesh("4x1")
    ids = {d.id for d in mesh.devices.flat}
    if len(devices) != 4 or len(ids) != 4:
        raise RuntimeError(f"--chips 4 needs a mesh over 4 distinct devices; "
                           f"got {len(devices)} devices, mesh ids {sorted(ids)}")
    base = ["--shape", "train_4k", "--mesh", "4x1", "--microbatches",
            "1" if reduced else "16"] + (["--reduced"] if reduced else [])
    runs = {"spmd": [], "overlap": ["--overlap"], "zero": ["--zero", "--overlap"]}
    losses = {}
    for name, extra in runs.items():
        losses[name], wall = train_phase(base + extra)
        _figure(f"train/{name}", args=base + extra, losses=losses[name],
                wall_s=wall, peak_bytes_since_start=_peak_bytes(devices))
    peaks = _peak_bytes(devices)
    if any(p == 0 for p in peaks):
        raise RuntimeError(f"a device held no memory (peak bytes {peaks}): "
                           f"the programs did not span all four chips")
    ref = losses["spmd"]
    for name in ("overlap", "zero"):
        for step, (a, b) in enumerate(zip(losses[name], ref)):
            rel = abs(a - b) / abs(b)
            print(f"step {step}  {name:8s} {a:.6f}  spmd {b:.6f}  "
                  f"rel {rel:.2e}", flush=True)
            if not rel <= LOSS_RTOL:
                raise RuntimeError(f"{name} step {step}: loss {a} vs SPMD {b} "
                                   f"(rel {rel:.2e} > {LOSS_RTOL})")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        raise SystemExit(f"chip_smoke.py: no {SRC / 'repro'}; run it from a "
                         f"checkout of the repo")
    sys.path.insert(0, str(SRC))
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke.py: JAX found no TPU (device 0 is "
                         f"{dev.platform!r}); refusing to run")
    if len(jax.devices()) != args.chips:
        raise SystemExit(f"chip_smoke.py --chips {args.chips}: JAX sees "
                         f"{len(jax.devices())} devices")
    (four_chips if args.chips == 4 else one_chip)()
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
