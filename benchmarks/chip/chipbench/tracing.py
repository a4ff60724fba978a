"""Profiler capture and the extraction of a compact event record from it.

The record is what `reduce` reads and what a test keeps as a recorded trace:

    {"window": [t0_ns, t1_ns],                      # the "bench.window" span
     "devices": {"0": {"ops": [[start_ns, dur_ns, name], ...],
                       "async": [...], "modules": [...]}, ...},
     "host": [[start_ns, dur_ns, name], ...]}       # "bench.*" spans

``ops`` are the device's "XLA Ops" line (what ran), ``async`` its
"Async XLA Ops" line (start-to-done spans of asynchronous copies and
collectives), ``modules`` its "XLA Modules" line (one event per program run).
Host and device events share the profiler's clock.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil
import tempfile
from typing import Any, Dict

_DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:(\d+)$")
_LINES = {"XLA Ops": "ops", "Async XLA Ops": "async", "XLA Modules": "modules"}
_NAME = re.compile(r"^%?([^ =]+)")
WINDOW = "bench.window"


def span(name: str):
    """A host span on the profiler's clock; free when no trace is taken."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def op_name(text: str) -> str:
    """``%fusion.3 = bf16[..] fusion(..)`` -> ``fusion.3``."""
    m = _NAME.match(text)
    return m.group(1) if m else text


def opcode(text: str) -> str:
    """The HLO opcode of an "XLA Ops" event (``fusion``, ``all-reduce-start``)."""
    m = re.search(r"[\]\})] ([a-z][a-z0-9\-]*)\(", text)
    return m.group(1) if m else ""


def extract(xplane_path: str) -> Dict[str, Any]:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane_path)
    devices: Dict[str, Dict[str, list]] = {}
    host = []
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            rec = devices.setdefault(m.group(1), {v: [] for v in _LINES.values()})
            for line in plane.lines:
                key = _LINES.get(line.name)
                if key is None:
                    continue
                out = rec[key]
                keep_text = key != "modules"
                for ev in line.events:
                    name = ev.name
                    if keep_text:
                        name = op_name(name) + "|" + opcode(name)
                    out.append([ev.start_ns, ev.duration_ns, name])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host.append([ev.start_ns, ev.duration_ns, ev.name])
    host.sort()
    win = [h for h in host if h[2] == WINDOW]
    window = [win[0][0], win[-1][0] + win[-1][1]] if win else None
    return {"window": window, "devices": devices, "host": host}


@contextlib.contextmanager
def capture(enabled: bool, out: Dict[str, Any]):
    """Trace the body when ``enabled``; on exit ``out["trace"]`` holds the
    compact record (the raw profile is deleted)."""
    if not enabled:
        yield
        return
    import jax

    import time

    tmp = tempfile.mkdtemp(prefix="chipbench-trace-")
    try:
        jax.profiler.start_trace(tmp)
        try:
            yield
        finally:
            t0 = time.perf_counter()
            jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
        if not paths:
            raise RuntimeError("the profiler wrote no .xplane.pb")
        out["trace"] = extract(paths[0])
        out["trace_read_s"] = time.perf_counter() - t0
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
