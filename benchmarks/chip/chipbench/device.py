"""The chips a cell runs on, and their peaks (``peaks.json``)."""
from __future__ import annotations

import json
from typing import Any, Dict, List

from .spec import ROOT


class DeviceError(Exception):
    """No accelerator, too few chips, or a device the peaks table lacks."""


def peaks(device_kind: str) -> Dict[str, float]:
    with open(ROOT / "peaks.json") as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise DeviceError(f"device kind {device_kind!r} is not in peaks.json "
                          f"(known: {sorted(table)})")
    return {k: float(v) for k, v in table[device_kind].items()}


def chips(n: int, platform: str = "tpu") -> List[Any]:
    """The first ``n`` accelerator devices; raises when JAX finds fewer."""
    import jax

    devs = jax.devices()
    if devs[0].platform != platform:
        raise DeviceError(f"JAX found no {platform} (device 0 is "
                          f"{devs[0].platform!r})")
    if len(devs) < n:
        raise DeviceError(f"the cell needs {n} chips; JAX sees {len(devs)}")
    return devs[:n]


def describe(devs, peak_bytes=None) -> Dict[str, Any]:
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs),
            "memory_peak_bytes": peak_bytes}


def peak_bytes(devs) -> int:
    """``peak_bytes_in_use`` of the fullest chip since the process started."""
    vals = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs]
    return int(max(vals))
