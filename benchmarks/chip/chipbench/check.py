"""The numbers that decide ``correct``, each held against its own limit."""
from __future__ import annotations

import math
import statistics
import sys
from typing import Dict, Mapping, Optional, Sequence


def leaf_norm_gap(prog: Mapping[str, float], ref: Mapping[str, float],
                  leaves: Optional[Sequence[str]] = None,
                  zero_prog: bool = False) -> float:
    """Worst leaf of |‖prog‖ − ‖ref‖| over the larger of the reference's norm
    of that leaf and of its median leaf (some leaves are all but zero).  With
    ``zero_prog`` the ``prog`` values are already the norms of differences
    and are taken as they are."""
    leaves = list(ref) if leaves is None else list(leaves)
    med = statistics.median(ref[k] for k in ref)
    worst = 0.0
    for k in leaves:
        den = max(ref[k], med)
        num = prog[k] if zero_prog else abs(prog[k] - ref[k])
        gap = num / den if den > 0 else num
        if not math.isfinite(gap):
            return math.inf
        worst = max(worst, gap)
    return worst


def moving_leaves(ref_grad: Mapping[str, float], share: float = 1e-3):
    """Leaves whose reference gradient is at least ``share`` of the median
    leaf's; the others move under Adam by round-off alone."""
    med = statistics.median(ref_grad.values())
    return [k for k, v in ref_grad.items() if v >= share * med]


def rel_gap(a: Sequence[float], b: Sequence[float]) -> float:
    if len(a) != len(b):
        return math.inf
    worst = 0.0
    for x, y in zip(a, b):
        g = abs(x - y) / max(abs(y), 1e-30)
        worst = max(worst, g if math.isfinite(g) else math.inf)
    return worst


def verdict(numbers: Mapping[str, float], limits: Mapping[str, float]) -> Dict:
    """``{name: {"value", "limit"}}`` for every number; correct when each is
    finite and at most its limit."""
    out = {}
    for k, v in numbers.items():
        if k not in limits:
            raise KeyError(f"no limit for the compared number {k!r}")
        out[k] = {"value": float(v), "limit": float(limits[k])}
    return out


def is_correct(checks: Mapping[str, Mapping[str, float]]) -> bool:
    return bool(checks) and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())


def print_checks(checks, stream=None) -> None:
    stream = stream or sys.stderr
    for k, c in checks.items():
        ok = math.isfinite(c["value"]) and c["value"] <= c["limit"]
        print(f"check {k} {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if ok else 'FAIL'}", file=stream, flush=True)
