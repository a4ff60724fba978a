"""From a compact trace record (`tracing.extract`) to numbers.

Every interval is [start_ns, end_ns).  All figures are per device and then
averaged over the devices the record holds, unless said otherwise.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all", "collective-broadcast")
#: control flow whose event spans the ops it runs (a scan's ``while``): left
#: out of busy time, of the ops that hide a collective, and of the top ops
CONTAINERS = ("while", "conditional", "call")

Interval = Tuple[float, float]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def measure(merged: Sequence[Interval]) -> float:
    return float(sum(e - s for s, e in merged))


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Merged ``a`` minus merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def is_collective(name: str) -> bool:
    """``name|opcode`` of an op event."""
    return any(c in name for c in COLLECTIVES)


def _iv(events) -> List[Interval]:
    return [(s, s + d) for s, d, _ in events]


def is_container(name: str) -> bool:
    return name.rsplit("|", 1)[-1] in CONTAINERS


def leaf_ops(d):
    """A device's op events without the control-flow containers."""
    return [e for e in d["ops"] if not is_container(e[2])]


def window_ns(rec) -> Tuple[float, float]:
    if rec.get("window"):
        return tuple(rec["window"])
    ops = [e for d in rec["devices"].values() for e in d["ops"]]
    return (min(s for s, _, _ in ops), max(s + d for s, d, _ in ops))


def busy(rec) -> Dict[str, float]:
    """Seconds in which an op ran, per device, inside the window."""
    lo, hi = window_ns(rec)
    return {dev: measure(union(clip(_iv(leaf_ops(d)), lo, hi))) * 1e-9
            for dev, d in rec["devices"].items()}


def idle_share(rec) -> Optional[float]:
    if not rec["devices"]:
        return None
    lo, hi = window_ns(rec)
    b = busy(rec)
    return 1.0 - (sum(b.values()) / len(b)) / ((hi - lo) * 1e-9)


def comm(rec) -> Optional[Dict[str, float]]:
    """Mean over devices of collective seconds in the window (sync collective
    ops and the start-to-done spans of asynchronous ones) and of the part of
    it in which no other op runs on that device; None without devices."""
    if not rec["devices"]:
        return None
    lo, hi = window_ns(rec)
    tot, exp, n = 0.0, 0.0, 0
    for d in rec["devices"].values():
        coll = [e for e in d["ops"] + d["async"] if is_collective(e[2])]
        other = [e for e in leaf_ops(d) if not is_collective(e[2])]
        cu = union(clip(_iv(coll), lo, hi))
        ou = union(clip(_iv(other), lo, hi))
        tot += measure(cu)
        exp += measure(subtract(cu, ou))
        n += 1
    return {"collective_s": tot * 1e-9 / n, "exposed_s": exp * 1e-9 / n}


def top_ops(rec, k: int = 10) -> List[List]:
    """The ops that took most device time, seconds averaged over devices."""
    lo, hi = window_ns(rec)
    acc: Dict[str, float] = defaultdict(float)
    for d in rec["devices"].values():
        for s, dur, name in leaf_ops(d):
            if s + dur > lo and s < hi:
                acc[name.split("|")[0]] += dur
    n = len(rec["devices"])
    items = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[name, v * 1e-9 / n] for name, v in items]


def idle_gaps(rec, k: int = 10) -> List[List]:
    """Device idle time inside the window, attributed to the host span that
    overlaps each gap most (``host`` where none does), seconds averaged over
    devices, largest first."""
    import bisect

    lo, hi = window_ns(rec)
    host = sorted((s, s + d, name) for s, d, name in rec["host"]
                  if name != "bench.window")
    starts = [h[0] for h in host]
    reach, top = [], float("-inf")          # latest end among spans [0..j]
    for h in host:
        top = max(top, h[1])
        reach.append(top)
    acc: Dict[str, float] = defaultdict(float)
    for d in rec["devices"].values():
        bu = union(clip(_iv(leaf_ops(d)), lo, hi))
        for gs, ge in subtract([(lo, hi)], bu):
            best, label = 0.0, "host"
            j = bisect.bisect_left(starts, ge) - 1
            while j >= 0 and reach[j] > gs:
                hs, he, name = host[j]
                ov = min(ge, he) - max(gs, hs)
                if ov > best:
                    best, label = ov, name
                j -= 1
            acc[label] += ge - gs
    n = len(rec["devices"])
    items = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[name, v * 1e-9 / n] for name, v in items]


def module_gaps(rec, match: str) -> Optional[float]:
    """Mean device idle seconds between the end of one run of a program whose
    name contains ``match`` and the start of the next such run (ops of other
    programs in between count as busy); None if no two runs follow."""
    lo, hi = window_ns(rec)
    gaps = []
    for d in rec["devices"].values():
        runs = sorted((s, s + dur) for s, dur, name in d["modules"]
                      if match in name and s >= lo and s + dur <= hi)
        busy = union(clip(_iv(leaf_ops(d)), lo, hi))
        for (s0, e0), (s1, e1) in zip(runs, runs[1:]):
            if s1 > e0:
                gaps.append(measure(subtract([(e0, s1)], busy)))
            else:
                gaps.append(0.0)
    if not gaps:
        return None
    return sum(gaps) / len(gaps) * 1e-9
