"""What the program's own telemetry adds to a trace record.

The record (`tracing.extract`) keeps the devices' ops, async spans and module
runs, and the harness's ``bench.*`` spans.  The program's telemetry module
(``repro.telemetry``), read in the same process after the window,
adds:

* its compiled programs (``NOTED``: the train step, the server's prefill and
  decode), fetched after the window from the caches that hold the
  executables that ran; their ``op_name`` metadata gives each device op its
  named scope, matched by module and op name;
* its host spans (``SPANS``, on ``time.perf_counter_ns``), put on the
  profiler's clock by the ``bench.*`` span each one encloses, and on the
  device's by causal pairs (a program run starts after its dispatch span
  starts; a wait span ends after the run it waits for ends);
* its compile counter, each event flagged by whether a profile was being
  recorded.

A program without that module, or a run without a trace, reads as None.
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from typing import Any, Dict, List, Optional, Sequence, Tuple

from . import reduce
from .reduce import Interval, clip, measure, subtract, union

OTHER = "other"
NONE = "none"
#: program span -> the harness span it encloses (the profiler's copy of it)
ENCLOSES = {"train.batch": "bench.batch", "train.step": "bench.step_call",
            "serve.prefill": "bench.prefill_call",
            "serve.decode": "bench.decode_call"}
#: program -> (its dispatch span, the span that waits for its result)
CAUSAL = {"train.step": ("train.step", "train.sync"),
          "serve.decode": ("serve.decode", "serve.readback")}


def telemetry():
    try:
        from repro import telemetry as tm
    except ImportError:
        return None
    return tm


def base(module_event: str) -> str:
    """``jit_decode(1234)`` -> ``jit_decode``."""
    return module_event.split("(", 1)[0]


def _cached(ctx, key, make):
    memo = ctx.setdefault("_program", {})
    if key not in memo:
        memo[key] = make()
    return memo[key]


# ------------------------------------------------------------ op -> scope
def op_paths(texts: Sequence[str]) -> Dict[Tuple[str, str], str]:
    """{(module, op): op_name} over compiled module texts."""
    from repro.analysis import hlo_trace

    return {(hlo_trace.module_name(t), op): path for t in texts
            for op, path in hlo_trace.op_names(t).items()}


def programs(ctx) -> Optional[Dict[str, Any]]:
    """The noted programs' module names and each op's ``op_name`` path and
    scope, from the executables that ran (the in-memory cache's, else the
    persistent cache's, whose key holds the metadata); ``rebuilt`` counts the
    programs that neither cache held, compiled anew.  None without the
    program's telemetry or a device trace."""
    tm = telemetry()
    rec = ctx.get("trace")
    if tm is None or rec is None or not rec["devices"] or not tm.NOTED:
        return None

    def make():
        from repro.analysis import hlo_trace

        before = tm.COMPILES.compiles
        texts = {name: fn() for name, fn in tm.NOTED.items()}
        paths = op_paths(list(texts.values()))
        return {"modules": {n: hlo_trace.module_name(t) for n, t in texts.items()},
                "scopes": {k: tm.scope_of(v) for k, v in paths.items()},
                "op_names": paths, "names": tm.SCOPES,
                "rebuilt": tm.COMPILES.compiles - before}
    return _cached(ctx, "programs", make)


def tag_ops(d, scopes) -> List[Tuple[float, float, str, str, Optional[str], bool]]:
    """One device's op and async events as ``(start, end, module, op, scope,
    leaf)``: each event's module is the module run that holds it."""
    runs = sorted((s, s + dur, base(n)) for s, dur, n in d["modules"])
    starts = [r[0] for r in runs]
    out = []
    for key, leaf in (("ops", True), ("async", False)):
        for s, dur, name in d[key]:
            if leaf and reduce.is_container(name):
                continue
            i = bisect.bisect_right(starts, s) - 1
            mod = runs[i][2] if i >= 0 and s < runs[i][1] else ""
            op = name.split("|")[0]
            out.append((s, s + dur, mod, op, scopes.get((mod, op)), leaf))
    return out


def scope_seconds(rec, scopes, names, module: Optional[str] = None):
    """Per device, then averaged: busy seconds in the window (inside runs of
    ``module`` when given), the seconds of each scope's leaf ops (their
    union), ``other`` = busy in no scope, and the runs of ``module``.
    ``exchange`` also counts its async collective spans, and
    ``exposed.exchange`` is the part of that in which no op outside
    ``exchange`` runs."""
    lo, hi = reduce.window_ns(rec)

    def u(iv):
        return union(clip(iv, lo, hi))

    acc: Dict[str, float] = defaultdict(float)
    for d in rec["devices"].values():
        leaf: Dict[Optional[str], List[Interval]] = defaultdict(list)
        wire: List[Interval] = []           # exchange's async collective spans
        for s, e, mod, _, sc, is_leaf in tag_ops(d, scopes):
            if module is not None and mod != module:
                continue
            if is_leaf:
                leaf[sc].append((s, e))
            elif sc == "exchange":
                wire.append((s, e))
        busy = u([iv for ivs in leaf.values() for iv in ivs])
        acc["busy"] += measure(busy)
        for sc in names:
            acc[sc] += measure(u(leaf[sc] + (wire if sc == "exchange" else [])))
        acc[OTHER] += measure(subtract(busy, u([iv for sc in names for iv in leaf[sc]])))
        outside = u([iv for sc, ivs in leaf.items() if sc != "exchange" for iv in ivs])
        acc["exposed.exchange"] += measure(subtract(u(leaf["exchange"] + wire), outside))
        if module is not None:
            acc["runs"] += sum(1 for s, dur, n in d["modules"]
                               if base(n) == module and s >= lo and s + dur <= hi)
    n = len(rec["devices"])
    return {k: (v if k == "runs" else v * 1e-9) / n for k, v in acc.items()}


def train_ms(ctx, scope: str) -> Optional[float]:
    """Device milliseconds per training step in ``scope`` (``other``,
    ``exposed.exchange`` too)."""
    if ctx.get("kind") != "train" or not ctx.get("steps"):
        return None
    prog = programs(ctx)
    if prog is None or "train.step" not in prog["modules"]:
        return None
    sec = _cached(ctx, "train_s", lambda: scope_seconds(
        ctx["trace"], prog["scopes"], prog["names"]))
    return 1e3 * sec[scope] / ctx["steps"]


def decode_ms(ctx, scope: str) -> Optional[float]:
    """Device milliseconds per decode run in ``scope`` (``other`` too)."""
    if ctx.get("kind") != "serve":
        return None
    prog = programs(ctx)
    if prog is None or "serve.decode" not in prog["modules"]:
        return None
    sec = _cached(ctx, "decode_s", lambda: scope_seconds(
        ctx["trace"], prog["scopes"], prog["names"], prog["modules"]["serve.decode"]))
    return 1e3 * sec[scope] / sec["runs"] if sec.get("runs") else None


# ------------------------------------------------------------------ spans
def self_times(spans) -> Dict[str, List[float]]:
    """{name: [duration minus the spans nested in it]} of properly nested
    ``(name, start, end)`` spans."""
    out: Dict[str, List[float]] = defaultdict(list)
    stack: List[List] = []      # [end, name, duration, nested]
    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][0] <= s:
            _close(stack, out)
        stack.append([e, name, e - s, 0.0])
    while stack:
        _close(stack, out)
    return out


def _close(stack, out):
    end, name, dur, nested = stack.pop()
    out[name].append(dur - nested)
    if stack:
        stack[-1][3] += dur


def host_ms(ctx, name: str) -> Optional[float]:
    """Mean self time, ms, of the program span ``name`` in the traced window."""
    tm = telemetry()
    if tm is None or ctx.get("trace") is None:
        return None
    t = self_times(list(tm.SPANS)).get(name)
    return 1e-6 * sum(t) / len(t) if t else None


def compiles_in_window(ctx) -> Optional[float]:
    """Programs built (backend compiles and cache loads) while the window's
    profile was recorded."""
    tm = telemetry()
    if tm is None or ctx.get("trace") is None:
        return None
    return float(sum(1 for *_, rec in tm.COMPILES.log if rec))


# ------------------------------------------------------------------ clock
def bracket(lower: Sequence[float], upper: Sequence[float]) -> Tuple[float, float]:
    """The offsets allowed by ``offset >= each lower`` and ``<= each upper``
    (an empty bracket, lo > hi, means the pairs disagree)."""
    return max(lower), min(upper)


def _by_name(spans):
    out: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    for name, s, e in spans:
        out[name].append((s, e))
    for v in out.values():
        v.sort()
    return out


def host_offset(spans, host) -> Optional[Tuple[float, float]]:
    """Bracket of the shift from the program's span clock to the profiler's:
    each ``bench.*`` span lies inside the program span that encloses it."""
    prog, bench = _by_name(spans), _by_name((n, s, s + d) for s, d, n in host)
    lower, upper = [], []
    for p_name, b_name in ENCLOSES.items():
        ps, bs = prog.get(p_name, []), bench.get(b_name, [])
        if not ps or len(ps) != len(bs):
            continue
        for (ps_, pe), (bs_, be) in zip(ps, bs):
            lower.append(be - pe)
            upper.append(bs_ - ps_)
    return bracket(lower, upper) if lower else None


def device_offset(spans, d, modules: Dict[str, str]) -> Optional[Tuple[float, float]]:
    """Bracket of the shift from host spans (profiler clock) to one device's
    events: a run starts after its dispatch span starts, and the wait span
    that follows a dispatch ends after that run ends."""
    by = _by_name(spans)
    lower, upper = [], []
    for prog, (dispatch, wait) in CAUSAL.items():
        if prog not in modules:
            continue
        runs = sorted((s, s + dur) for s, dur, n in d["modules"]
                      if base(n) == modules[prog])
        disp, waits = by.get(dispatch, []), by.get(wait, [])
        if not runs or len(runs) != len(disp):
            continue
        wstarts = [w[0] for w in waits]
        for (rs, re_), (ds, _) in zip(runs, disp):
            upper.append(rs - ds)
            j = bisect.bisect_right(wstarts, ds)     # first wait after it
            if j < len(waits):
                lower.append(re_ - waits[j][1])
    return bracket(lower, upper) if lower and upper else None


# ------------------------------------------------------------- idle gaps
def innermost(spans) -> List[Tuple[float, float, str]]:
    """Disjoint ``(start, end, name)`` segments naming the innermost of
    properly nested spans open at each instant (no segment where none is)."""
    segs: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, str]] = []
    t = float("-inf")
    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][0] <= s:
            end, nm = stack.pop()
            if end > t:
                segs.append((t, end, nm))
                t = end
        if stack and s > t:
            segs.append((t, s, stack[-1][1]))
        t = max(t, s)
        stack.append((e, name))
    while stack:
        end, nm = stack.pop()
        if end > t:
            segs.append((t, end, nm))
            t = end
    return segs


def charge(gaps: Sequence[Interval], segs) -> Dict[str, float]:
    """Each gap's time to the segment covering it, ``none`` where none does."""
    acc: Dict[str, float] = defaultdict(float)
    j = 0
    for gs, ge in gaps:
        covered = 0.0
        while j < len(segs) and segs[j][1] <= gs:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < ge:
            ov = min(ge, segs[k][1]) - max(gs, segs[k][0])
            if ov > 0:
                acc[segs[k][2]] += ov
                covered += ov
            k += 1
        acc[NONE] += (ge - gs) - covered
    return acc


def idle_gaps_program(rec, spans, modules, where: float = 0.5
                      ) -> Optional[Dict[str, Any]]:
    """Device idle time in the window by the innermost program span open
    during it, seconds averaged over devices, with the shifts used: spans go
    onto each device's clock at the point ``where`` of its offset bracket
    (0 its low end, 1 its high end)."""
    h = host_offset(spans, rec["host"])
    if h is None:
        return None
    a = (h[0] + h[1]) / 2
    onhost = [(n, s + a, e + a) for n, s, e in spans]
    lo, hi = reduce.window_ns(rec)
    acc: Dict[str, float] = defaultdict(float)
    clock = {}
    for dev, d in rec["devices"].items():
        b = device_offset(onhost, d, modules)
        clock[dev] = b
        shift = 0.0 if b is None else b[0] + where * (b[1] - b[0])
        segs = innermost([(n, s + shift, e + shift) for n, s, e in onhost])
        busy = union(clip([(s, s + dur) for s, dur, _ in reduce.leaf_ops(d)], lo, hi))
        for k, v in charge(subtract([(lo, hi)], busy), segs).items():
            acc[k] += v
    n = len(rec["devices"])
    gaps = sorted(([k, v * 1e-9 / n] for k, v in acc.items()), key=lambda kv: -kv[1])
    return {"idle_gaps_program": gaps, "host_offset_ns": list(h),
            "clock_offset_us": {d: None if b is None else (b[0] + b[1]) / 2e3
                                for d, b in clock.items()},
            "clock_bracket_us": {d: None if b is None else (b[1] - b[0]) / 1e3
                                 for d, b in clock.items()}}

