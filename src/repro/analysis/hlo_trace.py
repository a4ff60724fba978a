"""Structured post-SPMD HLO parsing: the compiled-artifact side of CommLint.

This module is the one home of the HLO-text machinery that used to be buried
in ``launch.hlo_analysis`` (dtype table, shape/replica-group parsing,
computation splitting, while-trip recovery, per-line cost accounting).
``launch.hlo_analysis.analyze_collectives`` / ``analyze_cost`` are now thin
consumers of it, and ``analysis.schedule`` builds the jaxpr<->HLO cross-check
on top of it.

``parse_hlo`` turns a compiled module's text into an ordered **HloTrace**:
one ``HloCollectiveRecord`` per scheduled collective op (async ``-start`` /
``-done`` pairs fold into one record), carrying

  * the HLO op (``all-reduce`` ...) and its canonical jaxpr kind (``psum``);
  * replica-group size and the device-id span of the first group (the
    pod-stride DCN classifier the roofline uses);
  * the wire dtype and the **input-side payload bytes** — normalized so an
    ``all-gather`` counts its per-device shard and a ``reduce-scatter`` the
    full pre-scatter operand, i.e. the same quantity a jaxpr
    ``CollectiveRecord.payload_bytes`` reports for the op that lowered to it;
  * the while-body execution multiplier (``trips``) recovered from the loop
    conditions, so ``payload x trips`` is exact per-step wire accounting;
  * async scheduling facts (start/done line indices) and, when the operand
    chain shows it, the dtype a feeding ``convert`` widened from.

Input-side normalization is what makes the cross-check possible at all: the
SPMD partitioner legitimately lowers a ``psum`` to ``all-gather`` + local
reduce (one-shot) or a ``reduce_scatter`` to ``all-reduce`` + slice, and only
the input-side payload survives those rewrites unchanged.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, FrozenSet, List, Optional, Tuple

# ------------------------------------------------------------------- tables

#: bytes per element of every HLO dtype the dumps use (one definition —
#: ``launch.hlo_analysis`` imports it from here)
DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

#: HLO dtype -> numpy-style name, so rules can compare against jaxpr records
DTYPE_NP = {
    "pred": "bool", "s8": "int8", "u8": "uint8", "s16": "int16",
    "u16": "uint16", "bf16": "bfloat16", "f16": "float16", "s32": "int32",
    "u32": "uint32", "f32": "float32", "s64": "int64", "u64": "uint64",
    "f64": "float64", "c64": "complex64",
    "f8e4m3fn": "float8_e4m3fn", "f8e5m2": "float8_e5m2",
}

#: HLO collective op -> the canonical jaxpr kind that lowers to it
HLO_TO_KIND = {
    "all-reduce": "psum",
    "all-gather": "all_gather",
    "reduce-scatter": "reduce_scatter",
    "all-to-all": "all_to_all",
    "collective-permute": "ppermute",
}

#: reduction-equivalent families: the SPMD partitioner may rewrite within a
#: family (psum -> one-shot all-gather + reduce, reduce_scatter ->
#: all-reduce + slice) without changing the input-side payload; a byte that
#: leaves its family is a genuine rewrite
KIND_FAMILY = {
    "psum": "reduce", "all_gather": "reduce", "reduce_scatter": "reduce",
    "ppermute": "permute", "all_to_all": "alltoall",
}

SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
OP_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:\w+\[[\d,]*\][^ ]*))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\(")
GROUPS_RE = re.compile(
    r"replica_groups=(\{\{[^=]*?\}\}|\[[^\]]*\]<=\[[^\]]*\](?:T\([\d,]+\))?)")
# lazy up to the closing "}}" so every pair is captured, not just the first
SOURCE_TARGET_RE = re.compile(r"source_target_pairs=\{(.*?)\}\}")
COMP_START_RE = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s+\(")
WHILE_RE = re.compile(r"while\(.*?\).*?condition=%?([\w.\-]+).*?body=%?([\w.\-]+)")
CALL_RE = re.compile(r"\b(?:call|fusion)\(.*?\).*?(?:to_apply|calls)=%?([\w.\-]+)")
CONST_RE = re.compile(r"%?([\w.\-]+)\s*=\s*\w+\[\]\s*constant\((\d+)\)")
# operands may carry inline scalar types (`compare(s32[] %iv, s32[] %c)`)
COMPARE_RE = re.compile(
    r"compare\((?:\w+\[\]\s+)?%?([\w.\-]+),\s*(?:\w+\[\]\s+)?%?([\w.\-]+)\),?"
    r".*direction=(LT|LE|GT|GE)")
DEF_RE = re.compile(r"%([\w.\-]+)\s*=\s*((?:\([^)]*\))|\w+\[[\d,]*\](?:\{[^}]*\})?)")
PARAM_ANNOT_RE = re.compile(r"([\w.\-]+):\s*((?:\([^)]*\))|\w+\[[\d,]*\](?:\{[^}]*\})?)")
# operands may carry an inline type (`dot(f32[8,8]{1,0} %a, ...)`) depending
# on the XLA version's dump style
DOT_RE = re.compile(
    r"=\s*(\w+\[[\d,]*\])[^ ]*\s+dot\("
    r"(?:\w+\[[\d,]*\](?:\{[^}]*\})?\s+)?%([\w.\-]+),\s*"
    r"(?:\w+\[[\d,]*\](?:\{[^}]*\})?\s+)?%([\w.\-]+)\)"
    r".*?lhs_contracting_dims=\{([\d,]*)\}")
FUSED_PREFIXES = ("fused_computation", "wrapped_", "add.", "add_", "max.",
                  "min.", "region_", "and.", "or.")
MODULE_RE = re.compile(r"^HloModule\s+([\w.\-]+)")
# an instruction and, where its metadata has one, its op_name (escaped
# quotes allowed)
OP_NAME_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=(?:.*?\bmetadata=\{[^\n]*?"
    r"\bop_name=\"((?:[^\"\\]|\\.)*)\")?", re.M)

#: payloads below this are sideband/control traffic (mirrors
#: ``analysis.expect.WIDE_BYTES``; duplicated literal avoided via import there)


# ---------------------------------------------------------------- primitives


def shape_bytes(type_str: str) -> int:
    """Total bytes of every shape in an HLO type string (tuples sum)."""
    total = 0
    for dtype, dims in SHAPE_RE.findall(type_str):
        if dtype not in DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * DTYPE_BYTES[dtype]
    return total


def dominant_dtype(type_str: str) -> str:
    """Numpy-style dtype of the largest shape in an HLO type string."""
    best, best_bytes = "float32", -1
    for dtype, dims in SHAPE_RE.findall(type_str):
        if dtype not in DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        b = n * DTYPE_BYTES[dtype]
        if b > best_bytes:
            best, best_bytes = DTYPE_NP.get(dtype, dtype), b
    return best


def dims_of(type_str: str):
    m = SHAPE_RE.search(type_str)
    if not m:
        return None, []
    dtype, dims = m.group(1), m.group(2)
    return dtype, [int(d) for d in dims.split(",")] if dims else []


def parse_group(line: str) -> Tuple[int, int]:
    """Returns (group_size, id_span_within_first_group) of a collective line.

    ``source_target_pairs`` (collective-permute) derives the group size from
    the pair graph: a ppermute-lowered alltoall or ring shift is a set of
    cycles/paths over the device ids, and the effective group is the largest
    connected component — it used to be hard-coded to 2, which misclassified
    every >2-device permute's DCN span share and per-op accounting.
    """
    m = GROUPS_RE.search(line)
    if not m:
        st = SOURCE_TARGET_RE.search(line)
        if st:
            ids = [int(x) for x in re.findall(r"\d+", st.group(1))]
            pairs = list(zip(ids[::2], ids[1::2]))
            if not pairs:
                return 1, 0
            span = max(abs(a - b) for a, b in pairs)
            # union-find over the undirected pair graph; group size = the
            # largest component's node count (a ring of n is one n-cycle)
            parent: Dict[int, int] = {}

            def find(x):
                parent.setdefault(x, x)
                while parent[x] != x:
                    parent[x] = parent[parent[x]]
                    x = parent[x]
                return x

            for a, b in pairs:
                ra, rb = find(a), find(b)
                if ra != rb:
                    parent[ra] = rb
            sizes: Dict[int, int] = defaultdict(int)
            for node in parent:
                sizes[find(node)] += 1
            return max(sizes.values()), span
        return 1, 0
    g = m.group(1)
    if g.startswith("{{"):
        first = g[2:].split("}")[0]
        ids = [int(x) for x in first.split(",") if x.strip()]
        return max(len(ids), 1), (max(ids) - min(ids)) if ids else 0
    # iota form: [G,S]<=[N...] with optional T(perm); malformed or truncated
    # group annotations (hand-written / trivial HLO) degrade to "no groups"
    # instead of raising out of the whole analysis
    import numpy as np
    try:
        left = [int(x) for x in re.findall(r"\d+", g.split("<=")[0])]
        right_part = g.split("<=")[1]
        reshape = [int(x) for x in re.findall(r"\d+", right_part.split("T")[0].strip("[] "))]
        tperm = re.search(r"T\(([\d,]+)\)", right_part)
        ngroups, gsize = (left + [1, 1])[:2] if len(left) >= 2 else (1, left[0] if left else 1)
        n = int(np.prod(reshape)) if reshape else ngroups * gsize
        ids = np.arange(n).reshape(reshape if reshape else (n,))
        if tperm:
            ids = ids.transpose([int(x) for x in tperm.group(1).split(",")])
        ids = ids.reshape(ngroups, gsize)
        span = int(ids[0].max() - ids[0].min()) if ids.size else 0
        return gsize, span
    except (IndexError, ValueError):
        return 1, 0


def split_computations(hlo_text: str) -> Dict[str, List[str]]:
    """Computation headers may wrap across lines; a computation starts at a
    non-indented `%name (`/`ENTRY %name (` line and ends at a bare `}`."""
    comps: Dict[str, List[str]] = {}
    cur: Optional[str] = None
    entry_name = None
    for raw in hlo_text.splitlines():
        line = raw.strip()
        if not raw.startswith((" ", "\t")):
            m = COMP_START_RE.match(line)
            if m:
                cur = m.group(1)
                comps[cur] = []
                if line.startswith("ENTRY") or raw.startswith("ENTRY"):
                    entry_name = cur
                continue
        if line == "}":
            continue
        if cur is not None:
            comps[cur].append(line)
    if entry_name is not None:
        comps["__entry__"] = comps[entry_name]
    return comps


def module_name(hlo_text: str) -> str:
    """``jit_step`` of ``HloModule jit_step, ...`` (what a profile names
    the module's runs, before its ``(fingerprint)``)."""
    m = MODULE_RE.match(hlo_text)
    return m.group(1) if m else ""


def op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> the ``op_name`` of its metadata (the jaxpr path,
    named scopes included), ``""`` for an instruction without one (a copy or
    layout change the compiler added)."""
    return {m.group(1): m.group(2) or "" for m in OP_NAME_RE.finditer(hlo_text)}


def trip_count(cond_lines: List[str]) -> int:
    """Trip count of a while loop from its condition computation's lines."""
    consts = {}
    for ln in cond_lines:
        for name, val in CONST_RE.findall(ln):
            consts[name] = int(val)
    for ln in cond_lines:
        m = COMPARE_RE.search(ln)
        if m:
            a, b, d = m.groups()
            if b in consts:
                return consts[b] + (1 if d in ("LE",) else 0)
            if a in consts:
                return consts[a] + (1 if d in ("GE",) else 0)
    # XLA usually fuses the compare (`ROOT %wrapped_compare = pred[]
    # fusion(%gte, %constant.N), ...`): the bound constant still lives in the
    # cond computation.  Only constants actually *referenced by* a
    # compare/fusion/call line qualify — an unrelated scalar constant in the
    # condition (a select threshold, say) must not become the trip count.
    fed: set = set()
    for ln in cond_lines:
        if "compare" in ln or "fusion" in ln or "call(" in ln:
            fed.update(re.findall(r"[\w.\-]+", ln))
    referenced = [v for k, v in consts.items() if k in fed]
    if referenced:
        return max(referenced)
    return 1


def multipliers(comps: Dict[str, List[str]]) -> Dict[str, float]:
    """Execution multiplier per computation (entry=1; while bodies x trips)."""
    children: Dict[str, List[Tuple[str, float]]] = defaultdict(list)
    for name, lines in comps.items():
        for ln in lines:
            w = WHILE_RE.search(ln)
            if w:
                cond, body = w.groups()
                trips = trip_count(comps.get(cond, []))
                children[name].append((body, float(max(trips, 1))))
                children[name].append((cond, float(max(trips, 1))))
                continue
            c = CALL_RE.search(ln)
            if c:
                children[name].append((c.group(1), 1.0))
    mult: Dict[str, float] = defaultdict(float)

    def visit(name: str, m: float, depth=0):
        if depth > 64:
            return
        mult[name] += m
        for k, w in children.get(name, []):
            if k in comps:
                visit(k, m * w, depth + 1)

    # "__entry__" aliases the real entry computation's lines, so its children
    # are the real entry's children; the real entry itself is fixed to x1 by
    # the consumers' alias check.
    visit("__entry__", 1.0)
    return dict(mult)


def collect_trip_counts(comps: Dict[str, List[str]]) -> set:
    """All >1 while trip counts in the module (the loop-carry slicing set)."""
    trips = set()
    for lines in comps.values():
        for ln in lines:
            w = WHILE_RE.search(ln)
            if w:
                trips.add(trip_count(comps.get(w.group(1), [])))
    return {t for t in trips if t > 1}


def build_type_map(hlo_text: str) -> Dict[str, str]:
    types: Dict[str, str] = {}
    for m in PARAM_ANNOT_RE.finditer(hlo_text):
        types.setdefault(m.group(1), m.group(2))
    for m in DEF_RE.finditer(hlo_text):
        types[m.group(1)] = m.group(2)
    return types


def comp_multiplier(name: str, lines, mult: Dict[str, float],
                    entry_lines) -> float:
    """Resolve one computation's execution multiplier against the walk.

    The walk only ever visits the ``__entry__`` alias, so the real entry
    computation resolves through identity with the alias's lines; anything
    genuinely unreachable through while/call edges (custom-call targets and
    the like) conservatively executes once.
    """
    m_exec = mult.get(name, 0.0)
    if m_exec == 0.0:
        m_exec = mult.get("__entry__", 1.0) if lines is entry_lines else 1.0
    return m_exec


# ---------------------------------------------------------------- the trace


@dataclasses.dataclass(frozen=True, eq=False)
class HloCollectiveRecord:
    """One scheduled collective of a compiled module (async pairs fold)."""
    op: str                    # HLO op name ("all-reduce", ...)
    kind: str                  # canonical jaxpr kind it corresponds to
    computation: str           # computation whose stream it is scheduled in
    start_index: int           # line index of the op (or its -start)
    done_index: Optional[int]  # line index of the -done, None when sync
    group_size: int            # replica-group size
    span: int                  # device-id span within the first group
    dtype: str                 # numpy-style wire dtype of the payload
    result_bytes: int          # bytes of the result type as written
    payload_bytes: int         # input-side payload (jaxpr-comparable)
    scalar: bool               # every shape in the type is rank-0
    trips: float               # while-body execution multiplier
    is_dcn: bool               # first group spans the pod stride
    fed_by_convert: Optional[str] = None  # source dtype of a feeding convert

    @property
    def wire_bytes(self) -> float:
        """Input-side payload x trips — the jaxpr-comparable accounting."""
        return self.payload_bytes * self.trips

    @property
    def is_async(self) -> bool:
        return self.done_index is not None

    @property
    def algo_wire_bytes(self) -> float:
        """Per-device bytes on the wire with the standard ring factors
        (``analyze_collectives``'s accounting), before the trip multiplier."""
        g = max(self.group_size, 1)
        s = float(self.result_bytes)
        if self.op == "all-reduce":
            return 2.0 * s * (g - 1) / g
        if self.op == "all-gather":
            return s * (g - 1) / g
        if self.op == "reduce-scatter":
            return s * (g - 1)
        if self.op == "all-to-all":
            return s * (g - 1) / g
        return s  # collective-permute

    def __str__(self) -> str:
        tag = " async" if self.is_async else ""
        loc = f"x{self.trips:g}" if self.trips != 1 else ""
        dcn = " dcn" if self.is_dcn else ""
        return (f"{self.op}[g={self.group_size}] {self.dtype} "
                f"{self.payload_bytes}B{loc}{tag}{dcn}")


@dataclasses.dataclass(frozen=True, eq=False)
class HloTrace:
    """Ordered collective records of one compiled module plus the parsed
    context (`comps`/`types`/`loop_trips`) the static scheduler reuses."""
    records: Tuple[HloCollectiveRecord, ...]
    pod_stride: int = 0
    comps: Dict[str, List[str]] = dataclasses.field(default_factory=dict)
    types: Dict[str, str] = dataclasses.field(default_factory=dict)
    loop_trips: FrozenSet[int] = frozenset()

    def of_kind(self, kind: str) -> Tuple[HloCollectiveRecord, ...]:
        return tuple(r for r in self.records if r.kind == kind)

    def of_op(self, op: str) -> Tuple[HloCollectiveRecord, ...]:
        return tuple(r for r in self.records if r.op == op)

    def kinds(self) -> FrozenSet[str]:
        return frozenset(r.kind for r in self.records)

    def counts(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for r in self.records:
            out[r.op] = out.get(r.op, 0) + 1
        return out

    def wire_bytes(self, kind: Optional[str] = None,
                   include_scalar: bool = False) -> float:
        """Input-side payload x trips (the jaxpr-comparable accounting)."""
        return sum(r.wire_bytes for r in self.records
                   if (kind is None or r.kind == kind)
                   and (include_scalar or not r.scalar))

    def coster(self) -> "LineCoster":
        return LineCoster(self.types, self.loop_trips)


def _operand_names(call_part: str) -> List[str]:
    """%-prefixed operand names inside one op's first balanced paren span."""
    paren = call_part.find("(")
    if paren < 0:
        return []
    depth, end = 0, len(call_part)
    for i in range(paren, len(call_part)):
        if call_part[i] == "(":
            depth += 1
        elif call_part[i] == ")":
            depth -= 1
            if depth == 0:
                end = i + 1
                break
    return re.findall(r"%([\w.\-]+)", call_part[paren:end])


def _convert_source(operands: List[str], defs: Dict[str, str],
                    types: Dict[str, str]) -> Optional[str]:
    """Numpy dtype a `convert` feeding the collective converts *from*, if
    any operand is one (dequantize-then-communicate shows up here)."""
    for name in operands:
        line = defs.get(name, "")
        if " convert(" not in line:
            continue
        paren = line.find(" convert(") + len(" convert")
        inner = line[paren:]
        m = SHAPE_RE.search(inner)
        if m and m.group(1) in DTYPE_BYTES:
            return DTYPE_NP.get(m.group(1), m.group(1))
        src = re.findall(r"%([\w.\-]+)", inner)
        if src:
            dt, _ = dims_of(types.get(src[0], ""))
            if dt in DTYPE_BYTES:
                return DTYPE_NP.get(dt, dt)
    return None


def _input_payload(op: str, result_bytes: int, g: int) -> int:
    """Input-side payload from the written result type: what the lowering's
    *source* jaxpr op carried as operand bytes."""
    g = max(g, 1)
    if op == "all-gather":
        return result_bytes // g
    if op == "reduce-scatter":
        return result_bytes * g
    return result_bytes


def parse_hlo(hlo_text: str, pod_stride: int = 0) -> HloTrace:
    """Parse a compiled module's text into an ordered HloTrace.

    ``pod_stride`` is the device-id stride of the pod (DCN) axis; groups whose
    first-group span reaches it are classified ``is_dcn``.  ``-start`` lines
    open an async record that the matching ``-done`` closes (payload then
    comes from the done's result type — the start's tuple double-counts);
    a start with no done degrades to half the tuple bytes.
    """
    if not hlo_text or not hlo_text.strip():
        return HloTrace(records=())
    comps = split_computations(hlo_text)
    if not comps:
        return HloTrace(records=())
    mult = multipliers(comps)
    types = build_type_map(hlo_text)
    loop_trips = frozenset(collect_trip_counts(comps))
    entry_lines = comps.get("__entry__")
    records: List[HloCollectiveRecord] = []
    for name, lines in comps.items():
        if name == "__entry__":
            continue
        m_exec = comp_multiplier(name, lines, mult, entry_lines)
        defs = {m.group(1): ln for ln in lines for m in [DEF_RE.match(
            ln[5:] if ln.startswith("ROOT ") else ln)] if m}
        pending: Dict[str, int] = {}  # start var name -> records index
        for idx, line in enumerate(lines):
            om = OP_RE.search(line)
            if not om:
                continue
            type_str, op, suffix = om.group(1), om.group(2), om.group(3)
            clean = line[5:] if line.startswith("ROOT ") else line
            dm = DEF_RE.match(clean)
            var = dm.group(1) if dm else f"__anon{idx}"
            operands = _operand_names(line[om.end() - 1:])
            if suffix == "-done":
                src = next((o for o in operands if o in pending), None)
                if src is not None:
                    ri = pending.pop(src)
                    rec = records[ri]
                    rb = shape_bytes(type_str)
                    pb = _input_payload(op, rb, rec.group_size)
                    records[ri] = dataclasses.replace(
                        rec, done_index=idx, result_bytes=rb,
                        payload_bytes=pb,
                        dtype=dominant_dtype(type_str),
                        scalar=shape_scalar(type_str))
                continue
            g, span = parse_group(line)
            rb = shape_bytes(type_str)
            if suffix == "-start":
                # the start's tuple is (operand, result[, sync flags]):
                # halve until the -done supplies the real result type
                rb = rb // 2
            rec = HloCollectiveRecord(
                op=op, kind=HLO_TO_KIND[op], computation=name,
                start_index=idx, done_index=None, group_size=g, span=span,
                dtype=dominant_dtype(type_str), result_bytes=rb,
                payload_bytes=_input_payload(op, rb, g),
                scalar=shape_scalar(type_str), trips=m_exec,
                is_dcn=(pod_stride > 0 and span >= pod_stride),
                fed_by_convert=_convert_source(operands, defs, types))
            records.append(rec)
            if suffix == "-start":
                pending[var] = len(records) - 1
    return HloTrace(records=tuple(records), pod_stride=pod_stride,
                    comps=comps, types=types, loop_trips=loop_trips)


def shape_scalar(type_str: str) -> bool:
    """True when every shape in the type string is rank-0."""
    found = SHAPE_RE.findall(type_str)
    return bool(found) and all(not dims for _, dims in found)


# ------------------------------------------------------------ per-line cost
# XLA's HloCostAnalysis counts a while body ONCE, so scanned layer stacks
# under-report flops/bytes by a factor of L.  The per-line accounting below
# (moved verbatim from `launch.hlo_analysis.analyze_cost`'s loop body) is what
# both the module-level cost pass and the static overlap scheduler
# (`analysis.schedule`) price compute with:
#   flops  = 2 * result_elems * prod(contracting dims)   over `dot` ops
#   bytes  = result + operand bytes per scheduled line (post-fusion HLO: one
#            line ~ one kernel), with slicing ops touching only the slice and
#            stacked loop carries touching one slice per iteration.


class LineCoster:
    """Prices one scheduled HLO line: matmul flops and HBM traffic."""

    _SKIP = ("tuple", "get-tuple-element", "bitcast", "parameter", "constant",
             "iota", "after-all", "partition-id", "replica-id", "reshape",
             # control flow: carries alias in place; the bodies' real traffic
             # is counted via their own multipliers
             "while", "conditional", "call", "custom-call")

    def __init__(self, types: Dict[str, str], loop_trips):
        self.types = types
        self.loop_trips = set(loop_trips)

    def _operand_bytes(self, name: str) -> float:
        """Bytes actually read from one operand.  Stacked loop carries —
        arrays whose leading dim equals a loop trip count, e.g. the (88, D, F)
        parameter stacks sliced inside fused dynamic-slice/update — are
        touched one slice per iteration, not in full."""
        t = self.types.get(name, "")
        b = shape_bytes(t)
        _, dims = dims_of(t)
        if len(dims) >= 2 and dims[0] in self.loop_trips:
            return b / dims[0]
        return b

    def dot_flops(self, line: str) -> float:
        dm = DOT_RE.search(line)
        if not dm:
            return 0.0
        res_t, lhs, _, cdims = dm.group(1), dm.group(2), dm.group(3), dm.group(4)
        _, res_dims = dims_of(res_t)
        res_elems = 1
        for d in res_dims:
            res_elems *= d
        _, lhs_dims = dims_of(self.types.get(lhs, ""))
        contract = 1
        for ci in ([int(x) for x in cdims.split(",")] if cdims else []):
            if ci < len(lhs_dims):
                contract *= lhs_dims[ci]
        return 2.0 * res_elems * contract

    def hbm_bytes(self, line: str) -> Optional[Tuple[str, float]]:
        """(op_kind, bytes) of one scheduled line, or None when it moves no
        HBM traffic of its own (control flow, aliases, metadata ops)."""
        clean = line[5:] if line.startswith("ROOT ") else line
        dfm = DEF_RE.match(clean)
        if not dfm:
            return None
        res_bytes = shape_bytes(dfm.group(2))
        op_part = clean[dfm.end():].lstrip()
        opm = re.match(r"([\w\-]+)\(", op_part)
        op_kind = opm.group(1) if opm else ""
        paren = op_part.find("(")
        close = op_part.find(")", paren)
        operands = []
        if paren >= 0 and close > paren:
            operands = re.findall(r"%([\w.\-]+)", op_part[paren:close + 1])
        # Data-movement rules: slicing ops touch only the slice, not the full
        # operand (critical inside layer scans: dynamic-slice reads of the
        # stacked (L, ...) parameter arrays would otherwise count L times
        # L-full).
        if op_kind in self._SKIP:
            return None
        if op_kind in ("dynamic-slice", "gather", "slice"):
            return op_kind, 2.0 * res_bytes
        if op_kind in ("dynamic-update-slice", "scatter"):
            upd_idx = 1 if op_kind == "dynamic-update-slice" else 2
            upd = shape_bytes(self.types.get(operands[upd_idx], "")) \
                if len(operands) > upd_idx else res_bytes
            return op_kind, 3.0 * min(upd, res_bytes)
        if op_kind in ("copy", "convert", "transpose", "broadcast"):
            return op_kind, 2.0 * res_bytes
        # results that are themselves stacked carries (fused DUS into an
        # (L, ...) accumulator) also only write one slice per iteration
        _, res_dims = dims_of(dfm.group(2))
        if len(res_dims) >= 2 and res_dims and res_dims[0] in self.loop_trips:
            res_bytes = res_bytes / res_dims[0]
        operand_bytes = sum(self._operand_bytes(on) for on in operands)
        return op_kind, res_bytes + operand_bytes
