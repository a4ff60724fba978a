"""Collective trace extraction: the shared jaxpr walker.

One recursive walk over a ClosedJaxpr (absorbing the walker that used to live
in `launch.hlo_analysis.count_jaxpr_eqns` and the per-test copies in
`tests/test_overlap.py` / `tests/test_moe_step.py` / `tests/test_codec.py`)
that yields a structured **CollectiveTrace**: one ordered record per
`psum` / `ppermute` / `all_gather` / `reduce_scatter` / `all_to_all` equation,
carrying the mesh axes it runs over, its wire dtype, payload bytes, the
scan-nesting depth it was issued at, and the scan trip multiplier (product of
enclosing `lax.scan` lengths — the number of times the collective fires per
step, which is what exact per-collective wire-byte accounting needs).

The walk descends into every sub-jaxpr a primitive carries (shard_map bodies,
scan/while bodies, cond branches, custom-vjp calls), so records come out in
issue order regardless of how deeply the step nests.

Note on naming: `lax.psum_scatter` lowers to a primitive called
``reduce_scatter`` on current jax; both spellings canonicalize to
``reduce_scatter`` here so rules and tests never care which one the tracer
emitted.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, FrozenSet, List, Optional, Sequence, Tuple

import jax
import jax.extend.core as jex

#: canonical collective kinds a CollectiveRecord can carry
COLLECTIVE_KINDS: FrozenSet[str] = frozenset(
    {"psum", "ppermute", "all_gather", "reduce_scatter", "all_to_all"})

#: primitive-name -> canonical kind (psum_scatter is reduce_scatter's old name)
_PRIM_TO_KIND: Dict[str, str] = {k: k for k in COLLECTIVE_KINDS}
_PRIM_TO_KIND["psum_scatter"] = "reduce_scatter"

#: primitives that multiply the issue count of their body's equations
_LOOP_PRIMS = frozenset({"scan", "while"})


def _sub_jaxprs(eqn):
    """Every Jaxpr reachable through one equation's params."""
    for val in eqn.params.values():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for u in vals:
            if isinstance(u, jex.ClosedJaxpr):
                yield u.jaxpr
            elif isinstance(u, jex.Jaxpr):
                yield u


def _as_jaxpr(closed):
    """Accept a ClosedJaxpr, a bare Jaxpr, or anything with `.jaxpr`."""
    return closed.jaxpr if hasattr(closed, "jaxpr") else closed


def walk_eqns(closed, visit: Callable) -> None:
    """Depth-first walk calling ``visit(eqn, scan_depth, scan_trips)`` on
    every equation.  `scan_depth` counts enclosing scan/while bodies and
    `scan_trips` is the product of their static lengths (1 when a loop's
    length is unknown, e.g. `while`)."""

    def walk(jaxpr, depth, trips):
        for eqn in jaxpr.eqns:
            visit(eqn, depth, trips)
            if eqn.primitive.name in _LOOP_PRIMS:
                length = eqn.params.get("length", 1)
                sub_depth = depth + 1
                sub_trips = trips * max(int(length or 1), 1)
            else:
                sub_depth, sub_trips = depth, trips
            for sub in _sub_jaxprs(eqn):
                walk(sub, sub_depth, sub_trips)

    walk(_as_jaxpr(closed), 0, 1)


def count_eqns(closed, name: Optional[str] = None) -> int:
    """Count equations (of primitive `name`, or all) across nested jaxprs.
    The walker formerly known as `hlo_analysis.count_jaxpr_eqns`."""
    cnt = 0

    def visit(eqn, depth, trips):
        nonlocal cnt
        if name is None or eqn.primitive.name == name:
            cnt += 1

    walk_eqns(closed, visit)
    return cnt


def prims_of(closed) -> FrozenSet[str]:
    """Set of primitive names appearing anywhere in the (nested) jaxpr."""
    prims = set()
    walk_eqns(closed, lambda eqn, d, t: prims.add(eqn.primitive.name))
    return frozenset(prims)


def scans_of(closed) -> List[Tuple[int, FrozenSet[str]]]:
    """Every `lax.scan` in the jaxpr as ``(length, body primitive set)``,
    in walk order (nested scans appear after their parent)."""
    out: List[Tuple[int, FrozenSet[str]]] = []

    def visit(eqn, depth, trips):
        if eqn.primitive.name == "scan":
            body = eqn.params.get("jaxpr")
            out.append((int(eqn.params.get("length") or 0),
                        prims_of(body) if body is not None else frozenset()))

    walk_eqns(closed, visit)
    return out


def _axes_of(eqn) -> Tuple[str, ...]:
    """Mesh axis names of a collective eqn — psum spells them `axes`, the
    rest `axis_name`; either may be a bare name or a tuple."""
    raw = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if not isinstance(raw, (tuple, list)):
        raw = (raw,)
    return tuple(str(a) for a in raw)


@dataclasses.dataclass(frozen=True)
class CollectiveRecord:
    """One collective equation as issued by the compiled step."""
    kind: str                      # canonical name (COLLECTIVE_KINDS)
    axes: Tuple[str, ...]          # mesh axes it communicates over
    dtype: str                     # wire dtype of the largest operand
    shape: Tuple[int, ...]         # shape of the largest operand
    payload_bytes: int             # total bytes of all array operands
    scalar: bool                   # every operand is rank-0 (clip/loss psums)
    scan_depth: int                # number of enclosing scan/while bodies
    scan_trips: int                # product of enclosing scan lengths

    @property
    def wire_bytes(self) -> int:
        """Bytes this record puts on the wire per step (payload x trips)."""
        return self.payload_bytes * self.scan_trips

    def __str__(self) -> str:
        loc = f" depth={self.scan_depth}x{self.scan_trips}" \
            if self.scan_depth else ""
        return (f"{self.kind}[{','.join(self.axes)}] "
                f"{self.dtype}{list(self.shape)}{loc}")


def _record(eqn, depth, trips) -> CollectiveRecord:
    avals = [v.aval for v in eqn.invars
             if hasattr(v.aval, "shape") and hasattr(v.aval, "dtype")]
    payload = sum(int(a.size) * a.dtype.itemsize for a in avals)
    big = max(avals, key=lambda a: int(a.size) * a.dtype.itemsize,
              default=None)
    return CollectiveRecord(
        kind=_PRIM_TO_KIND[eqn.primitive.name],
        axes=_axes_of(eqn),
        dtype=str(big.dtype) if big is not None else "float32",
        shape=tuple(big.shape) if big is not None else (),
        payload_bytes=payload,
        scalar=all(a.ndim == 0 for a in avals),
        scan_depth=depth,
        scan_trips=trips,
    )


@dataclasses.dataclass(frozen=True)
class CollectiveTrace:
    """Ordered collective records of one traced step + the jaxpr-level
    facts the lint rules consume (donation, concatenate pressure)."""
    records: Tuple[CollectiveRecord, ...]
    donate_argnums: Tuple[int, ...] = ()
    n_eqns: int = 0                # total equations (nested)
    n_concats: int = 0             # flat concatenates (nested, unweighted)

    def of_kind(self, kind: str) -> Tuple[CollectiveRecord, ...]:
        return tuple(r for r in self.records if r.kind == kind)

    def counts(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for r in self.records:
            out[r.kind] = out.get(r.kind, 0) + 1
        return out

    def kinds(self) -> FrozenSet[str]:
        return frozenset(r.kind for r in self.records)

    def wire_bytes(self, kind: Optional[str] = None,
                   include_scalar: bool = False) -> int:
        """Per-step bytes over the wire — each record's payload times its
        scan trip count, the exact (not aggregate) accounting."""
        return sum(r.wire_bytes for r in self.records
                   if (kind is None or r.kind == kind)
                   and (include_scalar or not r.scalar))


def trace_jaxpr(closed, donate_argnums: Sequence[int] = ()) -> CollectiveTrace:
    """Extract the CollectiveTrace of a (Closed)Jaxpr."""
    records: List[CollectiveRecord] = []
    n_eqns = 0
    n_concats = 0

    def visit(eqn, depth, trips):
        nonlocal n_eqns, n_concats
        n_eqns += 1
        name = eqn.primitive.name
        if name == "concatenate":
            # only flat (rank <= 2) operands are wire packing: leaf pieces and
            # carrier rows.  The model's own concatenates (rope halves on
            # (B, S, H, hd)) recur once per traced gradient, which a
            # microbatched step traces twice
            if eqn.outvars[0].aval.ndim <= 2:
                n_concats += 1
        elif name in _PRIM_TO_KIND:
            records.append(_record(eqn, depth, trips))

    walk_eqns(closed, visit)
    return CollectiveTrace(records=tuple(records),
                           donate_argnums=tuple(donate_argnums),
                           n_eqns=n_eqns, n_concats=n_concats)


def trace_step(step: Callable, *example_args) -> CollectiveTrace:
    """Trace a compiled step function on example (abstract-ok) arguments.
    Donation is read off the step's advertised `donate_argnums` (steps built
    by `runtime.steps` expose it)."""
    closed = jax.make_jaxpr(lambda *a: step(*a))(*example_args)
    return trace_jaxpr(closed,
                       donate_argnums=getattr(step, "donate_argnums", ()))
