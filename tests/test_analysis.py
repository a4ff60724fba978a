"""CommLint: the shared jaxpr walker, trace extraction, the StepProgram ->
ExpectedTrace compiler, golden (clean) traces for every named program, and one
negative test per finding code — each asserting the exact code, anchored on
individual collective records.  The compiled-HLO level (ScheduleLint) is
covered the same way: HLO-parsing units, jaxpr<->HLO cross-check goldens for
every named program, and synthetic-HLO negatives for each of its codes."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import pytest
from jax import lax

from repro.analysis import (COLLECTIVE_KINDS, FINDING_CODES, CollectiveRecord,
                            CollectiveTrace, Finding, count_eqns,
                            crosscheck_trace, expected_trace, lint_trace,
                            parse_hlo, prims_of, scans_of,
                            static_exposed_comm, trace_jaxpr, trace_step)
from repro.core import program as prg
from repro.core.autotune import CollectivePolicy
from repro.launch.lint import (_LintModel, _dense_fixture, _make_mesh,
                               lint_program_on_mesh)
from repro.launch.lint import main as lint_main
from repro.optim import adamw
from repro.runtime.steps import build_program_step

from .helpers import run_devices

BUCKET = 4 * 128  # tiny bucket: the 1.6 KiB toy gradient packs into 4 rows


# ---------------------------------------------------------------- the walker
def test_walker_counts_nested_eqns():
    def f(x):
        def body(c, _):
            return c + 1.0, c * 2.0
        c, ys = lax.scan(body, x, None, length=5)
        return c + jnp.sum(ys)

    jx = jax.make_jaxpr(f)(0.0)
    assert count_eqns(jx, "scan") == 1
    assert count_eqns(jx, "add") >= 1  # the body's add, found through the scan
    assert count_eqns(jx) > count_eqns(jx, "scan")
    assert "scan" in prims_of(jx) and "add" in prims_of(jx)
    scans = scans_of(jx)
    assert len(scans) == 1
    length, body_prims = scans[0]
    assert length == 5 and "add" in body_prims


def test_trace_record_fields():
    jx = jax.make_jaxpr(lambda x: lax.psum(x, "i"),
                        axis_env=[("i", 4)])(jnp.ones((8,), jnp.float32))
    tr = trace_jaxpr(jx, donate_argnums=(3,))
    assert tr.donate_argnums == (3,)
    (rec,) = tr.records
    assert rec.kind == "psum" and rec.axes == ("i",)
    assert rec.dtype == "float32" and rec.shape == (8,)
    assert rec.payload_bytes == 32 and not rec.scalar
    assert rec.scan_depth == 0 and rec.scan_trips == 1
    assert tr.wire_bytes() == 32 and tr.counts() == {"psum": 1}

    # scalar psums are flagged as such and excluded from wire accounting
    js = jax.make_jaxpr(lambda x: lax.psum(x, "i"),
                        axis_env=[("i", 4)])(jnp.float32(1.0))
    ts = trace_jaxpr(js)
    assert ts.records[0].scalar
    assert ts.wire_bytes() == 0 and ts.wire_bytes(include_scalar=True) == 4


def test_trace_canonicalizes_psum_scatter_and_gather():
    def f(x):
        return lax.all_gather(lax.psum_scatter(x, "i", tiled=True), "i")

    jx = jax.make_jaxpr(f, axis_env=[("i", 2)])(jnp.ones((4,), jnp.float32))
    tr = trace_jaxpr(jx)
    assert tr.kinds() == {"reduce_scatter", "all_gather"}
    assert tr.kinds() <= COLLECTIVE_KINDS


def test_trace_scan_nesting_multiplies_wire_bytes():
    def f(x):
        def body(c, _):
            return lax.psum(c, "i"), None
        c, _ = lax.scan(body, x, None, length=3)
        return c

    jx = jax.make_jaxpr(f, axis_env=[("i", 2)])(jnp.ones((4,), jnp.float32))
    (rec,) = trace_jaxpr(jx).records
    assert rec.scan_depth == 1 and rec.scan_trips == 3
    assert rec.payload_bytes == 16 and rec.wire_bytes == 48


# ------------------------------------------------- expect: budget resolution
def test_carrier_bytes_and_budget_resolution():
    from repro.analysis.expect import carrier_bytes

    assert carrier_bytes(1000, 512) == (1024, 2)   # pads to whole rows
    assert carrier_bytes(1000, None) == (1000, 64)  # per-tensor: no padding
    # a Bucketize node pinned to the plan crossover can't be priced without
    # the plan: the budget stays None rather than guess the cap
    p = prg.train_step_program()
    assert p.has("bucketize")
    assert expected_trace(p, grad_bytes=1 << 20).byte_budget is None
    pol = CollectivePolicy.from_model()
    e = expected_trace(p, grad_bytes=1 << 20, plan=pol)
    assert e.byte_budget is not None and e.byte_budget > 0
    # an explicit node cap needs no plan
    e2 = expected_trace(prg.train_step_program(bucket_bytes=BUCKET),
                        grad_bytes=1 << 20)
    assert e2.byte_budget is not None


def test_expected_collectives_per_schedule():
    ar = prg.train_step_program().expected_collectives()
    z = prg.train_step_program(zero=True).expected_collectives()
    moe = prg.moe_step_program().expected_collectives()
    assert ar <= COLLECTIVE_KINDS and "reduce_scatter" not in ar
    assert {"reduce_scatter", "all_gather"} <= z
    assert "all_to_all" in moe and "all_to_all" not in ar


def test_finding_code_catalog_is_closed():
    assert len(set(FINDING_CODES)) == 13
    with pytest.raises(ValueError, match="unknown finding code"):
        Finding("misaligned-warp", "not a real rule")


# ----------------------------------------------------------- hlo-text guards
def test_hlo_analysis_guards_empty_and_malformed():
    from repro.launch.hlo_analysis import (_parse_group, analyze_collectives,
                                           analyze_cost)

    for text in ("", "   \n  "):
        stats = analyze_collectives(text)
        assert stats.ici_bytes == 0.0 and stats.dcn_bytes == 0.0
        assert stats.by_op == {}
        cost = analyze_cost(text)
        assert cost.flops == 0.0 and cost.bytes == 0.0
        assert parse_hlo(text).records == ()
    # truncated iota group annotations degrade to "no groups", not a raise
    assert _parse_group("replica_groups=[2,4]<=") == (1, 0)
    assert _parse_group("no groups here at all") == (1, 0)


def test_parse_group_permute_cycle_length():
    """`source_target_pairs` derives the group from the pair graph — a
    4-ring is a group of 4, not the old hard-coded 2."""
    from repro.launch.hlo_analysis import _parse_group

    ring = ("%cp = f32[64] collective-permute(f32[64] %p), "
            "source_target_pairs={{0,1},{1,2},{2,3},{3,0}}")
    assert _parse_group(ring) == (4, 3)
    assert _parse_group("source_target_pairs={{0,1}}") == (2, 1)
    # two disjoint 2-cycles: the effective group is one component (size 2)
    assert _parse_group(
        "source_target_pairs={{0,1},{1,0},{2,3},{3,2}}") == (2, 1)


def test_trip_count_ignores_unreferenced_constants():
    """The fused-compare fallback only considers constants a compare/fusion
    line actually references — an unrelated scalar constant in the condition
    must not become the trip count."""
    from repro.launch.hlo_analysis import _trip_count

    fused = [
        "%threshold = s32[] constant(99)",  # unrelated (select threshold)
        "%constant.7 = s32[] constant(4)",
        "ROOT %wrapped_compare = pred[] fusion(s32[] %gte, "
        "s32[] %constant.7), kind=kLoop, calls=%cc",
    ]
    assert _trip_count(fused) == 4
    # a direct compare with inline-typed operands resolves exactly
    assert _trip_count([
        "%c.2 = s32[] constant(3)",
        "ROOT %cmp = pred[] compare(s32[] %iv, s32[] %c.2), direction=LT",
    ]) == 3
    # no compare-fed constant at all -> 1, never the stray max
    assert _trip_count(["%threshold = s32[] constant(99)"]) == 1


# ----------------------------------------------- hlo trace: structured parse
def _entry_hlo(body_lines, extra_comps=""):
    body = "\n".join("  " + ln for ln in body_lines)
    return (f"HloModule m\n\n{extra_comps}"
            f"ENTRY %main (p0: f32[1024]) -> f32[1024] {{\n{body}\n}}\n")


def test_parse_hlo_records_and_payload_normalization():
    """HLO result bytes normalize to input-side payloads (all-gather: the
    per-device shard; reduce-scatter: the full pre-scatter operand) so they
    are directly comparable with jaxpr operand accounting."""
    tr = parse_hlo(_entry_hlo([
        "%p0 = f32[1024] parameter(0)",
        "%ag = f32[2048] all-gather(f32[1024] %p0), replica_groups={{0,1}}, "
        "dimensions={0}",
        "%rs = f32[1024] reduce-scatter(f32[2048] %ag), "
        "replica_groups={{0,1}}, dimensions={0}, to_apply=%add",
        "ROOT %ar = f32[1024] all-reduce(f32[1024] %rs), "
        "replica_groups={{0,1}}, to_apply=%add",
    ]))
    ag, rs, ar = tr.records
    assert (ag.op, ag.kind, ag.group_size) == ("all-gather", "all_gather", 2)
    assert ag.result_bytes == 8192 and ag.payload_bytes == 4096
    assert (rs.op, rs.payload_bytes) == ("reduce-scatter", 8192)
    assert (ar.op, ar.payload_bytes) == ("all-reduce", 4096)
    assert all(not r.is_async and r.trips == 1 for r in tr.records)
    assert tr.wire_bytes() == 4096 + 8192 + 4096
    assert tr.counts() == {"all-gather": 1, "reduce-scatter": 1,
                           "all-reduce": 1}


def test_parse_hlo_folds_async_pairs_and_while_trips():
    """-start/-done fold into one async record; collectives inside a while
    body carry the loop's trip multiplier, recovered from the condition."""
    comps = (
        "%body (bp: (f32[1024], s32[])) -> (f32[1024], s32[]) {\n"
        "  %bp = (f32[1024], s32[]) parameter(0)\n"
        "  %gteb = f32[1024] get-tuple-element((f32[1024], s32[]) %bp), "
        "index=0\n"
        "  %arb = f32[1024] all-reduce(f32[1024] %gteb), "
        "replica_groups={{0,1}}, to_apply=%add\n"
        "  %iv = s32[] get-tuple-element((f32[1024], s32[]) %bp), index=1\n"
        "  ROOT %tup = (f32[1024], s32[]) tuple(f32[1024] %arb, s32[] %iv)\n"
        "}\n\n"
        "%cond (cp: (f32[1024], s32[])) -> pred[] {\n"
        "  %cp = (f32[1024], s32[]) parameter(0)\n"
        "  %iv2 = s32[] get-tuple-element((f32[1024], s32[]) %cp), index=1\n"
        "  %c3 = s32[] constant(3)\n"
        "  ROOT %cmp = pred[] compare(s32[] %iv2, s32[] %c3), direction=LT\n"
        "}\n\n")
    tr = parse_hlo(_entry_hlo([
        "%p0 = f32[1024] parameter(0)",
        "%ars = (f32[1024], f32[1024]) all-reduce-start(f32[1024] %p0), "
        "replica_groups={{0,1}}, to_apply=%add",
        "%mul = f32[1024] multiply(f32[1024] %p0, f32[1024] %p0)",
        "%ard = f32[1024] all-reduce-done((f32[1024], f32[1024]) %ars)",
        "%z = s32[] constant(0)",
        "%t0 = (f32[1024], s32[]) tuple(f32[1024] %ard, s32[] %z)",
        "%w = (f32[1024], s32[]) while((f32[1024], s32[]) %t0), "
        "condition=%cond, body=%body",
        "ROOT %res = f32[1024] get-tuple-element((f32[1024], s32[]) %w), "
        "index=0",
    ], extra_comps=comps))
    assert len(tr.records) == 2
    async_rec = next(r for r in tr.records if r.computation == "main")
    loop_rec = next(r for r in tr.records if r.computation == "body")
    assert async_rec.is_async and async_rec.done_index > async_rec.start_index
    assert async_rec.payload_bytes == 4096 and async_rec.trips == 1
    assert not loop_rec.is_async and loop_rec.trips == 3
    assert loop_rec.wire_bytes == 3 * 4096


# -------------------------------------------------- golden traces (1 device)
@pytest.mark.parametrize("name", sorted(prg.NAMED_PROGRAMS))
def test_named_program_lints_clean(name):
    """Both levels clean on the 1-device mesh: the jaxpr rules and the
    compiled-HLO cross-check (the 4/8-device goldens run via the CLI below)."""
    rep = lint_program_on_mesh(prg.named_program(name), n_devices=1, hlo=True)
    assert rep["codes"] == [], rep["findings"]
    if rep["schedule"] != "moe_alltoall":
        # (the degenerate 1-device mesh traces the MoE exchange away; the
        # multi-device golden below pins its 2 all_to_alls)
        assert rep["records"] >= 1
    assert set(rep["kinds"]) <= COLLECTIVE_KINDS
    h = rep["hlo"]
    assert h["records"] >= 0 and "static_overlap" in h
    for fam, d in h["byte_deltas"].items():
        assert d["rel_delta"] <= 0.05, (fam, d)


def test_lint_cli_rejects_unknown_program():
    with pytest.raises(SystemExit, match="unknown program"):
        lint_main(["warp_speed"])


# ------------------------------------------------ negatives: one per code
# The xla-forcing legacy policy pins the dense wire to plain psum emission,
# so each mutation lands on a deterministic jaxpr.
def _xla_policy():
    return CollectivePolicy({2: []}, {2: []}, {"source": "measured"})


@functools.lru_cache(maxsize=None)
def _built_trace(**flags):
    """Trace a step built from train_step_program(**flags) on one device."""
    mesh = _make_mesh((1,), ("data",))
    params, batch = _dense_fixture(1)
    opt = adamw.OptConfig(peak_lr=1e-2, warmup_steps=0, decay_steps=10)
    step = build_program_step(_LintModel(), opt, mesh,
                              prg.train_step_program(**flags),
                              policy=_xla_policy())
    return trace_step(step, params, step.init_opt_state(params), batch,
                      step.init_error_state(params))


def _codes(findings):
    return sorted({f.code for f in findings})


def test_negative_gradient_allreduce_under_zero():
    """An allreduce-built step linted against the ZeRO program: the
    tensor-sized gradient psums violate both scalar-only rules."""
    tr = _built_trace(bucket_bytes=BUCKET)
    fs = lint_trace(tr, expected_trace(prg.train_step_program(zero=True)))
    assert _codes(fs) == ["full-gradient-allreduce-under-zero",
                          "non-scalar-psum"], [str(f) for f in fs]
    assert all(f.record is not None and not f.record.scalar for f in fs)


def test_negative_wire_dtype_widening():
    """An fp32-wire step against the int8 program: every gradient-sized fp32
    record is a widened leg (the scalar clip combines stay exempt)."""
    tr = _built_trace(bucket_bytes=BUCKET)
    fs = lint_trace(tr, expected_trace(
        prg.train_step_program(compress_bits=8)))
    assert "wire-dtype-widening" in _codes(fs), [str(f) for f in fs]
    wides = [f for f in fs if f.code == "wire-dtype-widening"]
    assert all(f.record.dtype == "float32" and
               f.record.payload_bytes >= 256 for f in wides)


def test_negative_collective_outside_overlap_scan():
    """A non-overlap step against the overlap program: the bucket reductions
    issue at scan depth 0 instead of riding the issue schedule."""
    tr = _built_trace(bucket_bytes=BUCKET)
    fs = lint_trace(tr, expected_trace(
        prg.train_step_program(overlap=True, bucket_bytes=BUCKET)))
    assert _codes(fs) == ["collective-outside-overlap-scan"], \
        [str(f) for f in fs]
    assert all(f.record.scan_depth == 0 for f in fs)


def test_negative_undonated_carrier():
    """The int8 overlap step is clean as built; stripping the donation of the
    error-feedback carrier (argnum 3) is the one finding introduced."""
    tr = _built_trace(overlap=True, compress_bits=8, bucket_bytes=BUCKET)
    exp = expected_trace(prg.train_step_program(
        overlap=True, compress_bits=8, bucket_bytes=BUCKET))
    assert exp.require_donation == 3
    assert lint_trace(tr, exp) == [], \
        [str(f) for f in lint_trace(tr, exp)]
    stripped = dataclasses.replace(tr, donate_argnums=())
    fs = lint_trace(stripped, exp)
    assert _codes(fs) == ["undonated-carrier"], [str(f) for f in fs]


def test_negative_unplanned_collective():
    """A ZeRO-built step against the allreduce program: reduce_scatter is a
    kind the program never declared — and a stray kind does not also trip
    the wire rules (it reports once, as itself)."""
    tr = _built_trace(zero=True)
    fs = lint_trace(tr, expected_trace(
        prg.train_step_program(bucket_bytes=0)))
    assert _codes(fs) == ["unplanned-collective"], [str(f) for f in fs]
    assert {f.record.kind for f in fs} == {"reduce_scatter"}


def test_negative_unbucketed_concat():
    """Per-leaf concatenation (O(leaves) concatenates) against a bucketized
    program's O(1) codec cap."""
    def pack(xs):
        return functools.reduce(
            lambda a, b: jnp.concatenate([a, b]), xs)

    jx = jax.make_jaxpr(pack)([jnp.ones((4,), jnp.float32)] * 12)
    tr = trace_jaxpr(jx)
    assert tr.n_concats == 11
    fs = lint_trace(tr, expected_trace(
        prg.train_step_program(bucket_bytes=BUCKET)))
    assert _codes(fs) == ["unbucketed-concat"], [str(f) for f in fs]


def test_negative_byte_budget_exceeded():
    """An explicit (absurdly small) budget: the clean allreduce step exceeds
    it through exact payload x scan-trip accounting, scalars excluded."""
    tr = _built_trace(bucket_bytes=BUCKET)
    fs = lint_trace(tr, expected_trace(
        prg.train_step_program(bucket_bytes=BUCKET), byte_budget=1.0))
    assert _codes(fs) == ["byte-budget-exceeded"], [str(f) for f in fs]
    # and the real derived budget clears the same trace
    grad = sum(p.size * p.dtype.itemsize
               for p in jax.tree.leaves(_dense_fixture(1)[0]))
    clean = lint_trace(tr, expected_trace(
        prg.train_step_program(bucket_bytes=BUCKET), grad_bytes=grad))
    assert clean == [], [str(f) for f in clean]


# --------------------------- negatives: one per compiled-HLO finding code
# Synthetic post-SPMD modules (the CPU lowering never emits async pairs or
# rewrites, so the goldens above can't trip these) cross-checked against a
# hand-built jaxpr trace and the program expectation.
def _jx(*recs):
    return CollectiveTrace(records=tuple(recs))


def _jrec(kind, payload, trips=1, dtype="float32"):
    return CollectiveRecord(kind=kind, axes=("data",), dtype=dtype,
                            shape=(payload // 4,), payload_bytes=payload,
                            scalar=False, scan_depth=0, scan_trips=trips)


def _exp(n=2, **kw):
    return expected_trace(prg.train_step_program(bucket_bytes=BUCKET),
                          n_devices=n, **kw)


def test_negative_collective_rewritten():
    """The compiled module moves half the bytes the jaxpr issued: the
    partitioner changed what rides the wire."""
    htr = parse_hlo(_entry_hlo([
        "%p0 = f32[512] parameter(0)",
        "ROOT %ar = f32[512] all-reduce(f32[512] %p0), "
        "replica_groups={{0,1}}, to_apply=%add",
    ]))
    fs = crosscheck_trace(_jx(_jrec("psum", 4096)), htr, _exp())
    assert _codes(fs) == ["collective-rewritten"], [str(f) for f in fs]
    # ...and a psum legitimately lowered to a one-shot all-gather of the
    # same input payload stays clean (family matching, not kind matching)
    htr_ag = parse_hlo(_entry_hlo([
        "%p0 = f32[1024] parameter(0)",
        "ROOT %ag = f32[2048] all-gather(f32[1024] %p0), "
        "replica_groups={{0,1}}, dimensions={0}",
    ]))
    assert crosscheck_trace(_jx(_jrec("psum", 4096)), htr_ag, _exp()) == []


def test_negative_trip_count_mismatch():
    """Per-issue payloads agree but the HLO while runs 2 trips against the
    jaxpr's 4-trip scan: only the execution multiplier diverged."""
    comps = (
        "%body (bp: (f32[1024], s32[])) -> (f32[1024], s32[]) {\n"
        "  %bp = (f32[1024], s32[]) parameter(0)\n"
        "  %gteb = f32[1024] get-tuple-element((f32[1024], s32[]) %bp), "
        "index=0\n"
        "  %arb = f32[1024] all-reduce(f32[1024] %gteb), "
        "replica_groups={{0,1}}, to_apply=%add\n"
        "  %iv = s32[] get-tuple-element((f32[1024], s32[]) %bp), index=1\n"
        "  ROOT %tup = (f32[1024], s32[]) tuple(f32[1024] %arb, s32[] %iv)\n"
        "}\n\n"
        "%cond (cp: (f32[1024], s32[])) -> pred[] {\n"
        "  %cp = (f32[1024], s32[]) parameter(0)\n"
        "  %iv2 = s32[] get-tuple-element((f32[1024], s32[]) %cp), index=1\n"
        "  %c2 = s32[] constant(2)\n"
        "  ROOT %cmp = pred[] compare(s32[] %iv2, s32[] %c2), direction=LT\n"
        "}\n\n")
    htr = parse_hlo(_entry_hlo([
        "%p0 = f32[1024] parameter(0)",
        "%z = s32[] constant(0)",
        "%t0 = (f32[1024], s32[]) tuple(f32[1024] %p0, s32[] %z)",
        "%w = (f32[1024], s32[]) while((f32[1024], s32[]) %t0), "
        "condition=%cond, body=%body",
        "ROOT %res = f32[1024] get-tuple-element((f32[1024], s32[]) %w), "
        "index=0",
    ], extra_comps=comps))
    (rec,) = htr.records
    assert rec.trips == 2
    fs = crosscheck_trace(_jx(_jrec("psum", 4096, trips=4)), htr, _exp())
    assert _codes(fs) == ["trip-count-mismatch"], [str(f) for f in fs]
    # the matching trip count is clean
    assert crosscheck_trace(_jx(_jrec("psum", 4096, trips=2)), htr,
                            _exp()) == []


def test_negative_wire_widened_post_spmd():
    """A convert from int8 feeding an fp32 collective: the wire format was
    widened after partitioning (dequantize-then-communicate)."""
    htr = parse_hlo(_entry_hlo([
        "%p0 = s8[1024] parameter(0)",
        "%cv = f32[1024] convert(s8[1024] %p0)",
        "ROOT %ar = f32[1024] all-reduce(f32[1024] %cv), "
        "replica_groups={{0,1}}, to_apply=%add",
    ]))
    (rec,) = htr.records
    assert rec.fed_by_convert == "int8"
    fs = crosscheck_trace(_jx(_jrec("psum", 4096)), htr, _exp())
    assert _codes(fs) == ["wire-widened-post-spmd"], [str(f) for f in fs]
    # a narrowing convert (quantize before the wire) is healthy
    htr_n = parse_hlo(_entry_hlo([
        "%p0 = f32[4096] parameter(0)",
        "%cv = s8[4096] convert(f32[4096] %p0)",
        "ROOT %ar = s8[4096] all-reduce(s8[4096] %cv), "
        "replica_groups={{0,1}}, to_apply=%add",
    ]))
    assert crosscheck_trace(_jx(_jrec("psum", 4096, dtype="int8")), htr_n,
                            _exp()) == []


def test_negative_dcn_misrouted():
    """A replica group spanning the pod stride in a single-tier program —
    and, the other direction, a hierarchical program whose compiled groups
    never span it (the two-tier plan was flattened)."""
    spanning = _entry_hlo([
        "%p0 = f32[256] parameter(0)",
        "ROOT %ag = f32[512] all-gather(f32[256] %p0), "
        "replica_groups={{0,2}}, dimensions={0}",
    ])
    htr = parse_hlo(spanning, pod_stride=2)
    fs = crosscheck_trace(_jx(_jrec("all_gather", 1024)), htr, _exp(n=4))
    assert _codes(fs) == ["dcn-misrouted"], [str(f) for f in fs]
    # hierarchical expectation, intra-only groups -> flattened hierarchy
    intra = _entry_hlo([
        "%p0 = f32[256] parameter(0)",
        "ROOT %ag = f32[512] all-gather(f32[256] %p0), "
        "replica_groups={{0,1}}, dimensions={0}",
    ])
    htr2 = parse_hlo(intra, pod_stride=2)
    fs2 = crosscheck_trace(_jx(_jrec("all_gather", 1024)), htr2,
                           _exp(n=4, dcn_axis="pod"))
    assert _codes(fs2) == ["dcn-misrouted"], [str(f) for f in fs2]
    # and the intra-tier group in a single-tier program is clean
    assert crosscheck_trace(_jx(_jrec("all_gather", 1024)),
                            parse_hlo(intra, pod_stride=2), _exp(n=4)) == []


def test_negative_overlap_lost_in_compilation():
    """An async start/done pair with nothing scheduled inside the window
    hides no compute; the same pair with a real op between stays clean."""
    empty = parse_hlo(_entry_hlo([
        "%p0 = f32[1024] parameter(0)",
        "%ars = (f32[1024], f32[1024]) all-reduce-start(f32[1024] %p0), "
        "replica_groups={{0,1}}, to_apply=%add",
        "ROOT %ard = f32[1024] all-reduce-done((f32[1024], f32[1024]) %ars)",
    ]))
    fs = crosscheck_trace(_jx(_jrec("psum", 4096)), empty, _exp())
    assert _codes(fs) == ["overlap-lost-in-compilation"], [str(f) for f in fs]
    filled = parse_hlo(_entry_hlo([
        "%p0 = f32[1024] parameter(0)",
        "%ars = (f32[1024], f32[1024]) all-reduce-start(f32[1024] %p0), "
        "replica_groups={{0,1}}, to_apply=%add",
        "%mul = f32[1024] multiply(f32[1024] %p0, f32[1024] %p0)",
        "ROOT %ard = f32[1024] all-reduce-done((f32[1024], f32[1024]) %ars)",
    ]))
    assert crosscheck_trace(_jx(_jrec("psum", 4096)), filled, _exp()) == []
    # the static scheduler sees the same distinction: the empty window
    # exposes all wire time, the filled one hides some of it
    so_empty, so_filled = static_exposed_comm(empty), static_exposed_comm(filled)
    assert so_empty.n_async == 1 and so_empty.hidden_fraction == 0.0
    assert so_empty.exposed_s == so_empty.comm_s > 0.0
    assert so_filled.overlapped_s > 0.0
    assert so_filled.exposed_s < so_filled.comm_s


# --------------------------------------------- golden traces (multi-device)
LINT_CLI = r"""
import json
import os
import tempfile

from repro.core import program as prg
from repro.launch.lint import lint_program_on_mesh, main

path = os.path.join(tempfile.mkdtemp(), "lint_report.json")
assert main(["--hlo", "--all-named-programs", "--json", path]) == 0
data = json.load(open(path))
assert data["clean"] and data["hlo"]
for rep in data["reports"]:
    assert rep["codes"] == [], rep["findings"]
    # jaxpr-vs-HLO per-collective wire bytes agree within 5 percent
    for fam, d in rep["hlo"]["byte_deltas"].items():
        assert d["rel_delta"] <= 0.05, (rep["program"], fam, d)
# the hierarchical two-tier path: int8 chunked pipeline on a pod x data mesh
rep = lint_program_on_mesh(
    prg.train_step_program(overlap=True, compress_bits=8, chunks=2,
                           bucket_bytes=1 << 20),
    dcn=2, hlo=True)
assert rep["codes"] == [], rep["findings"]
print("ALL_OK")
"""


@pytest.mark.slow
@pytest.mark.parametrize("n", [4, 8])
def test_lint_cli_clean_multi_device(n):
    """`python -m repro.launch.lint --hlo --all-named-programs` exits 0 —
    every named program is clean at BOTH levels (jaxpr rules and the
    compiled-HLO cross-check) on real multi-device meshes, with jaxpr-vs-HLO
    wire bytes within the 5% tolerance, and `--json` round-trips."""
    assert "ALL_OK" in run_devices(LINT_CLI, n, timeout=560)


def test_lint_cli_json_report(tmp_path):
    """`--json` writes the machine-readable report (single program, one
    device: fast enough for tier-1)."""
    path = tmp_path / "report.json"
    import json

    assert lint_main(["allreduce", "--devices", "1", "--hlo",
                      "--json", str(path)]) == 0
    data = json.loads(path.read_text())
    assert data["clean"] and data["hlo"]
    (rep,) = data["reports"]
    assert rep["program"] == "allreduce" and rep["codes"] == []
    assert {"records", "ops", "byte_deltas", "static_overlap"} \
        <= set(rep["hlo"])
