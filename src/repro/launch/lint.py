"""CommLint launcher: statically verify compiled steps against their programs.

  PYTHONPATH=src python -m repro.launch.lint --all-named-programs
  PYTHONPATH=src python -m repro.launch.lint --hlo --json report.json \\
      zero_int8 moe_alltoall --devices 4

For every requested StepProgram this builds the step on a CPU mesh (a toy
multi-leaf model for the dense-gradient programs, the reduced MoE config for
the AllToAll program), extracts its CollectiveTrace (`analysis.trace`) from
the jaxpr — no compilation or execution, tracing only — compiles the program
into an ExpectedTrace (`analysis.expect`), and reports every lint finding
(`analysis.lint`).  `--hlo` adds the compiled-artifact level (ScheduleLint):
the step is actually compiled, its post-SPMD HLO parsed into an HloTrace
(`analysis.hlo_trace`) and cross-checked against the jaxpr trace and the
program (`analysis.schedule`), with the static exposed-comm estimate in the
report.  Exit status is the number of programs with findings, so CI can gate
on it; `--json PATH` writes the full reports machine-readably.
`launch.train --lint` and the dryrun roofline reuse `lint_program_on_mesh`.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Dict, List, Optional, Sequence, Tuple

from ..core import program as prg
from ..core.autotune import CollectivePolicy


class _LintModel:
    """Multi-leaf toy model: enough leaves to exercise packing, small enough
    that tracing is instant.  Loss touches every leaf and the batch."""

    @staticmethod
    def loss(params, batch):
        import jax
        import jax.numpy as jnp

        s = sum(jnp.sum(p) for p in jax.tree.leaves(params))
        return (s - 1.0) ** 2 + 0.0 * jnp.mean(batch["x"])


def _dense_fixture(n_devices: int, n_leaves: int = 6, leaf_elems: int = 65):
    import jax.numpy as jnp

    params = {f"w{i}": jnp.ones((leaf_elems + i,), jnp.float32)
              for i in range(n_leaves)}
    batch = {"x": jnp.ones((2 * n_devices,), jnp.float32)}
    return params, batch


def _make_mesh(shape: Tuple[int, ...], names: Tuple[str, ...]):
    import jax
    from jax.sharding import AxisType

    n = 1
    for s in shape:
        n *= s
    return jax.make_mesh(shape, names, devices=jax.devices()[:n],
                         axis_types=(AxisType.Auto,) * len(shape))


def lint_program_on_mesh(program: prg.StepProgram,
                         n_devices: Optional[int] = None,
                         policy: Optional[CollectivePolicy] = None,
                         dcn: int = 1,
                         hlo: bool = False) -> Dict:
    """Build `program`'s step on a CPU mesh, trace it, lint it.

    `n_devices` is the total mesh size (defaults to every visible device);
    `dcn > 1` splits off a leading "pod" axis of that size to lint the
    hierarchical two-tier path.  The MoE program clamps the mesh to the
    expert count (the EP axis must divide it).  `hlo=True` additionally
    compiles the step (`step.lower`), parses the post-SPMD module into an
    HloTrace, cross-checks it against the jaxpr trace
    (`analysis.schedule.crosscheck_trace`), and reports the static
    exposed-comm estimate under "hlo".  Returns a report dict with the
    findings as strings under "findings" and their codes under "codes";
    HLO-level findings are merged into the same lists.
    """
    import jax

    from ..analysis.expect import expected_trace
    from ..analysis.lint import lint_trace
    from ..analysis.trace import trace_step
    from ..optim import adamw

    t0 = time.perf_counter()
    program.validate()
    policy = policy or CollectivePolicy.from_model()
    n = n_devices or len(jax.devices())
    opt = adamw.OptConfig(peak_lr=1e-2, warmup_steps=0, decay_steps=10)

    if program.schedule == "moe_alltoall":
        from ..configs.base import get_config
        from ..runtime import moe_step as ms
        from ..runtime.steps import build_program_step

        cfg = get_config("deepseek-moe-16b").reduced()
        n = min(n, cfg.n_experts)  # EP axis must divide the expert count
        mesh = _make_mesh((n,), ("data",))
        params = ms.moe_ep_params(cfg, jax.random.PRNGKey(0))
        batch = ms.moe_ep_batch(cfg, jax.random.PRNGKey(1), 2 * n, 16)
        step = build_program_step(cfg, opt, mesh, program, policy=policy)
        import jax.numpy as jnp
        args = (params, adamw.init_opt_state(params), batch,
                jnp.zeros((), jnp.float32))
        expected = expected_trace(program, n_devices=n, plan=policy)
    else:
        from ..runtime.steps import build_program_step

        dcn = max(int(dcn), 1)
        if dcn > 1 and n // dcn >= 1 and n % dcn == 0:
            mesh = _make_mesh((dcn, n // dcn), ("pod", "data"))
            dcn_axis = "pod"
        else:
            mesh = _make_mesh((n,), ("data",))
            dcn_axis = None
        params, batch = _dense_fixture(n)
        step = build_program_step(_LintModel(), opt, mesh, program,
                                  policy=policy, dcn_axis=dcn_axis)
        args = (params, step.init_opt_state(params), batch,
                step.init_error_state(params))
        grad_bytes = sum(p.size * p.dtype.itemsize
                         for p in jax.tree.leaves(params))
        expected = expected_trace(program, n_devices=n, grad_bytes=grad_bytes,
                                  plan=policy, dcn_axis=dcn_axis)

    trace = trace_step(step, *args)
    findings = lint_trace(trace, expected)
    report = {
        "program": program.name,
        "schedule": program.schedule,
        "n_devices": n,
        "records": len(trace.records),
        "kinds": sorted(trace.kinds()),
        "wire_bytes": trace.wire_bytes(),
        "byte_budget": expected.byte_budget,
    }
    if hlo:
        from ..analysis.hlo_trace import parse_hlo
        from ..analysis.schedule import (byte_deltas, crosscheck_trace,
                                         static_exposed_comm)

        # pod axis is the leading mesh axis, so its device-id stride is the
        # size of everything under it (row-major device order)
        pod_stride = (n // dcn) if dcn > 1 and n % dcn == 0 else 0
        lowered = step.lower(*args) if hasattr(step, "lower") \
            else jax.jit(lambda *a: step(*a)).lower(*args)
        htrace = parse_hlo(lowered.compile().as_text(),
                           pod_stride=pod_stride)
        findings = findings + crosscheck_trace(trace, htrace, expected)
        static = static_exposed_comm(htrace)
        report["hlo"] = {
            "records": len(htrace.records),
            "ops": htrace.counts(),
            "wire_bytes": htrace.wire_bytes(),
            "n_async": sum(r.is_async for r in htrace.records),
            "byte_deltas": byte_deltas(trace, htrace,
                                       wide_bytes=expected.wide_bytes),
            "static_overlap": static.row(),
        }
    report.update(
        codes=sorted({f.code for f in findings}),
        findings=[str(f) for f in findings],
        seconds=time.perf_counter() - t0,
    )
    return report


def lint_named_programs(names: Optional[Sequence[str]] = None,
                        n_devices: Optional[int] = None,
                        policy: Optional[CollectivePolicy] = None,
                        hlo: bool = False) -> List[Dict]:
    """Lint reports for the requested named programs (default: all)."""
    names = list(names) if names else sorted(prg.NAMED_PROGRAMS)
    return [lint_program_on_mesh(prg.named_program(nm), n_devices=n_devices,
                                 policy=policy, hlo=hlo)
            for nm in names]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="repro.launch.lint",
        description="Lint compiled steps against their StepProgram IR")
    ap.add_argument("programs", nargs="*",
                    help=f"named programs (default: all of "
                         f"{sorted(prg.NAMED_PROGRAMS)})")
    ap.add_argument("--all-named-programs", action="store_true",
                    help="lint every named StepProgram")
    ap.add_argument("--devices", type=int, default=None,
                    help="mesh size (default: every visible device)")
    ap.add_argument("--policy", default=None,
                    help="CollectivePolicy JSON to dispatch through")
    ap.add_argument("--hlo", action="store_true",
                    help="add the compiled-HLO level: compile each step, "
                         "cross-check the post-SPMD schedule against the "
                         "jaxpr trace, report the static exposed-comm "
                         "estimate")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the full reports as JSON (machine-readable "
                         "findings; CI uploads this as an artifact)")
    args = ap.parse_args(argv)

    names = None if (args.all_named_programs or not args.programs) \
        else args.programs
    for nm in names or ():
        if nm not in prg.NAMED_PROGRAMS:
            raise SystemExit(f"unknown program {nm!r} "
                             f"(have {sorted(prg.NAMED_PROGRAMS)})")
    policy = CollectivePolicy.load(args.policy) if args.policy else None

    reports = lint_named_programs(names, n_devices=args.devices,
                                  policy=policy, hlo=args.hlo)
    bad = 0
    for rep in reports:
        status = "clean" if not rep["findings"] else \
            f"{len(rep['findings'])} finding(s)"
        print(f"{rep['program']:16s} n={rep['n_devices']} "
              f"records={rep['records']:2d} kinds={','.join(rep['kinds'])} "
              f"wire={rep['wire_bytes']}B "
              f"({rep['seconds']:.2f}s) {status}")
        if "hlo" in rep:
            h = rep["hlo"]
            so = h["static_overlap"]
            deltas = ", ".join(
                f"{fam}:{d['rel_delta']:.1%}"
                for fam, d in sorted(h["byte_deltas"].items())) or "-"
            print(f"    hlo: records={h['records']} "
                  f"async={h['n_async']} wire={h['wire_bytes']:.0f}B "
                  f"deltas[{deltas}] "
                  f"static exposed={so['exposed_s']:.2e}s "
                  f"hidden={so['hidden_fraction']:.0%}")
        for f in rep["findings"]:
            print(f"    {f}")
        bad += bool(rep["findings"])
    print(f"lint: {len(reports)} program(s), "
          f"{sum(len(r['findings']) for r in reports)} finding(s)")
    if args.json:
        with open(args.json, "w") as fh:
            json.dump({"reports": reports, "hlo": args.hlo,
                       "clean": bad == 0}, fh, indent=2, sort_keys=True)
        print(f"wrote {args.json}")
    return bad


if __name__ == "__main__":
    sys.exit(main())
