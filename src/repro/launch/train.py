"""Production training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --shape train_4k \
      --steps 100 [--reduced] [--mesh 2x4] [--microbatches 4] [--resume] \
      [--residual-shard] [--fused-qkv] [--policy artifacts/policy.json] \
      [--calibration artifacts/bench/calibration.json] \
      [--explicit-dp] [--bucket-bytes N] [--overlap] [--chunks C] \
      [--compress-bits {0,8,auto}] [--zero] \
      [--faults messy:0|PLAN.json] [--guard] [--straggler-action sync]

--reduced cuts the config and shape to CPU size; without it the published
widths run (chip_smoke.py drives them on a TPU).  The mesh string "DxM" builds
(data=D, model=M) over the available devices; "PxDxM" adds the pod axis.
Without --mesh, a best-effort host mesh is used.
"""
from __future__ import annotations

import argparse
import dataclasses
import sys

from ..configs.base import SHAPES, get_config, list_configs, shape_applicable
from ..core import program as prg
from ..core.autotune import CollectivePolicy
from ..optim import OptConfig
from ..runtime.train import Trainer, TrainConfig
from .compile_cache import use_compile_cache
from .mesh import make_host_mesh, make_mesh


def parse_mesh(spec: str):
    dims = [int(x) for x in spec.lower().split("x")]
    if len(dims) == 2:
        return make_mesh(tuple(dims), ("data", "model"))
    if len(dims) == 3:
        return make_mesh(tuple(dims), ("pod", "data", "model"))
    raise SystemExit(f"bad --mesh {spec!r} (want DxM or PxDxM)")


def resolve_step_program(args, mesh, plan):
    """One place for the explicit-DP flag implications, mesh validation, and
    wire resolution.  Returns ``(program, dcn_axis)``: the StepProgram the
    runtime compiles and the pricer prices, or ``(None, None)`` when the XLA
    SPMD path runs (it chooses its own collectives — no program to plan).
    """
    if args.overlap or args.zero:
        args.explicit_dp = True  # both are explicit-DP execution modes
    dcn_axis = None
    if args.explicit_dp:
        if mesh is None:
            raise SystemExit("--explicit-dp needs multiple devices (set "
                             "XLA_FLAGS=--xla_force_host_platform_device_count=N "
                             "on a single-device host)")
        if mesh.shape.get("model", 1) > 1:
            raise SystemExit("--explicit-dp needs a pure-DP mesh (model dim 1); "
                             f"got mesh {dict(mesh.shape)}")
        if mesh.shape.get("pod", 1) > 1:
            dcn_axis = "pod"  # hierarchical allreduce over DCN when two-level
    if args.compress_bits == "auto":
        # the plan's calibrated per-tier wire decision (core.wire), restricted
        # to what the runtime's wire can realize: int8 rides the gather over
        # the DP axis, so on a flat mesh that gather spans the whole fabric
        # (any planned lossy tier pays), while on a two-level mesh the inter
        # leg stays fp32 and only a lossy *intra* decision is realizable.  A
        # bf16-planned tier maps to the int8 error-feedback wire (the only
        # lossy format the trainer implements — strictly fewer bytes, and
        # error feedback where bf16 would round silently).
        from ..core.wire import gather_wins
        wire = (plan or CollectivePolicy.from_model()).wire
        if args.zero:
            # the ZeRO all-gather (param return) leg realizes the *idealized*
            # multiplier at any endpoint count — each device contributes its
            # 1/n shard exactly once — so there is no gather_wins gate: any
            # planned lossy tier is worth compressing.
            realizable = args.explicit_dp and wire.compresses
        else:
            realizable = args.explicit_dp and (
                (wire.intra != "fp32") if dcn_axis is not None
                else wire.compresses)
            # the realized int8 gather must also win at the mesh's actual
            # gather axis size — above 8 endpoints it moves more bytes than
            # fp32.  Without --explicit-dp there is no wire to compress: auto
            # resolves to 0 (only a literal 8 hard-errors below).
            n_gather = mesh.shape.get("data", 1) if mesh is not None else 1
            realizable = realizable and gather_wins(n_gather)
        compress_bits = 8 if realizable else 0
        print(f"wire: {wire.intra}/{wire.inter} -> compress_bits={compress_bits}")
    else:
        try:
            compress_bits = int(args.compress_bits)
        except ValueError:
            raise SystemExit(f"--compress-bits {args.compress_bits!r}: "
                             f"want 0, 8, or auto")
    if compress_bits and not args.explicit_dp:
        raise SystemExit("--compress-bits needs --explicit-dp (the XLA SPMD "
                         "path chooses its own collectives)")
    if not args.explicit_dp:
        return None, None
    program = prg.train_step_program(
        overlap=args.overlap, zero=args.zero, compress_bits=compress_bits,
        chunks=args.chunks, microbatches=args.microbatches,
        bucket_bytes=args.bucket_bytes)
    return program, dcn_axis


def main(argv=None):
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_configs())
    ap.add_argument("--shape", default="train_4k", choices=list(SHAPES))
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--mesh", default=None)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=50)
    ap.add_argument("--ckpt-dir", default="artifacts/train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--residual-shard", action="store_true")
    ap.add_argument("--fused-qkv", action="store_true")
    ap.add_argument("--fast-norm", action="store_true")
    ap.add_argument("--policy", default=None,
                    help="collective policy JSON (core.autotune); informational "
                         "for the XLA path, binding for explicit-DP runs")
    ap.add_argument("--calibration", default=None,
                    help="measured CalibrationProfile JSON (core.calibrate); "
                         "builds a policy re-ranked from the measured fits "
                         "(mutually exclusive with --policy)")
    ap.add_argument("--explicit-dp", action="store_true",
                    help="shard_map DP trainer with CommPlan-dispatched gradient "
                         "collectives (requires a pure-DP mesh: model dim 1)")
    ap.add_argument("--bucket-bytes", type=int, default=None,
                    help="gradient bucket size for --explicit-dp (default: the "
                         "plan's latency/bandwidth crossover; 0 = per-tensor)")
    ap.add_argument("--compress-bits", default="0",
                    help="int8 error-feedback wire compression for "
                         "--explicit-dp: 8 = on (composes with --overlap/"
                         "--chunks via the per-bucket codec), 0 = fp32 wire, "
                         "auto = compress iff the plan's calibrated wire "
                         "decision picks a lossy format on a tier the "
                         "runtime's int8 wire rides (the DP-axis gather; the "
                         "inter leg of a two-level mesh stays fp32)")
    ap.add_argument("--overlap", action="store_true",
                    help="overlap-aware explicit-DP execution (implies "
                         "--explicit-dp): reverse-layer-order gradient buckets "
                         "on a scan-carried issue schedule; with --microbatches "
                         "each bucket's reduction overlaps the next "
                         "microbatch's backward; on a PxDx1 mesh buckets run "
                         "the chunked hierarchical pipeline")
    ap.add_argument("--chunks", type=int, default=None,
                    help="hierarchical pipeline depth for --overlap (default: "
                         "chosen from the plan's per-tier alpha-beta fits)")
    ap.add_argument("--zero", action="store_true",
                    help="ZeRO-style sharded optimizer (implies --explicit-dp): "
                         "reduce-scatter the packed gradient carrier, AdamW "
                         "over each device's shard (fp32 m/v carrier-sharded, "
                         "optimizer memory / DP degree), all-gather updated "
                         "params at the wire dtype; --compress-bits 8 makes "
                         "the all-gather leg int8")
    ap.add_argument("--straggler-threshold", type=float, default=2.5)
    ap.add_argument("--straggler-action", default="log",
                    choices=["log", "sync", "skip"],
                    help="on a detected straggler step: log it, 'sync' (insert "
                         "a resynchronizing barrier), or 'skip' (revert the "
                         "step's update — rejected with --zero, where optimizer "
                         "state is sharded)")
    ap.add_argument("--faults", default=None,
                    help="fault-injection plan: 'messy[:SEED]' (canonical "
                         "messy-fabric plan, core.faults), 'nodeloss[:SEED]', "
                         "or a FaultPlan JSON path; faults perturb the "
                         "simulated fabric deterministically")
    ap.add_argument("--guard", action="store_true",
                    help="drift-aware execution (runtime.guard): watch step "
                         "times against an EWMA band, on sustained drift "
                         "re-probe/refit/re-rank the plan and lint-gate the "
                         "swap; guard events land in "
                         "artifacts/guard_report.json")
    ap.add_argument("--lint", action="store_true",
                    help="statically lint the compiled step against its "
                         "StepProgram before training (analysis.lint); any "
                         "finding refuses to start the run")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    cfg = dataclasses.replace(cfg, residual_shard=args.residual_shard,
                              fused_qkv=args.fused_qkv and not cfg.qkv_bias,
                              fast_norm=args.fast_norm)
    shape = SHAPES[args.shape]
    if args.reduced:
        shape = shape.reduced()
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        raise SystemExit(why)
    if shape.kind != "train":
        raise SystemExit(f"--shape {args.shape} is a {shape.kind} shape; use launch.serve")

    # explicit-DP wants a pure-DP default mesh (model dim 1); --overlap/--zero
    # imply explicit-DP (resolve_step_program re-asserts the implication)
    explicit = args.explicit_dp or args.overlap or args.zero
    mesh = parse_mesh(args.mesh) if args.mesh \
        else make_host_mesh(model=1 if explicit else 0)
    policy = None
    if args.policy and args.calibration:
        raise SystemExit("--policy and --calibration are mutually exclusive "
                         "(a policy file already carries its tables; "
                         "--calibration re-ranks them from the measured fits)")
    if args.policy:
        try:
            policy = CollectivePolicy.load(args.policy)
        except FileNotFoundError:
            raise SystemExit(f"--policy {args.policy}: file not found")
        except (KeyError, ValueError, TypeError) as e:
            raise SystemExit(f"--policy {args.policy}: not a policy file ({e})")
    if args.calibration:
        from ..core import hw
        from ..core.calibrate import CalibrationProfile
        from ..core.costmodel import make_comm_model
        try:
            profile = CalibrationProfile.load(args.calibration)
        except FileNotFoundError:
            raise SystemExit(f"--calibration {args.calibration}: file not found")
        except (KeyError, ValueError, TypeError) as e:
            raise SystemExit(f"--calibration {args.calibration}: "
                             f"not a calibration file ({e})")
        # re-rank the topology the profile was measured against, not a default
        system = profile.system if profile.system in hw.SYSTEMS else "tpu_v5e"
        policy = CollectivePolicy.from_model(make_comm_model(system),
                                             calibration=profile)
        print(f"calibration: {args.calibration} (schema v{profile.version}, "
              f"system={system}, {len(profile.params)} fitted keys) -> "
              f"re-ranked plan, bucket={policy.bucket_bytes} B")
    if policy is not None:
        src = policy.meta.get("source", "?")
        print(f"policy: {args.policy or args.calibration} (source={src}, "
              f"bucket={policy.bucket_bytes} B, "
              f"wire={policy.wire.intra}/{policy.wire.inter})")
    program, dcn_axis = resolve_step_program(args, mesh, policy)
    if program is not None:
        print(f"program: {program.name} "
              f"({' -> '.join(nd.kind for nd in program.nodes)})")
    if args.lint:
        if program is None:
            raise SystemExit("--lint needs a step program to lint against: "
                             "the XLA SPMD path (no --explicit-dp/--overlap/"
                             "--zero) chooses its own collectives")
        from .lint import lint_program_on_mesh
        n_data = mesh.shape.get("data", 1) if mesh is not None else 1
        n_pod = mesh.shape.get("pod", 1) if mesh is not None else 1
        # both levels: jaxpr rules plus the compiled-HLO cross-check — the
        # gate covers what the SPMD partitioner did, not just the intent
        rep = lint_program_on_mesh(program, n_devices=n_pod * n_data,
                                   policy=policy, dcn=n_pod, hlo=True)
        if rep["findings"]:
            for f in rep["findings"]:
                print(f"lint: {f}", file=sys.stderr)
            raise SystemExit(
                f"lint: {len(rep['findings'])} finding(s) on program "
                f"{program.name!r} — refusing to start the run")
        h = rep["hlo"]
        print(f"lint: program {program.name} clean "
              f"({rep['records']} collectives, {h['records']} compiled, "
              f"{h['n_async']} async, {rep['seconds']:.2f}s)")

    faults = None
    if args.faults:
        from ..core.faults import FaultPlan
        faults = FaultPlan.resolve(args.faults, steps=args.steps)
        print(f"faults: {args.faults} -> {len(faults.events)} events "
              f"(seed={faults.seed})")

    trainer = Trainer(
        cfg, shape,
        OptConfig(peak_lr=args.lr, warmup_steps=args.warmup, decay_steps=args.steps),
        TrainConfig(steps=args.steps, microbatches=args.microbatches,
                    ckpt_every=args.ckpt_every, ckpt_dir=args.ckpt_dir,
                    log_every=1 if args.steps <= 10 else 10,
                    straggler_threshold=args.straggler_threshold,
                    straggler_action=args.straggler_action,
                    explicit_dp=args.explicit_dp, dcn_axis=dcn_axis,
                    policy=policy, program=program,
                    faults=faults, guard=args.guard),
        mesh=mesh,
    )
    result = trainer.run(resume=args.resume)
    losses = [m["loss"] for m in result["metrics"]]
    if losses:
        print(f"done: step {result['final_step']}, loss {losses[0]:.4f} -> "
              f"{losses[-1]:.4f}, stragglers {result['straggler_events']}")
    if result.get("retries") or result.get("skipped_steps"):
        print(f"recovery: {result['retries']} transient retr"
              f"{'y' if result['retries'] == 1 else 'ies'}, "
              f"{result.get('skipped_steps', 0)} skipped step(s)")
    if args.guard:
        import json
        import os
        rep = result.get("guard", {})
        os.makedirs("artifacts", exist_ok=True)
        path = os.path.join("artifacts", "guard_report.json")
        with open(path, "w") as f:
            json.dump(rep, f, indent=2, sort_keys=True)
        print(f"guard: {rep.get('n_replans', 0)} replan(s), "
              f"{rep.get('n_rejected', 0)} rejected, "
              f"{rep.get('n_events', 0)} event(s) -> {path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
