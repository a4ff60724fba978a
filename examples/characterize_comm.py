"""Interconnect characterization — the paper's measurement campaign, end to end.

Runs the {mechanism} x {pattern} x {size} matrix on a forced-multi-device mesh
(the intra-node analog), prints the derived observations, then projects the
at-scale figures (9/10/13) from the calibrated cost models.

  PYTHONPATH=src python examples/characterize_comm.py [--devices 8]

NOTE: spawns itself with XLA_FLAGS to get multiple host devices.
"""
import argparse
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))


def inner(n_devices: int):
    import jax
    from jax.sharding import AxisType

    from repro.core.bench import print_records, write_csv
    from repro.core.calibrate import (CalibrationProfile, compare_to_model,
                                      plan_table_deltas, run_calibration)
    from repro.core.characterize import characterize_mesh, project_at_scale
    from repro.core.commplan import CommPlan
    from repro.core.costmodel import make_comm_model
    from repro.core.noise import NoiseModel

    mesh = jax.make_mesh((n_devices,), ("x",), axis_types=(AxisType.Auto,))
    print(f"== measuring on {n_devices} host devices (ICI analog) ==")
    model = make_comm_model("tpu_v5e")
    report = characterize_mesh(mesh, "x", sizes=(1 << 12, 1 << 16, 1 << 20),
                               iters=20, model=model)
    print_records(report.records)
    out = ROOT / "artifacts" / "bench"
    out.mkdir(parents=True, exist_ok=True)
    write_csv(str(out / "characterization.csv"), report.records)
    print("\n== observations (local evidence) ==")
    for k, v in report.observations.items():
        print(f"  {k}: {v}")
    print("\n== at-scale projection (Figs. 9/10/13 analog) ==")
    for row in project_at_scale("tpu_v5e", noise=NoiseModel.tpu_dcn()):
        print("  ", row)

    print("\n== calibration (measured alpha-beta fits vs the analytic model) ==")
    profile, records = run_calibration(mesh, "x",
                                       sizes=(1 << 12, 1 << 16, 1 << 20),
                                       iters=20, model=model,
                                       base_records=report.records)
    calib_path = out / "calibration.json"
    profile.save(str(calib_path))
    assert CalibrationProfile.load(str(calib_path)) == profile
    write_csv(str(out / "calibration_records.csv"), records)
    print(f"  artifact: {calib_path} "
          f"({len(profile.params)} fitted (mechanism, pattern, regime) keys)")
    for row in compare_to_model(profile, model):
        print(f"  {row['key']:38s} measured={row['measured_us']:9.1f}us "
              f"analytic={row['analytic_us']:9.1f}us "
              f"ratio={row['ratio']:7.2f} r2={row['r2']:.2f}")
    topo = model.two_level or model.graph
    analytic_plan = CommPlan.from_topology(topo, profile=model.profile)
    calibrated_plan = CommPlan.from_topology(topo, profile=model.profile,
                                             calibration=profile)
    deltas = plan_table_deltas(analytic_plan, calibrated_plan)
    print(f"  plan entries re-ranked by the measured profile: {len(deltas)} "
          f"(bucket {analytic_plan.bucket_bytes >> 10} -> "
          f"{calibrated_plan.bucket_bytes >> 10} KiB)")
    for d in deltas[:8]:
        print(f"    {d}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--_inner", action="store_true")
    args = ap.parse_args()
    if args._inner:
        inner(args.devices)
        return
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={args.devices}"
    env["JAX_PLATFORMS"] = "cpu"  # host devices are the measured fabric
    env["PYTHONPATH"] = str(ROOT / "src") + os.pathsep + env.get("PYTHONPATH", "")
    sys.exit(subprocess.call([sys.executable, __file__, "--devices",
                              str(args.devices), "--_inner"], env=env))


if __name__ == "__main__":
    main()
