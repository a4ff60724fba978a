#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

  python3 benchmarks/chip/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (an entry of ``workloads`` in BENCHMARK.json) names a configuration
and a traffic mix, found by name under this directory.  Set-up makes the
weights and inputs from ``--seed``, warms up the cell's own shapes and counts
as ``setup_s``; the window then runs for ``--seconds``.  With ``--trace 0``
the result carries the cell's end-to-end metrics; with ``--trace 1`` the
window is profiled and the result carries its per-layer metrics.  After the
window the outputs are compared with the plain reference; each number
compared is printed beside its limit on the last lines of stderr and under
``checks`` in the result.  The last line of stdout is the result:

  {"correct": ..., "attempted": ..., "failed": ..., "metrics": {...},
   "device": {...}, ["breakdown": {...},] "info": {...}, "checks": {...}}

``info`` holds the step times or batch latencies, the reference's seconds
and the run's own; ``checks`` comes last, each number with its limit.

Exits nonzero with no result when JAX finds no TPU or fewer chips than the
cell asks for, or when the checkout's ``src/`` is missing.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
SRC = HERE.parents[1] / "src"


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def metrics_of(cell, res, trace: bool):
    if not trace:
        return {m["name"]: {"value": res["end_to_end"][m["name"]], "unit": m["unit"]}
                for m in cell.end_to_end}
    from chipbench import spec

    out = {}
    for m in cell.per_layer:
        v = spec.metric_reader(m["name"])(res["ctx"])
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def run(args, bench=None, require_chip: bool = True, t_start: float = T_START):
    """Resolve, run and report one cell; returns the result dict (the caller
    prints it).  ``require_chip=False`` skips the look for a TPU (tests)."""
    from chipbench import check, device, reduce, spec

    cell = spec.resolve(bench or spec.load_benchmark(), args.workload)
    fam = spec.family(cell.config["reference"])
    if require_chip:
        devs = device.chips(cell.chips)
        peaks = device.peaks(devs[0].device_kind)
    else:
        import jax

        devs = jax.devices()[:cell.chips]
        peaks = device.peaks(cell.traffic.get("test_device_kind", "TPU v5 lite"))
    runner = spec.runner(cell.traffic["kind"])
    res = runner.run_cell(cell, fam, args.seed, args.seconds, bool(args.trace),
                          devs, t_start)
    res["ctx"]["peaks"] = peaks
    checks = check.verdict(res["numbers"], cell.traffic["limits"])
    out = {"correct": check.is_correct(checks) and res["failed"] == 0,
           "attempted": res["attempted"], "failed": res["failed"],
           "metrics": metrics_of(cell, res, bool(args.trace)),
           "device": device.describe(devs, res["peak_bytes"])}
    rec = res["ctx"].get("trace")
    if rec is not None and rec["devices"]:
        lo, hi = reduce.window_ns(rec)
        busy = reduce.busy(rec)
        out["device"]["busy_s"] = sum(busy.values()) / len(busy)
        out["device"]["window_s"] = (hi - lo) * 1e-9
        out["breakdown"] = {"device_ops": reduce.top_ops(rec),
                            "idle_gaps": reduce.idle_gaps(rec)}
    out["info"] = dict(res["info"], trace_read_s=res["ctx"].get("trace_read_s"),
                       run_s=time.perf_counter() - t_start)
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    args = parse(argv)
    sys.path.insert(0, str(HERE))
    from chipbench import check, device, spec

    try:
        bench = spec.load_benchmark()
        spec.resolve(bench, args.workload)
    except spec.SpecError as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    if not (SRC / "repro").is_dir():
        print(f"run.py: no {SRC / 'repro'}: run it from a checkout of the repo",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        cell = spec.resolve(bench, args.workload)
        device.peaks(device.chips(cell.chips)[0].device_kind)
    except (device.DeviceError, RuntimeError) as e:
        print(f"run.py: {e}; refusing to run", file=sys.stderr)
        return 3
    out = run(args, bench)
    check.print_checks(out["checks"])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
