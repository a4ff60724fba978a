#!/usr/bin/env python3
"""One traced run of a cell, and where its window's time went, by the
program's own spans, scopes and compile counter.

  python3 benchmarks/chip/anatomy.py --workload <name> --seed <n> --seconds <s> \
      [--out FILE]

Runs the cell as ``run.py --trace 1`` does and prints that result line; then
one JSON line more (also written to ``--out``):

* ``top_ops``: the ops with the most device time, each with its module, the
  program's named scope (``None``: in no scope; ``?``: a module the program
  did not note) and its ``op_name`` path;
* ``scopes``: device seconds by scope in each noted program's runs, and
  ``matched_share``: per module, the share of its device time whose ops
  the compiled texts name, with that time; ``rebuilt``: the noted programs
  that were compiled anew to read their text, where neither compile cache
  still held the executable that ran (0 when the texts are those that ran);
* ``idle_gaps_program``: device idle time by the innermost program span open
  during it, with the spans put on each device's clock at the low end, the
  middle and the high end of its offset bracket;
* ``spans``: count and mean self time of each program span;
* ``compiles``: the program's compile log (kind, function, seconds, whether a
  profile was being recorded);
* ``span_cost_us``: one span's enter and exit, without and with a profile
  being recorded.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from collections import defaultdict  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]


def anatomy(rec, scopes, modules, paths, k: int = 15):
    """Top ops with module and scope, and device seconds by scope in each
    noted program's runs."""
    from chipbench import program, reduce

    lo, hi = reduce.window_ns(rec)
    ops = defaultdict(float)
    found = defaultdict(lambda: [0.0, 0.0])     # module: [in the map, all]
    for d in rec["devices"].values():
        for s, e, mod, op, sc, leaf in program.tag_ops(d, scopes):
            if leaf and e > lo and s < hi:
                t = min(e, hi) - max(s, lo)
                ops[(mod, op, sc if mod in modules.values() else "?")] += t
                found[mod][0] += t if (mod, op) in scopes else 0.0
                found[mod][1] += t
    n = len(rec["devices"])
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:k]
    names = program.telemetry().SCOPES
    return ([[*key, v * 1e-9 / n, paths.get(key[:2])] for key, v in top],
            {name: program.scope_seconds(rec, scopes, names, mod)
             for name, mod in modules.items()},
            {mod: [a / b if b else None, b * 1e-9 / n] for mod, (a, b) in found.items()})


def span_cost_us(n: int = 20000):
    import tempfile

    import jax

    from repro import telemetry as tm

    def loop():
        t0 = time.perf_counter()
        for _ in range(n):
            with tm.span("serve.decode"):
                pass
        return (time.perf_counter() - t0) / n * 1e6

    off = loop()
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        on = loop()
        jax.profiler.stop_trace()
    for _ in range(n):
        tm.SPANS.pop()
    return {"off": off, "on": on}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    args.trace = 1
    import jax

    import run as harness
    from chipbench import check, program, spec
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()                 # as run.py's main sets them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    bench = spec.load_benchmark()
    cell = spec.resolve(bench, args.workload)
    runner = spec.runner(cell.traffic["kind"])
    kept = {}
    run_cell = runner.run_cell

    def keep(*a, **kw):
        kept["res"] = run_cell(*a, **kw)
        return kept["res"]

    runner.run_cell = keep
    out = harness.run(args, bench, t_start=T_START)
    check.print_checks(out["checks"])
    print(json.dumps(out), flush=True)

    ctx = kept["res"]["ctx"]
    tm = program.telemetry()
    rec = ctx["trace"]
    prog = program.programs(ctx)
    if prog is None:
        print("anatomy.py: no device trace or no program telemetry", file=sys.stderr)
        return 1
    t0 = time.perf_counter()
    top, by_prog, matched = anatomy(rec, prog["scopes"], prog["modules"],
                                    prog["op_names"])
    spans = list(tm.SPANS)
    selfs = program.self_times(spans)
    extra = {"workload": args.workload, "seed": args.seed,
             "modules": prog["modules"], "top_ops": top, "scopes": by_prog,
             "matched_share": matched, "rebuilt": prog["rebuilt"],
             "idle_gaps_program": {w: program.idle_gaps_program(
                 rec, spans, prog["modules"], w) for w in (0.0, 0.5, 1.0)},
             "spans": {k: [len(v), 1e-6 * sum(v) / len(v)] for k, v in selfs.items()},
             "compiles": tm.COMPILES.log,
             "analysis_s": time.perf_counter() - t0,
             "span_cost_us": span_cost_us()}
    line = json.dumps(extra)
    print(line, flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(out) + "\n" + line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
