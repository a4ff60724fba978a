#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, at a cell's own size.

  python3 benchmarks/chip/control.py --workload <name> --seeds 11,12,13 \
      [--faults half_batch,no_exchange] [--no-sound]

For each seed, in one process that holds the cell's chips:
  * sound: the program's numbers against the float32 reference, as a run
    compares them (training: the first three steps; serving: one batch);
  * control: the reference computed with float8 (e4m3) operands in the
    program's place, compared the same way;
  * for each fault named by ``--faults``, the program with that fault
    planted (a step that leaves its state unchanged, half of each microbatch
    left out, the gradient exchange left out; for serving a token altered
    where it is produced, a decode that returns its state unchanged).
Each reading is printed as one JSON line; no timed window is run.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]


@contextlib.contextmanager
def patched(obj, name, make):
    own = name in vars(obj)
    orig = getattr(obj, name)
    setattr(obj, name, make(orig))
    try:
        yield
    finally:
        if own:
            setattr(obj, name, orig)
        else:
            delattr(obj, name)


# ------------------------------------------------------------------ faults
def fault_unchanged_state():
    from repro.optim import adamw

    def make(orig):
        def apply_updates(params, grads, state, cfg):
            _, _, metrics = orig(params, grads, state, cfg)
            return params, state, metrics
        return apply_updates
    return patched(adamw, "apply_updates", make)


def fault_half_batch():
    from repro.models.model import Model

    def make(orig):
        def loss(self, params, batch):
            return orig(self, params, {k: v[: v.shape[0] // 2] for k, v in batch.items()})
        return loss
    return patched(Model, "loss", make)


def fault_no_exchange():
    from repro.core.commplan import CommPlan

    return patched(CommPlan, "all_reduce",
                   lambda orig: lambda self, x, axis, axis_size, dcn_axis=None: x)


TRAIN_FAULTS = {"unchanged_state": fault_unchanged_state,
                "half_batch": fault_half_batch,
                "no_exchange": fault_no_exchange}


def fault_altered_token(server):
    """Every request's token at decode step 5 is replaced by its successor."""
    def make(orig):
        calls = {"n": 0}

        def sample(logits, serve, key):
            tok = orig(logits, serve, key)
            calls["n"] += 1
            return (tok + 1) % server.cfg.vocab if calls["n"] == 6 else tok
        return sample
    return patched(server, "_sample", make)


def fault_decode_state_unchanged(server):
    def make(orig):
        def decode(params, cache, tok, pos):
            logits, _ = orig(params, cache, tok, pos)
            return logits, cache
        return decode
    # under the harness's counting wrapper, where there is one
    target = server._decode
    return patched(target, "fn", make) if hasattr(target, "fn") \
        else patched(server, "_decode", make)


SERVE_FAULTS = {"altered_token": fault_altered_token,
                "decode_state_unchanged": fault_decode_state_unchanged}


# ---------------------------------------------------------------- readings
def train_readings(cell, fam, seed, devs, faults, sound=True):
    from chipbench import precision, train

    ref = train.reference(fam, cell.config, cell.traffic, seed, devs)
    yield "reference_s", {"seconds": ref["seconds"]}

    def program():
        prog = train.build(cell, fam, seed)
        try:
            return train.first_steps(prog, fam, cell.traffic)
        finally:
            train.release(prog)

    if sound:
        yield "sound", train.numbers(program(), ref, cell.chips)
    ctrl = train.reference(fam, cell.config, cell.traffic, seed, devs, rnd=precision.fp8)
    ctrl["replica_diff"] = 0.0
    yield "control_fp8", train.numbers(ctrl, ref, cell.chips)
    for name in faults:
        if name == "no_exchange" and cell.chips == 1:
            continue
        with TRAIN_FAULTS[name]():
            yield f"fault_{name}", train.numbers(program(), ref, cell.chips)


def serve_readings(cell, fam, seed, devs, faults, sound=True):
    import numpy as np

    from chipbench import precision, serve, traffic as T
    from repro.runtime.serve import ServeConfig

    t = cell.traffic
    V = fam.sizes(cell.config)["V"]

    def one_batch(plant=None):
        server = serve.build(cell, fam, seed)
        serve.warm(server, seed, t, V)
        ctx = plant(server) if plant else contextlib.nullcontext()
        with ctx:
            ids = server.generate(serve.prompts(seed, 0, t, V),
                                  ServeConfig(max_new_tokens=t["new_tokens"]))
        server.params = server.model = None
        del server, ctx
        gc.collect()
        return [np.asarray(ids)]

    served = one_batch()
    sample = T.sample(seed, t["batch"], t["sample_requests"])
    ref = serve.reference_gaps(fam, cell.config, seed, t, served, sample)
    yield "sound", {"served_gap": float(np.max(ref["gaps"])),
                    "reference_s": ref["seconds"]}
    ctrl = serve.reference_gaps(fam, cell.config, seed, t, served, sample,
                                rnd=precision.fp8)
    lg = np.asarray(ref["logits"])
    top = ctrl["top"]
    gaps = lg.max(-1) - np.take_along_axis(lg, top[..., None], -1)[..., 0]
    yield "control_fp8", {"served_gap": float(gaps.max()),
                          "tokens_differing": int((top != ref["top"]).sum())}
    ref.pop("logits")
    del ctrl, lg
    for name in faults:
        served_f = one_batch(lambda s: SERVE_FAULTS[name](s))
        r = serve.reference_gaps(fam, cell.config, seed, t, served_f, sample)
        yield f"fault_{name}", {"served_gap": float(np.max(r["gaps"]))}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", default="",
                    help="comma list of faults to plant, one at a time")
    ap.add_argument("--no-sound", action="store_true",
                    help="skip the program's own readings (a run prints them)")
    args = ap.parse_args(argv)
    import jax

    from chipbench import device, spec
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = spec.resolve(spec.load_benchmark(), args.workload)
    fam = spec.family(cell.config["reference"])
    devs = device.chips(cell.chips)
    kind = cell.traffic["kind"]
    known = TRAIN_FAULTS if kind == "train" else SERVE_FAULTS
    faults = [f for f in args.faults.split(",") if f]
    unknown = set(faults) - set(known)
    if unknown:
        raise SystemExit(f"unknown faults {sorted(unknown)}; known: {sorted(known)}")
    readings = train_readings if kind == "train" else serve_readings
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        for what, nums in readings(cell, fam, seed, devs, faults,
                                   sound=not args.no_sound):
            print(json.dumps({"workload": args.workload, "seed": seed, "what": what,
                              "numbers": nums, "t": time.perf_counter() - t0}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
