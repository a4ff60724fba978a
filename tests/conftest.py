"""Test-suite bootstrap.

Three jobs:
  * run the suite on the CPU backend unless the caller chose a platform: the
    multi-device tests force host devices, and a test process must never
    claim an accelerator (it is set here, before anything imports JAX, and
    `tests.helpers.run_devices` hands it to child processes);
  * make `repro` importable without external PYTHONPATH plumbing (the tier-1
    command sets PYTHONPATH=src, but IDEs / CI matrices may not);
  * provide a deterministic stand-in for `hypothesis` when it isn't installed
    (this container has no network access, and the property tests only use
    `given` / `settings` / `strategies.{integers,floats,sampled_from}`).
    The stub sweeps boundary values first, then a seeded random sample — not a
    shrinker, but it keeps the property tests meaningful and reproducible.
"""
from __future__ import annotations

import os
import random
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

SRC = str(Path(__file__).resolve().parent.parent / "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

try:
    import hypothesis  # noqa: F401
except ImportError:
    import types

    class _Strategy:
        def __init__(self, examples_fn):
            self._examples_fn = examples_fn

        def examples(self, rng, k):
            return self._examples_fn(rng, k)

    def integers(min_value, max_value):
        def gen(rng, k):
            bounds = [min_value, max_value]
            rest = [rng.randint(min_value, max_value) for _ in range(max(k - 2, 0))]
            return (bounds + rest)[:k]
        return _Strategy(gen)

    def floats(min_value, max_value):
        def gen(rng, k):
            bounds = [float(min_value), float(max_value)]
            rest = [rng.uniform(min_value, max_value) for _ in range(max(k - 2, 0))]
            return (bounds + rest)[:k]
        return _Strategy(gen)

    def sampled_from(seq):
        seq = list(seq)

        def gen(rng, k):
            out = list(seq)[:k]
            while len(out) < k:
                out.append(rng.choice(seq))
            return out
        return _Strategy(gen)

    def settings(max_examples=10, **_kw):
        def deco(fn):
            fn._stub_max_examples = max_examples
            return fn
        return deco

    def given(*arg_strats, **kw_strats):
        def deco(fn):
            def wrapper(*args, **kwargs):
                k = getattr(fn, "_stub_max_examples", 10)
                rng = random.Random(0)
                cols = [s.examples(rng, k) for s in arg_strats]
                kw_cols = {name: s.examples(rng, k) for name, s in kw_strats.items()}
                for i in range(k):
                    vals = [c[i] for c in cols]
                    kws = {name: c[i] for name, c in kw_cols.items()}
                    fn(*args, *vals, **kwargs, **kws)
            wrapper.__name__ = fn.__name__
            wrapper.__doc__ = fn.__doc__
            return wrapper
        return deco

    stub = types.ModuleType("hypothesis")
    stub.given = given
    stub.settings = settings
    strategies = types.ModuleType("hypothesis.strategies")
    strategies.integers = integers
    strategies.floats = floats
    strategies.sampled_from = sampled_from
    stub.strategies = strategies
    sys.modules["hypothesis"] = stub
    sys.modules["hypothesis.strategies"] = strategies
