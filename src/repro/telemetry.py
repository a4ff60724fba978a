"""The program's own telemetry: host spans, named scopes, a compile counter.

* ``span(name)`` is a host span (``jax.profiler.TraceAnnotation``) on the
  profiler's clock; ``step_span(name, step)`` the same as a step marker.  Both
  cost one check when no profile is being recorded.  While one is, the span's
  start and end (``time.perf_counter_ns``) are also kept in ``SPANS``, for a
  reader that no longer holds the profile.
* ``scope(name)`` is a ``jax.named_scope``: it applies only while a program
  is traced and sets the ``op_name`` metadata of the ops it encloses, in the
  forward and the backward pass.  An op belongs to its innermost scope.
* ``COMPILES`` counts the process's backend compiles and executables loaded
  from the persistent cache, with their seconds.
* ``note_program(name, lower, *args)``, called once per program built,
  keeps how to fetch the compiled text of the program a span dispatches (the
  arguments' shapes and shardings, never the arrays), so the profile's op
  names can be read back to their scopes.  ``use_compile_cache`` puts
  ``op_name`` metadata into the persistent cache's key, so that text is the
  one of the executable that ran, also where it came from that cache.

Every name the program emits is in the table below; readers import them
from here and never retype them.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax

#: the step marker of ``Trainer.run`` (one per step, ``step_num`` = step)
TRAIN_STEP = "train"
#: spans inside each training step: batch build and copy, step dispatch,
#: wait for the loss, metric readback / straggler / guard / logging
TRAIN_SPANS = ("train.batch", "train.step", "train.sync", "train.host")
#: spans of ``BatchedServer.generate``: cache init and prefill dispatch once;
#: per token the readback, the sampling program, and the decode dispatch
SERVE_SPANS = ("serve.prefill", "serve.readback", "serve.sample", "serve.decode")
#: named scopes: the ops of each model part, the step's accumulation, the
#: optimizer, and the explicit-DP gradient exchange (pack, codec, collectives,
#: unpack)
SCOPES = ("attention", "mlp", "head", "mixer", "grad_accum", "optimizer",
          "exchange")
#: programs noted by ``note_program``
PROGRAMS = ("train.step", "serve.prefill", "serve.decode")

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"

recording = jax.profiler.TraceAnnotation.is_enabled

#: (name, start_ns, end_ns) of every span closed while a profile was recorded
SPANS: collections.deque = collections.deque(maxlen=1 << 20)
#: program name -> () -> compiled HLO text (``note_program``)
NOTED: Dict[str, Callable[[], str]] = {}


@contextlib.contextmanager
def _timed(annotation: Callable[[], jax.profiler.TraceAnnotation], name: str):
    if not recording():
        yield
        return
    t0 = time.perf_counter_ns()
    try:
        with annotation():
            yield
    finally:
        SPANS.append((name, t0, time.perf_counter_ns()))


def span(name: str):
    return _timed(lambda: jax.profiler.TraceAnnotation(name), name)


def step_span(name: str, step: int):
    return _timed(lambda: jax.profiler.StepTraceAnnotation(name, step_num=step),
                  name)


def scope(name: str):
    if name not in SCOPES:
        raise ValueError(f"unknown scope {name!r}; known: {SCOPES}")
    return jax.named_scope(name)


def scope_of(op_name: str) -> Optional[str]:
    """The innermost of ``SCOPES`` on an ``op_name`` path, None if none is:
    ``jit(step)/while/body/transpose(jvp(attention))/dot_general`` ->
    ``attention``."""
    found = None
    for part in op_name.split("/"):
        while part.endswith(")") and "(" in part:   # jvp(...), transpose(...)
            part = part[part.index("(") + 1:-1]
        if part in SCOPES:
            found = part
    return found


def _spec(x):
    """An array's shape, dtype and, where it is committed, its sharding: what
    its lowering depends on, so the spec lowers to the same module."""
    if not isinstance(x, jax.Array):
        return x
    return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                sharding=x.sharding if x.committed else None)


def note_program(name: str, lower: Callable, *args) -> None:
    """Keep ``lower(*specs).compile().as_text()`` for ``name`` (``lower`` is
    a jitted function's ``.lower``): after the program has run, that compile
    is the in-memory cache's, the executable that ran."""
    specs = jax.tree.map(_spec, args)
    NOTED[name] = lambda: lower(*specs).compile().as_text()


@dataclasses.dataclass
class CompileCounter:
    """Backend compiles and persistent-cache loads of this process; ``log``
    holds ``(kind, function, seconds, recording)`` per event."""
    compiles: int = 0
    compile_s: float = 0.0
    cache_loads: int = 0
    load_s: float = 0.0
    log: List[Tuple[str, str, float, bool]] = dataclasses.field(default_factory=list)
    _loaded: bool = False

    @property
    def total(self) -> int:
        return self.compiles + self.cache_loads

    def __call__(self, event: str, seconds: float, **kw) -> None:
        if event == CACHE_LOAD:
            # recorded inside the backend-compile event of the same program
            self._loaded = True
            return
        if event != BACKEND_COMPILE:
            return
        if self._loaded:
            self._loaded = False
            kind, self.cache_loads, self.load_s = \
                "load", self.cache_loads + 1, self.load_s + seconds
        else:
            kind, self.compiles, self.compile_s = \
                "compile", self.compiles + 1, self.compile_s + seconds
        self.log.append((kind, str(kw.get("fun_name", "")), seconds, recording()))


COMPILES = CompileCounter()
jax.monitoring.register_event_duration_secs_listener(COMPILES)
