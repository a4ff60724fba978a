"""The program's telemetry: the compile counter, the host spans of
``Trainer.run`` and ``BatchedServer.generate`` in a CPU profile, the named
scopes in compiled HLO, and the serve launcher's timed report."""
from __future__ import annotations

import dataclasses
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import hlo_trace
from repro.configs.base import SHAPES, get_config
from repro import telemetry as tm

from .helpers import run_devices


def test_compile_counter_counts_a_fresh_jit_once():
    f = jax.jit(lambda x: x * 3.0 + 1.0)
    x = jnp.arange(7.0)
    before = tm.COMPILES.total
    f(x).block_until_ready()
    assert tm.COMPILES.total == before + 1
    assert tm.COMPILES.log[-1][0] in ("compile", "load")
    f(x).block_until_ready()
    assert tm.COMPILES.total == before + 1


def test_compile_counter_tells_cache_loads_apart():
    c = tm.CompileCounter()
    c(tm.BACKEND_COMPILE, 2.0, fun_name="jit(a)")
    c(tm.CACHE_LOAD, 0.1)
    c(tm.BACKEND_COMPILE, 0.3, fun_name="jit(b)")
    c("/jax/some/other/event", 5.0)
    assert (c.compiles, c.cache_loads, c.total) == (1, 1, 2)
    assert c.compile_s == 2.0 and c.load_s == 0.3
    assert [e[:2] for e in c.log] == [("compile", "jit(a)"), ("load", "jit(b)")]


@pytest.mark.parametrize("op_name, scope", [
    ("jit(train_step)/while/body/closed_call/transpose(jvp(attention))/dot_general",
     "attention"),
    ("jit(train_step)/checkpoint/mlp/attention/sin", "attention"),
    ("jit(step)/exchange/while/body/grad_accum/add", "grad_accum"),
    ("jit(decode)/while/body/dynamic_update_slice", None),
    ("jvp(head)/reduce_sum", "head"),
])
def test_scope_of_takes_the_innermost_listed_scope(op_name, scope):
    assert tm.scope_of(op_name) == scope


def test_noted_program_is_the_executable_that_ran():
    @jax.jit
    def f(x):
        with tm.scope("mlp"):
            return jnp.tanh(x) * 2.0

    x = jnp.arange(5.0)
    f(x).block_until_ready()
    tm.note_program("probe", f.lower, x)
    try:
        before = tm.COMPILES.total
        text = tm.NOTED["probe"]()
        assert tm.COMPILES.total == before          # fetched, not rebuilt
    finally:
        del tm.NOTED["probe"]
    assert "mlp" in {tm.scope_of(n) for n in hlo_trace.op_names(text).values()}


CACHE_PROBE = r"""
import sys
from pathlib import Path
sys.path.insert(0, str(Path(__file__).parent / "src"))
import jax, jax.numpy as jnp
from repro import telemetry as tm
from repro.launch.compile_cache import use_compile_cache

use_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


@jax.jit
def f(x):
    with tm.scope(SCOPE):
        return jnp.tanh(x) @ x


f(jnp.ones((8, 8))).block_until_ready()
print("KINDS", [kind for kind, fun, *_ in tm.COMPILES.log if fun == "jit(f)"])
"""


def test_compile_cache_holds_scopes_and_survives_a_moved_checkout(tmp_path):
    """The persistent cache's key holds the programs' metadata, but relative
    to the checkout: a second checkout at another path loads what the first
    compiled, and a third whose program differs only by a named scope
    compiles its own."""
    import shutil
    import subprocess
    import sys

    src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                       "src", "repro")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    kinds = []
    for name, scope in (("a", "mlp"), ("b", "mlp"), ("c", "head")):
        root = tmp_path / name
        shutil.copytree(src, root / "src" / "repro",
                        ignore=shutil.ignore_patterns("__pycache__"))
        (root / "probe.py").write_text(f"SCOPE = {scope!r}\n" + CACHE_PROBE)
        r = subprocess.run([sys.executable, str(root / "probe.py")], env=env,
                           capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stderr[-2000:]
        kinds.append(r.stdout.split("KINDS", 1)[1].strip())
    assert kinds == ["['compile']", "['load']", "['compile']"]


def test_unknown_scope_is_refused():
    with pytest.raises(ValueError, match="unknown scope"):
        tm.scope("attn")


def test_hlo_op_names_and_module_name():
    text = jax.jit(lambda x: jnp.sin(x) @ x).lower(jnp.ones((4, 4))).compile().as_text()
    assert hlo_trace.module_name(text) == "jit__lambda"
    names = hlo_trace.op_names(text)
    assert any(v.endswith("/sin") for v in names.values())
    line = ('  ROOT %fusion.3 = bf16[2]{0} fusion(%a), kind=kLoop, calls=%f, '
            'metadata={op_name="jit(s)/batch[\\\'tokens\\\']/x" source_line=3}')
    assert hlo_trace.op_names(line) == {"fusion.3": "jit(s)/batch[\\'tokens\\']/x"}
    # an instruction the compiler added carries no op_name
    assert hlo_trace.op_names("  %copy.7 = f32[4]{0} copy(%p)") == {"copy.7": ""}


# ------------------------------------------------- spans in a CPU profile
def _host_spans(logdir):
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)[0]
    names = set(tm.TRAIN_SPANS + tm.SERVE_SPANS + (tm.TRAIN_STEP,))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events if ev.name in names]
    return sorted(out, key=lambda s: s[1])


def _inside(inner, outers):
    return any(o[1] <= inner[1] and inner[2] <= o[2] for o in outers)


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """A 2-step Trainer.run (smollm, 2 microbatches) and a 3-token generate
    (mamba2), each under its own CPU profile."""
    from repro.runtime.serve import BatchedServer, ServeConfig
    from repro.runtime.train import Trainer, TrainConfig

    tmp = tmp_path_factory.mktemp("telemetry")
    cfg = get_config("smollm-135m").reduced()
    shape = dataclasses.replace(SHAPES["train_4k"], seq_len=32, global_batch=4)
    trainer = Trainer(cfg, shape, train_cfg=TrainConfig(
        steps=2, microbatches=2, ckpt_every=0, ckpt_dir=str(tmp / "ckpt"),
        log_every=100))
    tm.SPANS.clear()
    jax.profiler.start_trace(str(tmp / "train"))
    trainer.run()
    jax.profiler.stop_trace()
    train_log = list(tm.SPANS)

    server = BatchedServer(get_config("mamba2-2.7b").reduced(), max_seq=24,
                           batch_size=2)
    prompts = np.random.RandomState(0).randint(0, server.cfg.vocab, (2, 8))
    tm.SPANS.clear()
    jax.profiler.start_trace(str(tmp / "serve"))
    server.generate(prompts.astype(np.int32), ServeConfig(max_new_tokens=3))
    jax.profiler.stop_trace()
    return {"train": _host_spans(str(tmp / "train")), "train_log": train_log,
            "serve": _host_spans(str(tmp / "serve")), "serve_log": list(tm.SPANS),
            "texts": {k: fn() for k, fn in tm.NOTED.items()},
            "rows": trainer.metrics_log}


def test_train_spans_nest_inside_each_step(profiled):
    spans = profiled["train"]
    steps = [s for s in spans if s[0] == tm.TRAIN_STEP]
    assert len(steps) == 2
    for name in tm.TRAIN_SPANS:
        mine = [s for s in spans if s[0] == name]
        assert len(mine) == 2, name
        assert all(_inside(s, steps) for s in mine), name
    # the program's own log holds the same spans, in the same order
    assert [s[0] for s in profiled["train_log"]] == \
        [s[0] for s in sorted(spans, key=lambda s: s[2])]
    assert [r["compiles"] > 0 for r in profiled["rows"]] == [True, False]


def test_serve_spans_per_token(profiled):
    counts = {n: sum(1 for s in profiled["serve"] if s[0] == n) for n in tm.SERVE_SPANS}
    assert counts == {"serve.prefill": 1, "serve.readback": 3,
                      "serve.sample": 4, "serve.decode": 3}
    assert sorted(s[0] for s in profiled["serve_log"]) == \
        sorted(s[0] for s in profiled["serve"])


@pytest.mark.parametrize("program, scopes", [
    ("train.step", {"attention", "mlp", "head", "grad_accum", "optimizer"}),
    ("serve.decode", {"mixer", "head"}),
])
def test_compiled_ops_carry_their_scopes(profiled, program, scopes):
    text = profiled["texts"][program]
    found = {tm.scope_of(n) for n in hlo_trace.op_names(text).values()}
    assert scopes <= found, (program, found)


EXCHANGE_SCOPE = r"""
import jax
from jax.sharding import AxisType
from repro.analysis import hlo_trace
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.models import build_model
from repro.optim import adamw
from repro import telemetry as tm
from repro.runtime import steps as rsteps

cfg = get_config("smollm-135m").reduced()
mesh = jax.make_mesh((4,), ("data",), axis_types=(AxisType.Auto,))
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
batch = model.make_batch(ShapeConfig("t", 32, 8, "train"))
for kw in (dict(overlap=True, microbatches=2), dict(overlap=True, compress_bits=8),
           dict(bucket_bytes=1 << 20), dict(zero=True, overlap=True, microbatches=2)):
    step = rsteps.build_explicit_dp_step(model, adamw.OptConfig(), mesh, "data", **kw)
    text = step.lower(params, step.init_opt_state(params), batch,
                      step.init_error_state(params)).compile().as_text()
    found = {tm.scope_of(n) for n in hlo_trace.op_names(text).values()}
    want = {"exchange", "optimizer", "attention"}
    if kw.get("microbatches"):
        want.add("grad_accum")
    assert want <= found, (kw, found)
    # every collective of the step is in the exchange scope, but the ZeRO
    # update's clipping psum, which is the optimizer's
    coll = [n for line in text.splitlines() if hlo_trace.OP_RE.search(line)
            for n in hlo_trace.op_names(line).values()]
    assert coll and all(tm.scope_of(n) == "exchange" or
                        (kw.get("zero") and tm.scope_of(n) == "optimizer")
                        for n in coll), (kw, coll)
print("EXCHANGE_OK")
"""


@pytest.mark.slow
def test_explicit_dp_exchange_scope_on_four_devices():
    assert "EXCHANGE_OK" in run_devices(EXCHANGE_SCOPE, 4, timeout=560)


def test_serve_launcher_times_a_warm_batch(capsys):
    from repro.launch import serve

    assert serve.main(["--arch", "smollm-135m", "--reduced", "--batch", "2",
                       "--prompt-len", "8", "--new-tokens", "3"]) == 0
    out = capsys.readouterr().out
    m = re.search(r"([\d.]+) tok/s \(batch (\d+), (\d+) new, ([\d.]+)s, (\d+) compiles\)",
                  out)
    assert m, out
    assert (int(m.group(2)), int(m.group(3))) == (2, 3)
    assert int(m.group(5)) == 0          # the warm-up built every program
