"""The program's telemetry read against a trace record: device time by named
scope, the clock offsets from causal pairs, idle time by the innermost
program span, and the new readers' silence without a trace or without the
program's telemetry.  Every number is worked out by hand from a made-up
record: one device, a window of 1000 ns, two training steps."""
import types

import pytest

import chipbench_tiny  # noqa: F401
from chipbench import program, spec

SCOPES = ("attention", "mlp", "head", "mixer", "grad_accum", "optimizer",
          "exchange")
HLO = """HloModule jit_step, is_scheduled=true

ENTRY %main (p: f32[4]) -> f32[4] {
  %fusion.1 = f32[4]{0} fusion(%p), kind=kLoop, calls=%f1, metadata={op_name="jit(step)/while/body/transpose(jvp(attention))/dot_general"}
  %fusion.2 = f32[4]{0} fusion(%fusion.1), kind=kLoop, calls=%f2, metadata={op_name="jit(step)/mlp/mul"}
  %fusion.3 = f32[4]{0} fusion(%fusion.2), kind=kLoop, calls=%f3, metadata={op_name="jit(step)/while/body/dynamic_update_slice"}
  %all-reduce.4 = f32[4]{0} all-reduce(%fusion.3), to_apply=%add, metadata={op_name="jit(step)/exchange/psum"}
  %all-gather-start.6 = f32[4]{0} all-gather-start(%fusion.3), metadata={op_name="jit(step)/exchange/all_gather"}
  ROOT %fusion.5 = f32[4]{0} fusion(%all-reduce.4), kind=kLoop, calls=%f5, metadata={op_name="jit(step)/optimizer/sub"}
}
"""


def scope_of(name):
    from repro import telemetry

    return telemetry.scope_of(name)


def record():
    """Step runs 100-400 and 600-900; a copy outside any run at 950-960."""
    ops = [[100, 100, "fusion.1|fusion"], [200, 50, "fusion.2|fusion"],
           [250, 50, "fusion.3|fusion"], [300, 50, "all-reduce.4|all-reduce"],
           [350, 50, "fusion.5|fusion"], [100, 300, "while.9|while"],
           [600, 100, "fusion.1|fusion"], [700, 100, "fusion.2|fusion"],
           [800, 100, "fusion.5|fusion"], [950, 10, "copy.1|copy"]]
    asyn = [[320, 60, "all-gather-start.6|all-gather-start"]]
    mods = [[100, 300, "jit_step(77)"], [600, 300, "jit_step(77)"]]
    host = [[0, 1000, "bench.window"], [45, 10, "bench.batch"],
            [62, 26, "bench.step_call"], [505, 30, "bench.batch"],
            [541, 28, "bench.step_call"]]
    return {"window": [0, 1000], "host": host,
            "devices": {"0": {"ops": ops, "async": asyn, "modules": mods}}}


def spans():
    """The program's spans on its own clock: the profiler's less 1000 ns."""
    on_profiler = [("train", 40, 500), ("train.batch", 40, 60),
                   ("train.step", 60, 90), ("train.sync", 90, 430),
                   ("train.host", 430, 500), ("train", 500, 1000),
                   ("train.batch", 500, 540), ("train.step", 540, 570),
                   ("train.sync", 570, 930), ("train.host", 930, 1000)]
    return [(n, s - 1000, e - 1000) for n, s, e in on_profiler]


def scopes():
    return {k: scope_of(v) for k, v in program.op_paths([HLO]).items()}


def test_op_to_scope_map():
    assert scopes() == {("jit_step", "fusion.1"): "attention",
                        ("jit_step", "fusion.2"): "mlp", ("jit_step", "fusion.3"): None,
                        ("jit_step", "all-reduce.4"): "exchange",
                        ("jit_step", "all-gather-start.6"): "exchange",
                        ("jit_step", "fusion.5"): "optimizer"}


@pytest.mark.parametrize("module, want", [
    # busy 100-400, 600-900, 950-960; exchange = the all-reduce 300-350 with
    # the async all-gather 320-380; exposed = 300-350, where nothing else runs
    (None, {"busy": 610, "attention": 200, "mlp": 150, "head": 0, "mixer": 0,
            "grad_accum": 0, "optimizer": 150, "exchange": 80, "other": 60,
            "exposed.exchange": 50}),
    ("jit_step", {"busy": 600, "attention": 200, "mlp": 150, "optimizer": 150,
                  "exchange": 80, "other": 50, "exposed.exchange": 50, "runs": 2}),
])
def test_scope_seconds(module, want):
    sec = program.scope_seconds(record(), scopes(), SCOPES, module)
    for k, v in want.items():
        assert sec[k] == pytest.approx(v if k == "runs" else v * 1e-9), k
    # the five scopes and other cover busy time but for the exchange's ops
    five = sum(sec[k] for k in ("attention", "mlp", "head", "grad_accum", "optimizer"))
    assert five + sec["other"] + 50e-9 == pytest.approx(sec["busy"])


@pytest.mark.parametrize("name, ns_per_step", [
    ("train_ms.attention", 100), ("train_ms.mlp", 75), ("train_ms.head", 0),
    ("train_ms.grad_accum", 0), ("train_ms.optimizer", 75), ("train_ms.other", 30),
    ("train_ms.exchange", 40), ("exposed_ms.exchange", 25)])
def test_train_readers_on_the_record(name, ns_per_step, monkeypatch):
    from repro import telemetry as tm

    fake = types.SimpleNamespace(NOTED={"train.step": lambda: HLO}, SCOPES=SCOPES,
                                 scope_of=tm.scope_of,
                                 COMPILES=types.SimpleNamespace(compiles=0))
    monkeypatch.setattr(program, "telemetry", lambda: fake)
    ctx = {"kind": "train", "trace": record(), "steps": 2}
    assert spec.metric_reader(name)(ctx) == pytest.approx(ns_per_step * 1e-6)
    assert program.programs(ctx)["rebuilt"] == 0


def test_offsets_from_causal_pairs():
    # each bench span lies inside the program span that encloses it
    assert program.host_offset(spans(), record()["host"]) == (999, 1001)
    on_host = [(n, s + 1000, e + 1000) for n, s, e in spans()]
    # runs start 40 and 60 ns after their dispatch spans start, and end 30 ns
    # before the sync spans that wait for them end
    b = program.device_offset(on_host, record()["devices"]["0"],
                              {"train.step": "jit_step"})
    assert b == (-30, 40)


def test_idle_gaps_by_innermost_program_span():
    out = program.idle_gaps_program(record(), spans(), {"train.step": "jit_step"})
    # spans land on the device shifted by 1000 (host) + 5 (device); the idle
    # 0-100, 400-600, 900-950, 960-1000 then fall under:
    want = {"none": 45, "train.batch": 60, "train.step": 60, "train.sync": 100,
            "train.host": 125}
    assert {k: pytest.approx(v * 1e-9) for k, v in want.items()} == \
        dict(out["idle_gaps_program"])
    assert out["clock_offset_us"] == {"0": pytest.approx(5e-3)}
    assert out["clock_bracket_us"] == {"0": pytest.approx(70e-3)}


def test_self_times_leave_out_nested_spans():
    t = program.self_times([("train", 0, 100), ("train.step", 10, 30),
                            ("train.sync", 30, 90)])
    assert t == {"train": [20], "train.step": [20], "train.sync": [60]}


NEW = ["train_ms.attention", "train_ms.mlp", "train_ms.head", "train_ms.grad_accum",
       "train_ms.optimizer", "train_ms.other", "train_ms.exchange",
       "exposed_ms.exchange", "decode_ms.mixer", "decode_ms.head", "decode_ms.other",
       "host_ms.decode_dispatch", "host_ms.sample", "compiles_in_window.train",
       "compiles_in_window.serve"]


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("kind", ["train", "serve"])
def test_new_readers_read_nothing_without_a_trace(name, kind):
    read = spec.metric_reader(name)
    assert read({"kind": kind, "trace": None, "steps": 3,
                 "calls": {"prefill": 1, "decode": 3}}) is None


@pytest.mark.parametrize("name", NEW)
def test_new_readers_read_nothing_without_the_programs_telemetry(name, monkeypatch):
    monkeypatch.setattr(program, "telemetry", lambda: None)
    kind = "serve" if name.startswith(("decode", "host", "compiles_in_window.serve")) \
        else "train"
    assert spec.metric_reader(name)({"kind": kind, "trace": record(), "steps": 2}) is None
