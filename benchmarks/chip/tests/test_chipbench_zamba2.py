"""The zamba2-serve-b4 cell: a whole tiny serving run through the harness
(correct when sound, not correct with a planted fault, the float8 control
not correct), its readers against a made-up record worked out by hand, and
the family's operation and byte counts against hand arithmetic."""
import contextlib
import json
import types

import numpy as np
import pytest

import chipbench_tiny
import control
import zamba2_tiny
from chipbench import precision, program, serve, spec

fam = spec.family("zamba2")
CELL = "zamba2-serve-b4"
READERS = ["decode_ms.attention", "decode_ms.mlp", "decode_ms.adapter",
           "prefill_ms.attention", "serve_mfu.kv"]


@pytest.fixture()
def bench(tmp_path, monkeypatch):
    b, traffic = zamba2_tiny.setup(tmp_path)
    monkeypatch.setattr(spec, "TRAFFIC", traffic)
    return b


def test_sound_run_is_correct_and_traced(bench):
    out = chipbench_tiny.run(bench, CELL, trace=1)
    assert out["correct"], out["checks"]
    assert out["attempted"] % 2 == 0 and out["failed"] == 0
    assert "serve_mfu.kv" in out["metrics"]


@pytest.mark.parametrize("fault", ["altered_token", "decode_state_unchanged"])
def test_planted_fault_is_not_correct(bench, fault, monkeypatch):
    build = serve.build
    planted = contextlib.ExitStack()

    def faulty_build(*a, **k):
        server = build(*a, **k)
        planted.enter_context(control.SERVE_FAULTS[fault](server))
        return server

    monkeypatch.setattr(serve, "build", faulty_build)
    monkeypatch.setattr(serve, "warm", lambda *a: None)
    with planted:
        out = chipbench_tiny.run(bench, CELL)
    assert not out["correct"], out["checks"]


def test_control_in_float8_is_not_correct(bench):
    import jax

    cell = spec.resolve(bench, CELL)
    t, seed = cell.traffic, 2**31 + 3
    V = fam.sizes(cell.config)["V"]
    step = jax.jit(lambda k, s: fam.logits(k, cell.config, s, np.array([s.shape[1] - 1])))
    served = []
    for b in range(2):   # the reference's own greedy tokens stand in for a server
        seqs = serve.prompts(seed, b, t, V)
        for _ in range(t["new_tokens"]):
            lg = step(serve.weights_key(seed), seqs)
            seqs = np.concatenate([seqs, np.asarray(lg.argmax(-1))], axis=1)
        served.append(seqs[:, t["prompt"]:])
    sample = np.arange(2 * t["batch"])
    ref = serve.reference_gaps(fam, cell.config, seed, t, served, sample)
    ctrl = serve.reference_gaps(fam, cell.config, seed, t, served, sample,
                                rnd=precision.fp8)
    lg = np.asarray(ref["logits"])
    gap = (lg.max(-1) - np.take_along_axis(lg, ctrl["top"][..., None], -1)[..., 0]).max()
    assert np.max(ref["gaps"]) <= t["limits"]["served_gap"]
    assert gap > t["limits"]["served_gap"], gap


# ------------------------------------------------------------------ readers
HLO = {"serve.decode": """HloModule jit_decode, is_scheduled=true

ENTRY %main (p: f32[4]) -> f32[4] {
  %fusion.1 = f32[4]{0} fusion(%p), kind=kLoop, calls=%f1, metadata={op_name="jit(decode)/attention/dot_general"}
  %fusion.2 = f32[4]{0} fusion(%fusion.1), kind=kLoop, calls=%f2, metadata={op_name="jit(decode)/mlp/adapter/dot_general"}
  %fusion.3 = f32[4]{0} fusion(%fusion.2), kind=kLoop, calls=%f3, metadata={op_name="jit(decode)/mlp/mul"}
  %fusion.4 = f32[4]{0} fusion(%fusion.3), kind=kLoop, calls=%f4, metadata={op_name="jit(decode)/adapter/dot_general"}
  ROOT %fusion.5 = f32[4]{0} fusion(%fusion.4), kind=kLoop, calls=%f5, metadata={op_name="jit(decode)/while/body/closed_call/mixer/dot_general"}
}
""", "serve.prefill": """HloModule jit_prefill, is_scheduled=true

ENTRY %main (p: f32[4]) -> f32[4] {
  %fusion.1 = f32[4]{0} fusion(%p), kind=kLoop, calls=%f1, metadata={op_name="jit(prefill)/attention/dot_general"}
  %custom-call.2 = f32[4]{0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(prefill)/attention/pallas_call"}
  ROOT %fusion.3 = f32[4]{0} fusion(%custom-call.2), kind=kLoop, calls=%f3, metadata={op_name="jit(prefill)/while/body/mixer/dot_general"}
}
"""}


def record():
    """One prefill run 0-300, decode runs 400-500 and 600-700."""
    ops = [[0, 100, "fusion.1|fusion"], [100, 100, "custom-call.2|custom-call"],
           [200, 100, "fusion.3|fusion"],
           [400, 20, "fusion.1|fusion"], [420, 10, "fusion.2|fusion"],
           [430, 20, "fusion.3|fusion"], [450, 20, "fusion.4|fusion"],
           [470, 30, "fusion.5|fusion"],
           [600, 30, "fusion.1|fusion"], [630, 10, "fusion.2|fusion"],
           [640, 10, "fusion.3|fusion"], [650, 10, "fusion.4|fusion"],
           [660, 40, "fusion.5|fusion"]]
    mods = [[0, 300, "jit_prefill(3)"], [400, 100, "jit_decode(4)"],
            [600, 100, "jit_decode(4)"]]
    return {"window": [0, 1000], "host": [[0, 1000, "bench.window"]],
            "devices": {"0": {"ops": ops, "async": [], "modules": mods}}}


def _traced(monkeypatch, scopes=None):
    from repro import telemetry as tm

    fake = types.SimpleNamespace(NOTED={k: (lambda v=v: v) for k, v in HLO.items()},
                                 SCOPES=scopes or tm.SCOPES, scope_of=tm.scope_of,
                                 COMPILES=types.SimpleNamespace(compiles=0))
    monkeypatch.setattr(program, "telemetry", lambda: fake)
    return {"kind": "serve", "trace": record(), "calls": {"prefill": 1, "decode": 2}}


@pytest.mark.parametrize("name, ns", [
    # per decode run: attention 20 and 30, the adapter (innermost, also inside
    # mlp) 10 + 20 and 10 + 10, mlp 20 and 10
    ("decode_ms.attention", 25), ("decode_ms.mlp", 15), ("decode_ms.adapter", 25),
    # the one prefill run: its two ops under attention, the kernel's included
    ("prefill_ms.attention", 200)])
def test_readers_on_the_record(name, ns, monkeypatch):
    ctx = _traced(monkeypatch)
    assert spec.metric_reader(name)(ctx) == pytest.approx(ns * 1e-6)


def test_adapter_reader_is_silent_where_the_program_has_no_adapter(monkeypatch):
    ctx = _traced(monkeypatch, scopes=("attention", "mlp", "head", "mixer",
                                       "grad_accum", "optimizer", "exchange"))
    assert spec.metric_reader("decode_ms.adapter")(ctx) is None
    assert spec.metric_reader("decode_ms.attention")(ctx) == pytest.approx(25e-6)


def test_serve_mfu_kv_counts_each_decode_at_its_cache_length():
    """A prefill bound at 0.5 s, a decode at cache_len microseconds: two
    batches of 3 decodes after a 10-token prompt attend over 11, 12, 13
    positions each, so the window of 2 s holds 1.000072 s of roofline."""
    pk = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    f = types.SimpleNamespace(
        prefill_cost=lambda c, b, p: (0.5 * pk["bf16_flops_per_s"], 0.0),
        decode_cost=lambda c, b, cache_len: (0.0, cache_len * 1e-6 * pk["hbm_bytes_per_s"]))
    ctx = {"kind": "serve", "trace": record(), "family": f, "config": {}, "peaks": pk,
           "traffic": {"batch": 2, "prompt": 10, "new_tokens": 3},
           "calls": {"prefill": 2, "decode": 6}, "window_s": 2.0}
    read = spec.metric_reader("serve_mfu.kv")
    assert read(ctx) == pytest.approx(100 * 1.000072 / 2.0)
    # a family whose decode cost takes no cache length reads nothing
    ctx["family"] = spec.family("mamba2")
    assert read(ctx) is None


@pytest.mark.parametrize("name", READERS)
def test_new_readers_read_nothing_without_a_trace(name):
    ctx = {"kind": "serve", "trace": None, "calls": {"prefill": 1, "decode": 3},
           "family": fam, "traffic": zamba2_tiny.TRAFFIC, "config": zamba2_tiny.config(),
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "window_s": 1.0}
    assert spec.metric_reader(name)(ctx) is None


# ------------------------------------------------------------------- counts
def _published():
    b = json.loads((chipbench_tiny.REPO / "BENCHMARK.json").read_text())
    return spec.resolve(b, CELL).config


def test_weight_bytes_are_the_stored_weights():
    """With each shared block applied once (2 applications, 2 blocks), the
    bytes a forward pass reads are the weights as stored."""
    import jax

    c = zamba2_tiny.config()
    w = fam.make_weights(jax.random.PRNGKey(0), c)
    assert fam.weight_bytes(c) == sum(a.nbytes for a in jax.tree.leaves(w))


def test_decode_and_prefill_counts_at_the_published_widths():
    """One decode step of 4 rows over 3712 positions (the mean over a batch
    of 3584-token prompts and 256 new tokens) reads 8.87 GB: 24 Mamba2 layers
    3.77 GB, the shared blocks with adapter and linear at each of 4
    applications 2.81 GB, keys and values 1.70 GB, the SSM state and conv
    tail read and written 0.36 GB, the tied head 0.23 GB; a prefill of 4 x
    3584 tokens is 98.4 TFLOP."""
    c = _published()
    s = fam.sizes(c)
    layer = 2 * (3584 * 14704 + 7168 * 3584 + 5 * 7424 + 112 + 7168 + 3584) + 8 * 112
    assert fam._mamba_layer_bytes(s) == layer
    block = 2 * (7168 * 3 * 7168 + 7168 * 3584 + 3 * 3584 * 14336 + 7168 + 3584)
    app = 2 * (128 * (3584 + 2 * 14336) + 3584 * 3584)
    kv = 4 * 2 * 4 * 3712 * 32 * 224 * 2
    state = 4 * 24 * (112 * 64 * 64 * 4 + 3 * 7424 * 2)
    head = 2 * (32000 * 3584 + 3584)
    flops, nbytes = fam.decode_cost(c, 4, 3712)
    assert nbytes == 24 * layer + 4 * (block + app) + kv + 2 * state + head
    assert 8.86e9 < nbytes < 8.87e9
    block_mm = 7168 * 3 * 7168 + 7168 * 3584 + 3 * 3584 * 14336
    app_mm = 128 * (3584 + 2 * 14336) + 3584 * 3584
    per_row = (24 * (2 * (3584 * 14704 + 7168 * 3584) + 2 * 4 * 7424 + 4 * 112 * 64 * 64)
               + 4 * (2 * (block_mm + app_mm) + 4 * 32 * 224 * 3712) + 2 * 32000 * 3584)
    assert flops == 4 * per_row
    pf, _ = fam.prefill_cost(c, 4, 3584)
    assert 98.4e12 < pf < 98.5e12
