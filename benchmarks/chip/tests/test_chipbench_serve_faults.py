"""A whole tiny serving run through the harness (no chip): correct when
sound, not correct with a token altered where it is produced or a decode
that leaves its state unchanged, and the float8 control not correct."""
import contextlib

import numpy as np
import pytest

import chipbench_tiny
import control
from chipbench import precision, serve, spec


@pytest.fixture()
def bench(tmp_path, monkeypatch):
    b, traffic = chipbench_tiny.setup(tmp_path)
    monkeypatch.setattr(spec, "TRAFFIC", traffic)
    return b


def test_sound_run_is_correct_and_traced(bench):
    out = chipbench_tiny.run(bench, "mamba2-serve-b16", trace=1)
    assert out["correct"], out["checks"]
    assert out["attempted"] % 4 == 0 and out["failed"] == 0
    assert "serve_mfu" in out["metrics"]


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
        out = chipbench_tiny.run(bench, "mamba2-serve-b16")
    assert not out["correct"], out["checks"]


def test_control_in_float8_is_not_correct(bench):
    cell = spec.resolve(bench, "mamba2-serve-b16")
    fam = spec.family(cell.config["reference"])
    t, seed = cell.traffic, 2**31 + 3
    V = fam.sizes(cell.config)["V"]
    served = []
    for b in range(2):   # the reference's own greedy tokens stand in for a server
        p = serve.prompts(seed, b, t, V)
        seqs = p
        for _ in range(t["new_tokens"]):
            import jax
            lg = jax.jit(lambda k, s: fam.logits(k, cell.config, s, np.array([s.shape[1] - 1])))(
                serve.weights_key(seed), seqs)
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
