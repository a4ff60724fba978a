"""Operation and byte counts against hand counts at a small size and at the
published sizes, and the plain references against the program at a small
size in float32 (the references follow the same equations)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chipbench_tiny
from chipbench import spec

llama = spec.family("llama")
mamba2 = spec.family("mamba2")


def test_llama_train_flops_hand_count():
    c = chipbench_tiny.llama()   # D 64, F 128, H 4, K 2, hd 16, L 2, V 256
    per_layer = 64 * 64 + 64 * 32 + 64 * 32 + 64 * 64 + 3 * 64 * 128
    matmul = 2 * per_layer + 256 * 64
    assert llama.matmul_params(c) == matmul == 90112
    assert llama.train_flops_per_token(c, 64) == 6 * matmul + 6 * 2 * 64 * 4 * 16
    w = llama.make_weights(jax.random.PRNGKey(0), c)
    leaves = [a for a in jax.tree.leaves(w["layers"]) if a.ndim == 3]
    assert sum(a.size for a in leaves) + w["emb"].size == matmul


def test_llama_published_count():
    c = spec.resolve(chipbench_tiny.json.loads((chipbench_tiny.REPO / "BENCHMARK.json").read_text()),
                     "smollm-train4k-1chip").config
    assert llama.matmul_params(c) == 134_479_872
    assert llama.train_flops_per_token(c, 4096) == 6 * 134_479_872 + 6 * 30 * 4096 * 576


def test_mamba2_costs_hand_count():
    c = chipbench_tiny.mamba2()  # D 64, Di 128, P 16, H 8, N 16, K 4, Q 8, V 256, L 2
    mm = 64 * (2 * 128 + 2 * 16 + 8) + 128 * 64
    assert mm == 27136
    per_row = 2 * (2 * mm + 2 * 4 * 160 + 4 * 8 * 16 * 16) + 2 * 256 * 64
    state = 4 * 2 * (8 * 16 * 16 * 4 + 3 * 160 * 2)
    w = mamba2.make_weights(jax.random.PRNGKey(0), c)
    wbytes = sum(a.nbytes for a in jax.tree.leaves(w))
    assert mamba2.weight_bytes(c) == wbytes
    assert mamba2.decode_cost(c, 4) == (4 * per_row, wbytes + 2 * state)
    ssd = 8 * 16 + 8 * 8 * 16 + 4 * 8 * 16 * 16
    flops = 2 * 4 * 16 * (2 * mm + 2 * 4 * 160 + ssd) + 2 * 4 * 256 * 64
    assert mamba2.prefill_cost(c, 4, 16) == (flops, wbytes + state)


def test_mamba2_published_decode_bytes():
    b = chipbench_tiny.json.loads((chipbench_tiny.REPO / "BENCHMARK.json").read_text())
    c = spec.resolve(b, "mamba2-serve-b16").config
    flops, nbytes = mamba2.decode_cost(c, 16)
    # 2.7B bf16 weights plus twice 16 rows x 64 layers x 80 heads x 64 x 128 fp32
    assert 5.3e9 < mamba2.weight_bytes(c) < 5.6e9
    assert nbytes == pytest.approx(mamba2.weight_bytes(c) + 2 * mamba2.state_bytes(c, 16))
    assert 2.68e9 < mamba2.state_bytes(c, 16) < 2.76e9


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def test_llama_reference_matches_program_in_float32():
    from repro.models.model import build_model

    c = chipbench_tiny.llama()
    w = _f32(llama.make_weights(jax.random.PRNGKey(3), c))
    toks = np.random.default_rng(0).integers(0, 256, (2, 64), dtype=np.int32)
    model = build_model(llama.program_config(c))
    with jax.default_matmul_precision("highest"):
        prog = model.loss(llama.to_program(w), {"tokens": jnp.asarray(toks)})
    ref = np.mean([llama.loss(w, jnp.asarray(t), c) for t in toks])
    assert float(prog) == pytest.approx(float(ref), rel=1e-5)


def test_mamba2_reference_matches_program_prefill_and_decode_in_float32():
    from repro.configs.base import ShapeConfig
    from repro.models.model import build_model

    c = chipbench_tiny.mamba2()
    w = _f32(mamba2.make_weights(jax.random.PRNGKey(4), c))
    model = build_model(mamba2.program_config(c))
    toks = np.random.default_rng(1).integers(0, 256, (2, 20), dtype=np.int32)
    P = 16
    with jax.default_matmul_precision("highest"):
        cache = jax.tree.map(lambda a: a.astype(jnp.float32),
                             model.init_cache(ShapeConfig("s", 24, 2, "decode"), 2))
        params = mamba2.to_program(w)
        lg, cache = model.prefill(params, {"tokens": jnp.asarray(toks[:, :P])}, cache)
        got = [lg[:, -1]]
        for t in range(P, 20 - 1):
            lg, cache = model.decode(params, cache, jnp.asarray(toks[:, t]), t)
            got.append(lg[:, -1])
    got = np.stack(got, axis=1)
    # the reference rebuilds the same (bf16-stored) weights from the key
    ref = mamba2.logits(jax.random.PRNGKey(4), c, jnp.asarray(toks),
                        np.arange(P - 1, 20 - 1))
    np.testing.assert_allclose(got, np.asarray(ref), rtol=1e-4, atol=1e-5)
