"""Architecture smoke tests (all 10, reduced configs) + semantic equivalences."""
import collections
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SHAPES, get_config, list_configs
from repro.configs.base import ShapeConfig, shape_applicable
from repro.models import build_model
from repro.models import transformer as T

from .helpers import run_devices

ALL_ARCHS = list_configs()
TRAIN_SHAPE = ShapeConfig("t", 64, 4, "train")


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_arch_smoke_train_step(arch):
    """Reduced config: one forward/loss on CPU — shapes + no NaNs."""
    cfg = get_config(arch).reduced()
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    batch = m.make_batch(TRAIN_SHAPE)
    loss = jax.jit(m.loss)(params, batch)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), f"{arch} loss not finite"
    assert 1.0 < float(loss) < 20.0  # ~ln(vocab) at init


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_arch_smoke_decode_step(arch):
    cfg = get_config(arch).reduced()
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    dshape = ShapeConfig("d", 32, 2, "decode")
    cache = m.init_cache(dshape, batch_size=2)
    tok = m.make_batch(dshape)["tokens"][:2]
    logits, cache2 = jax.jit(m.decode)(params, cache, tok, jnp.array(3))
    assert logits.shape[0] == 2 and logits.shape[-1] == cfg.vocab
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
    assert jax.tree.structure(cache) == jax.tree.structure(cache2)


@pytest.mark.parametrize("arch", ["smollm-135m", "qwen1.5-4b", "musicgen-medium",
                                  "mamba2-2.7b", "zamba2-7b"])
def test_decode_matches_forward(arch):
    """prefill + incremental decode == full forward (the caching invariant)."""
    cfg = dataclasses.replace(get_config(arch).reduced(), remat="none")
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    S, P0 = 16, 8
    batch = m.make_batch(ShapeConfig("t", S, 2, "train"))
    x, positions = m._embed(params, batch)
    if cfg.family == "ssm":
        xh = m._ssm_forward(params, x)
    elif cfg.family == "hybrid":
        from repro.models import hybrid as H
        xh = H.hybrid_forward(params, x, cfg, m.shd, positions)
    else:
        xh, _ = T.forward(params, x, cfg, m.shd, positions)
    full = np.asarray(T.unembed(params, xh, cfg, m.shd).astype(jnp.float32))

    cache = m.init_cache(ShapeConfig("d", S, 2, "decode"), batch_size=2)
    toks = batch["tokens"]
    lg, cache = jax.jit(m.prefill)(params, {"tokens": toks[:, :P0]}, cache)
    errs = [np.abs(np.asarray(lg.astype(jnp.float32))[:, 0] - full[:, P0 - 1]).max()]
    dec = jax.jit(m.decode)
    for p in range(P0, S - 1):
        tok = toks[:, p] if toks.ndim == 2 else toks[:, p, :]
        lg, cache = dec(params, cache, tok, jnp.array(p, jnp.int32))
        errs.append(np.abs(np.asarray(lg.astype(jnp.float32))[:, 0] - full[:, p]).max())
    tol = 1e-4 if cfg.family in ("dense", "audio") else 0.08  # bf16 recurrences
    assert max(errs) < tol, f"{arch}: {max(errs)}"


def test_moe_decode_matches_forward_without_drops():
    cfg = dataclasses.replace(get_config("deepseek-moe-16b").reduced(),
                              remat="none", capacity_factor=16.0)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    S = 16
    batch = m.make_batch(ShapeConfig("t", S, 2, "train"))
    x, positions = m._embed(params, batch)
    xh, _ = T.forward(params, x, cfg, m.shd, positions)
    full = np.asarray(T.unembed(params, xh, cfg, m.shd).astype(jnp.float32))
    cache = m.init_cache(ShapeConfig("d", S, 2, "decode"), batch_size=2)
    lg, cache = jax.jit(m.prefill)(params, {"tokens": batch["tokens"][:, :8]}, cache)
    err = np.abs(np.asarray(lg.astype(jnp.float32))[:, 0] - full[:, 7]).max()
    assert err < 1e-4


def test_moe_capacity_drops_tokens():
    """Low capacity must change outputs (drops) but keep them finite."""
    base = get_config("deepseek-moe-16b").reduced()
    m_lo = build_model(dataclasses.replace(base, capacity_factor=0.5, remat="none"))
    m_hi = build_model(dataclasses.replace(base, capacity_factor=16.0, remat="none"))
    params = m_lo.init(jax.random.PRNGKey(0))
    batch = m_lo.make_batch(TRAIN_SHAPE)
    lo = jax.jit(m_lo.loss)(params, batch)
    hi = jax.jit(m_hi.loss)(params, batch)
    assert bool(jnp.isfinite(lo)) and bool(jnp.isfinite(hi))
    assert abs(float(lo) - float(hi)) > 1e-6


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_param_count_analytic_matches_tree(arch):
    """cfg.param_count() (used for MODEL_FLOPS) vs the actual parameter tree."""
    cfg = get_config(arch)
    m = build_model(cfg)
    tree = m.abstract_params()
    actual = sum(np.prod(l.shape) for l in jax.tree.leaves(tree))
    expected = cfg.param_count()
    assert abs(actual - expected) / expected < 0.05, (actual, expected)


def test_vlm_loss_ignores_image_positions():
    cfg = get_config("internvl2-26b").reduced()
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    b = m.make_batch(TRAIN_SHAPE)
    l1 = jax.jit(m.loss)(params, b)
    assert bool(jnp.isfinite(l1))


def test_long_500k_applicability():
    """The documented skip matrix: ssm/hybrid run long_500k, full-attention don't."""
    long = SHAPES["long_500k"]
    runnable = {a for a in ALL_ARCHS if shape_applicable(get_config(a), long)[0]}
    assert runnable == {"mamba2-2.7b", "zamba2-7b"}


@pytest.mark.parametrize("g", [1, 2])
def test_ssd_chunked_matches_reference(g):
    """Chunked SSD against the recurrence, with one group and with two (head
    h reads group h // (heads / groups))."""
    from repro.models.mamba2 import ssd_chunked, ssd_reference
    rng = np.random.RandomState(0)
    b, s, h, p, n = 2, 32, 4, 8, 16
    x = jnp.array(rng.randn(b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jnp.array(rng.randn(b, s, h), jnp.float32))
    A = -jnp.exp(jnp.array(rng.randn(h), jnp.float32))
    B = jnp.array(rng.randn(b, s, g, n), jnp.float32)
    C = jnp.array(rng.randn(b, s, g, n), jnp.float32)
    y_ref, f_ref = ssd_reference(x, dt, A, B, C)
    for chunk in (8, 16, 32):
        y, f = ssd_chunked(x, dt, A, B, C, chunk)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-4)
        np.testing.assert_allclose(np.asarray(f), np.asarray(f_ref), atol=1e-4)


def test_blockwise_equals_naive_attention():
    from repro.models.layers import blockwise_attention, naive_attention
    rng = np.random.RandomState(0)
    q = jnp.array(rng.randn(2, 128, 4, 32), jnp.float32)
    k = jnp.array(rng.randn(2, 128, 4, 32), jnp.float32)
    v = jnp.array(rng.randn(2, 128, 4, 32), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(blockwise_attention(q, k, v, q_block=32)),
        np.asarray(naive_attention(q, k, v)), atol=1e-5, rtol=1e-5)


def test_gqa_repeat_semantics():
    """GQA with K=H must equal MHA; K<H groups share kv."""
    from repro.models.layers import attention
    rng = np.random.RandomState(0)
    q = jnp.array(rng.randn(1, 32, 4, 16), jnp.float32)
    k4 = jnp.array(rng.randn(1, 32, 4, 16), jnp.float32)
    v4 = jnp.array(rng.randn(1, 32, 4, 16), jnp.float32)
    out = attention(q, k4, v4, impl="naive")
    # grouped: take 2 kv heads, repeat manually
    k2, v2 = k4[:, :, :2], v4[:, :, :2]
    out_g = attention(q, k2, v2, impl="naive")
    manual_k = jnp.repeat(k2, 2, axis=2)
    manual_v = jnp.repeat(v2, 2, axis=2)
    out_m = attention(q, manual_k, manual_v, impl="naive")
    np.testing.assert_allclose(np.asarray(out_g), np.asarray(out_m), atol=1e-6)
    assert not np.allclose(np.asarray(out_g), np.asarray(out))


def _paths(fn, *specs):
    """The attention paths one abstract trace of ``fn`` counts."""
    from repro import telemetry as tm
    before = collections.Counter(tm.ATTENTION_PATHS)
    jax.eval_shape(fn, *specs)
    return dict(tm.ATTENTION_PATHS - before)


def _qkv_specs(b=2, s=256, h=9, kh=3, hd=64):
    return tuple(jax.ShapeDtypeStruct((b, s, n, hd), jnp.bfloat16) for n in (h, kh, kh))


def test_attention_path_on_the_cpu():
    """The default traces the scan off a TPU; "pallas" forces the kernel."""
    from repro.models.layers import attention
    specs = _qkv_specs()
    assert _paths(lambda q, k, v: attention(q, k, v), *specs) == {"scan": 1}
    assert _paths(lambda q, k, v: attention(q, k, v, impl="blockwise"), *specs) == {"scan": 1}
    assert _paths(lambda q, k, v: attention(q, k, v, impl="naive"), *specs) == {"naive": 1}
    assert _paths(lambda q, k, v: attention(q, k, v, impl="pallas"), *specs) == {"fused": 1}
    with pytest.raises(ValueError, match="query offset"):
        _paths(lambda q, k, v: attention(q, k, v, impl="pallas", q_offset=8), *specs)
    from repro import telemetry as tm
    assert set(tm.ATTENTION_PATHS) <= set(tm.ATTENTION_PATH_NAMES)


@pytest.mark.parametrize("kw,shape,want", [
    ({}, {}, "fused"),
    ({}, {"s": 4096}, "fused"),
    ({}, {"s": 200}, "scan"),               # S not a multiple of a block
    ({}, {"hd": 32}, "scan"),               # head dim under a lane, not a multiple of 64
    ({}, {"hd": 224}, "fused"),             # zamba2-7b's head dim
    ({"q_offset": 8}, {}, "scan"),
    ({"causal": False}, {}, "scan"),
])
def test_attention_path_on_a_tpu_backend(monkeypatch, kw, shape, want):
    """The default's choice on a TPU backend, from the shapes alone (traced
    abstractly, so nothing is compiled for the CPU)."""
    from repro.models.layers import attention
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    specs = _qkv_specs(**shape)
    assert _paths(lambda q, k, v: attention(q, k, v, **kw), *specs) == {want: 1}


PER_SHARD = r'''
import collections
import jax, jax.numpy as jnp, numpy as np
from repro import telemetry as tm
from repro.launch.mesh import make_mesh
from repro.models.layers import attention
from repro.models.sharding import Sharder

def paths(shd, b, h, kh):
    before = collections.Counter(tm.ATTENTION_PATHS)
    spec = lambda n: jax.ShapeDtypeStruct((b, 256, n, 64), jnp.bfloat16)
    jax.eval_shape(lambda q, k, v: attention(q, k, v, shd=shd), spec(h), spec(kh), spec(kh))
    return dict(tm.ATTENTION_PATHS - before)

dp = Sharder(make_mesh((4, 1), ("data", "model")))
tp = Sharder(make_mesh((2, 2), ("data", "model")))
backend = jax.default_backend
jax.default_backend = lambda: "tpu"
assert paths(dp, 4, 9, 3) == {"fused": 1}
assert paths(dp, 2, 9, 3) == {"scan": 1}      # batch does not divide the data axis
assert paths(tp, 2, 4, 2) == {"fused": 1}     # heads over tp
assert paths(tp, 2, 9, 3) == {"scan": 1}      # context parallel: 9 heads on tp=2
assert paths(tp, 2, 4, 1) == {"scan": 1}      # one kv head does not divide tp
jax.default_backend = backend

rng = np.random.RandomState(0)
q = jnp.array(rng.randn(4, 256, 4, 64), jnp.float32)
k, v = (jnp.array(rng.randn(4, 256, 2, 64), jnp.float32) for _ in range(2))
ct = jnp.array(rng.randn(4, 256, 4, 64), jnp.float32)
for shd in (dp, tp):
    def run(impl):
        f = lambda q, k, v: jnp.sum(attention(q, k, v, impl=impl, shd=shd) * ct)
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2)))(q, k, v)
    (lf, gf), (ls, gs) = run("pallas"), run("blockwise")
    np.testing.assert_allclose(float(lf), float(ls), rtol=1e-5)
    for a, b in zip(gf, gs):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)
print("ALL_OK")
'''


def test_attention_per_shard_on_a_mesh():
    """On four devices: the fused kernel runs per shard (batch over data,
    heads over tp where H and K divide it) and matches the scan in value and
    gradient; meshes it cannot be split over take the scan."""
    assert "ALL_OK" in run_devices(PER_SHARD, 4)


def test_model_loss_and_grad_fused_match_scan():
    """A reduced smollm (hd 64, GQA 2:1) at S=128: loss and gradient with the
    kernel forced (interpreted) against the scan."""
    base = dataclasses.replace(get_config("smollm-135m").reduced(),
                               n_heads=2, n_kv_heads=1)
    assert base.head_dim == 64
    shape = ShapeConfig("t", 128, 2, "train")
    models = {impl: build_model(dataclasses.replace(base, attn_impl=impl))
              for impl in ("pallas", "blockwise")}
    params = models["blockwise"].init(jax.random.PRNGKey(0))
    batch = models["blockwise"].make_batch(shape)
    (lf, gf), (ls, gs) = (jax.jit(jax.value_and_grad(m.loss))(params, batch)
                          for m in models.values())
    assert abs(float(lf) - float(ls)) / float(ls) < 1e-3
    for a, b in zip(jax.tree.leaves(gf), jax.tree.leaves(gs)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.linalg.norm(a - b) <= 2e-2 * np.linalg.norm(b) + 1e-6
