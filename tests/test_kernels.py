"""Per-kernel allclose vs ref.py oracles: shape/dtype sweeps + hypothesis."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax.experimental.pallas.ops.tpu import splash_attention as splash

from repro.kernels import flash_attention as fa
from repro.kernels import ops, ref
from repro.models.layers import blockwise_attention

RNG = np.random.RandomState(0)


def _attn_ref_4d(q, k, v, causal=True, scale=None):
    """ref.attention_ref over (B, S, H, hd), k and v repeated to H heads."""
    b, s, h, hd = q.shape
    g = h // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    fold = lambda t: t.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    out = ref.attention_ref(fold(q), fold(k), fold(v), causal=causal, scale=scale)
    return out.reshape(b, h, s, hd).transpose(0, 2, 1, 3)


def _scan_4d(q, k, v):
    g = q.shape[2] // k.shape[2]
    return blockwise_attention(q, jnp.repeat(k, g, axis=2),
                               jnp.repeat(v, g, axis=2), q_block=128)


def _qkv(rng, b, s, h, kh, hd, dtype):
    return (jnp.array(rng.randn(b, s, h, hd), dtype),
            jnp.array(rng.randn(b, s, kh, hd), dtype),
            jnp.array(rng.randn(b, s, kh, hd), dtype))


def _blocks(fwd, bwd, fused=True):
    dq = {} if fused else {"block_q_dq": bwd, "block_kv_dq": bwd}
    return splash.BlockSizes(block_q=fwd, block_kv=fwd, block_kv_compute=fwd,
                             block_q_dkv=bwd, block_kv_dkv=bwd,
                             block_kv_dkv_compute=bwd,
                             use_fused_bwd_kernel=fused, **dq)


# (batch, seq, query heads, kv heads, head dim): GQA groups 1 and 3, hd 64
# and 128, S 256 and 512, batch 1 and 2
SHAPES = [(2, 256, 4, 4, 64), (1, 256, 9, 3, 64), (2, 512, 6, 2, 128),
          (1, 512, 2, 2, 128)]


@pytest.mark.parametrize("b,s,h,kh,hd", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, s, h, kh, hd, dtype):
    q, k, v = _qkv(RNG, b, s, h, kh, hd, dtype)
    out = fa.flash_attention(q, k, v)
    want = _attn_ref_4d(q, k, v)
    tol = 5e-6 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return np.linalg.norm(a - b) / np.linalg.norm(b)


def _grads(fn, q, k, v, ct):
    return jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * ct),
                    argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("b,s,h,kh,hd", SHAPES + [(2, 512, 9, 3, 64),
                                                  (1, 256, 6, 2, 128)])
def test_flash_attention_grads(b, s, h, kh, hd):
    """dq, dk, dv of the kernel against the float32 oracle and the scan, bf16
    operands: each within a relative norm of 1e-2 of the oracle's (bf16's
    rounding is 2**-9), and no further from it than twice the scan is."""
    rng = np.random.RandomState(b * 1000 + s + h + hd)
    q, k, v = _qkv(rng, b, s, h, kh, hd, jnp.bfloat16)
    ct = jnp.array(rng.randn(b, s, h, hd), jnp.float32)
    f32 = lambda t: t.astype(jnp.float32)
    want = _grads(lambda q, k, v: _attn_ref_4d(f32(q), f32(k), f32(v)), q, k, v, ct)
    got = _grads(fa.flash_attention, q, k, v, ct)
    scan = _grads(_scan_4d, q, k, v, ct)
    for name, g, w, sc in zip("qkv", got, want, scan):
        assert g.shape == w.shape and g.dtype == jnp.bfloat16, name
        assert _rel(g, w) < 1e-2, (name, _rel(g, w))
        assert _rel(g, w) < 2 * _rel(sc, w) + 1e-3, (name, _rel(g, w), _rel(sc, w))


@pytest.mark.parametrize("blocks", [_blocks(128, 128), _blocks(256, 128, fused=False),
                                    _blocks(128, 256, fused=False)])
def test_flash_attention_block_shapes(blocks):
    """Other tiles and the separate dq kernel: the same values and gradients
    (float32, so the tiling's order of sums is all that differs)."""
    b, s, h, kh, hd = 1, 256, 2, 1, 64
    q, k, v = _qkv(RNG, b, s, h, kh, hd, jnp.float32)
    out = fa.flash_attention(q, k, v, blocks=blocks)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_attn_ref_4d(q, k, v)),
                               atol=1e-5, rtol=1e-5)
    ct = jnp.array(RNG.randn(b, s, h, hd), jnp.float32)
    got = _grads(partial(fa.flash_attention, blocks=blocks), q, k, v, ct)
    want = _grads(_attn_ref_4d, q, k, v, ct)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-4, rtol=1e-4)


def test_flash_attention_zamba2_head_dim():
    """Head dim 224 (zamba2-7b's), scores scaled by (224 / 2) ** -0.5: values
    and gradients against the float32 oracle, in float32 at two blocks of 256
    (the order of sums is all that differs); at S = 3584 the kernel takes
    blocks of 512."""
    assert fa.block_sizes(3584, 224).block_q == 512
    assert fa.block_sizes(3584, 224).block_kv_dkv == 512
    b, s, h, kh, hd = 1, 512, 2, 2, 224
    scale = (hd / 2) ** -0.5
    q, k, v = _qkv(RNG, b, s, h, kh, hd, jnp.float32)
    fused = partial(fa.flash_attention, blocks=_blocks(256, 256), scale=scale)
    oracle = partial(_attn_ref_4d, scale=scale)
    np.testing.assert_allclose(np.asarray(fused(q, k, v)), np.asarray(oracle(q, k, v)),
                               atol=1e-5, rtol=1e-5)
    ct = jnp.array(RNG.randn(b, s, h, hd), jnp.float32)
    for g, w in zip(_grads(fused, q, k, v, ct), _grads(oracle, q, k, v, ct)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-4, rtol=1e-4)


def test_flash_attention_non_causal():
    b, s, h, kh, hd = 1, 128, 2, 1, 64
    q, k, v = _qkv(RNG, b, s, h, kh, hd, jnp.float32)
    out = fa.flash_attention(q, k, v, causal=False)
    want = _attn_ref_4d(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5, rtol=1e-5)


@given(st.integers(1, 2), st.sampled_from([128, 256]), st.sampled_from([1, 3]),
       st.sampled_from([64, 128]))
@settings(max_examples=8, deadline=None)
def test_flash_attention_property(b, s, g, hd):
    rng = np.random.RandomState(b * 1000 + s + g + hd)
    q, k, v = _qkv(rng, b, s, 2 * g, 2, hd, jnp.float32)
    out = fa.flash_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_attn_ref_4d(q, k, v)),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_softmax_invariance():
    """Property: softmax rows sum to one, so with every value row equal the
    output is that row, whatever the scores."""
    b, s, h, kh, hd = 1, 128, 3, 1, 64
    q, k, _ = _qkv(RNG, b, s, h, kh, hd, jnp.float32)
    v = jnp.ones((b, s, kh, hd), jnp.float32) * 3.5
    out = fa.flash_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), 3.5, atol=1e-5)


def test_flash_attention_refuses_shapes_it_cannot_tile():
    assert fa.block_sizes(4096, 64).block_q == 1024
    assert fa.block_sizes(2560, 64).block_q_dkv == 512
    assert fa.block_sizes(384, 128).block_q == 128
    assert fa.block_sizes(200, 64) is None and fa.block_sizes(256, 32) is None
    q, k, v = _qkv(RNG, 1, 64, 2, 1, 64, jnp.float32)
    with pytest.raises(ValueError, match="cannot take"):
        fa.flash_attention(q, k, v)


@pytest.mark.parametrize("r,d", [(8, 128), (64, 576), (128, 2048), (5, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(r, d, dtype):
    x = jnp.array(RNG.randn(r, d), dtype)
    sc = jnp.array(RNG.randn(d), dtype)
    out = ops.rmsnorm(x, sc)
    want = ref.rmsnorm_ref(x, sc)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(want, np.float32),
                               atol=1e-5 if dtype == jnp.float32 else 2e-2)


def test_rmsnorm_3d():
    x = jnp.array(RNG.randn(2, 7, 96), jnp.float32)
    sc = jnp.array(RNG.randn(96), jnp.float32)
    np.testing.assert_allclose(np.asarray(ops.rmsnorm(x, sc)),
                               np.asarray(ref.rmsnorm_ref(x, sc)), atol=1e-5)


def _ssd_oracle(x, dt, A, B, C, chunk):
    b, s = x.shape[0], x.shape[1]
    ys = []
    for bi in range(b):
        h0 = jnp.zeros((x.shape[2], x.shape[3], B.shape[-1]), jnp.float32)
        outs = []
        for c in range(s // chunk):
            sl = slice(c * chunk, (c + 1) * chunk)
            yc, h0 = ref.ssd_chunk_ref(x[bi, sl], dt[bi, sl], A, B[bi, sl], C[bi, sl], h0)
            outs.append(yc)
        ys.append(jnp.concatenate(outs, 0))
    return jnp.stack(ys)


@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (2, 64, 4, 8, 16, 16), (1, 128, 2, 16, 8, 32), (1, 32, 8, 4, 4, 8),
])
def test_ssd_scan_sweep(b, s, h, p, n, chunk):
    rng = np.random.RandomState(7)
    x = jnp.array(rng.randn(b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jnp.array(rng.randn(b, s, h), jnp.float32))
    A = -jnp.exp(jnp.array(rng.randn(h), jnp.float32))
    B = jnp.array(rng.randn(b, s, n), jnp.float32)
    C = jnp.array(rng.randn(b, s, n), jnp.float32)
    out = ops.ssd_scan(x, dt, A, B, C, chunk=chunk)
    want = _ssd_oracle(x, dt, A, B, C, chunk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-4, rtol=2e-3)


def test_ssd_kernel_matches_model_chunked():
    """Kernel vs models/mamba2.ssd_chunked (two independent implementations)."""
    from repro.models.mamba2 import ssd_chunked
    rng = np.random.RandomState(3)
    b, s, h, p, n, chunk = 2, 64, 4, 8, 16, 16
    x = jnp.array(rng.randn(b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jnp.array(rng.randn(b, s, h), jnp.float32))
    A = -jnp.exp(jnp.array(rng.randn(h), jnp.float32))
    B = jnp.array(rng.randn(b, s, 1, n), jnp.float32)
    C = jnp.array(rng.randn(b, s, 1, n), jnp.float32)
    out_kernel = ops.ssd_scan(x, dt, A, B, C, chunk=chunk)
    out_model, _ = ssd_chunked(x, dt, A, B, C, chunk)
    np.testing.assert_allclose(np.asarray(out_kernel), np.asarray(out_model),
                               atol=2e-4, rtol=2e-3)
