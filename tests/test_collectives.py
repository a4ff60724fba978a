"""Explicit collective algorithms vs jnp oracles (8 forced host devices)."""
import pytest

from .helpers import run_devices

VALIDATE = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.core import collectives as C
from jax.sharding import PartitionSpec as P, AxisType

mesh = jax.make_mesh((8,), ("x",), axis_types=(AxisType.Auto,))
rng = np.random.RandomState(0)
x = rng.randn(8, 37).astype(np.float32)
want = np.broadcast_to(x.sum(0), (8, 37))
for name, fn in C.ALL_REDUCE_ALGOS.items():
    out = jax.jit(jax.shard_map(lambda v, fn=fn: fn(v, 'x'), mesh=mesh,
                                in_specs=P('x'), out_specs=P('x')))(x)
    assert np.allclose(np.asarray(out), want, atol=1e-4), name
    print("ok", name)

xg = rng.randn(8, 8, 3).astype(np.float32)
def oracle(xg, n, k):
    return np.stack([np.concatenate([xg[s][r*k:(r+1)*k] for s in range(n)]) for r in range(n)])
for name, fn in C.ALL_TO_ALL_ALGOS.items():
    out = np.asarray(jax.jit(jax.shard_map(lambda v, fn=fn: fn(v, 'x'), mesh=mesh,
                     in_specs=P('x'), out_specs=P('x')))(xg.reshape(64, 3))).reshape(8, 8, 3)
    assert np.allclose(out, oracle(xg, 8, 1)), name
    print("ok a2a", name)

mesh2 = jax.make_mesh((2, 4), ("pod", "ici"), axis_types=(AxisType.Auto,)*2)
xh = rng.randn(8, 21).astype(np.float32)
out = jax.jit(jax.shard_map(lambda v: C.hierarchical_all_reduce(v, 'ici', 'pod'),
      mesh=mesh2, in_specs=P(('pod','ici')), out_specs=P(('pod','ici'))))(xh)
assert np.allclose(np.asarray(out), np.broadcast_to(xh.sum(0), (8, 21)), atol=1e-4)
print("ok hierarchical")

# dtype sweep for ring (the trainer's DP path)
for dt in (np.float32, np.float16, np.int32):
    xi = (rng.randn(8, 16) * 10).astype(dt)
    out = jax.jit(jax.shard_map(lambda v: C.ring_all_reduce(v, 'x'), mesh=mesh,
                                in_specs=P('x'), out_specs=P('x')))(xi)
    ref = np.broadcast_to(xi.sum(0), (8, 16)).astype(dt)
    tol = 1e-2 if dt == np.float16 else 1e-4
    assert np.allclose(np.asarray(out).astype(np.float64), ref.astype(np.float64),
                       atol=tol, rtol=tol), dt
    print("ok ring dtype", dt)

# odd sizes exercise padding paths
for size in (1, 7, 63, 129):
    xo = rng.randn(8, size).astype(np.float32)
    out = jax.jit(jax.shard_map(lambda v: C.bidir_ring_all_reduce(v, 'x'), mesh=mesh,
                                in_specs=P('x'), out_specs=P('x')))(xo)
    assert np.allclose(np.asarray(out), np.broadcast_to(xo.sum(0), (8, size)), atol=1e-4), size
    print("ok bidir size", size)
print("ALL_OK")
"""


@pytest.mark.slow
def test_collective_algorithms_8dev():
    out = run_devices(VALIDATE, 8)
    assert "ALL_OK" in out


NONPOW2 = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.core import collectives as C
from jax.sharding import PartitionSpec as P, AxisType
mesh = jax.make_mesh((6,), ("x",), axis_types=(AxisType.Auto,))
rng = np.random.RandomState(1)
x = rng.randn(6, 11).astype(np.float32)
for name in ("ring", "bidir_ring", "one_shot", "xla"):
    fn = C.ALL_REDUCE_ALGOS[name]
    out = jax.jit(jax.shard_map(lambda v, fn=fn: fn(v, 'x'), mesh=mesh,
                                in_specs=P('x'), out_specs=P('x')))(x)
    assert np.allclose(np.asarray(out), np.broadcast_to(x.sum(0), (6, 11)), atol=1e-4), name
print("ALL_OK")
"""


@pytest.mark.slow
def test_ring_family_non_power_of_two():
    assert "ALL_OK" in run_devices(NONPOW2, 6)


CHUNKED = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.core import collectives as C
from repro.core.overlap import chunked_hierarchical_all_reduce
from jax.sharding import PartitionSpec as P, AxisType

mesh = jax.make_mesh((2, 4), ("pod", "ici"), axis_types=(AxisType.Auto,)*2)
rng = np.random.RandomState(3)

# integer-valued fp32: sums are exact regardless of association, so the
# chunked pipeline must match the psum oracle bit-for-bit
for size in (1, 7, 64, 129, 1000):
    x = rng.randint(-64, 64, (8, size)).astype(np.float32)
    want = np.asarray(jax.jit(jax.shard_map(
        lambda v: jax.lax.psum(v, ("pod", "ici")), mesh=mesh,
        in_specs=P(("pod", "ici")), out_specs=P(("pod", "ici"))))(x))
    for n_chunks in (1, 2, 3, 5):
        out = np.asarray(jax.jit(jax.shard_map(
            lambda v, c=n_chunks: chunked_hierarchical_all_reduce(
                v, "ici", "pod", n_chunks=c),
            mesh=mesh, in_specs=P(("pod", "ici")),
            out_specs=P(("pod", "ici"))))(x))
        assert np.array_equal(out, want), (size, n_chunks)
    print("ok chunked size", size)

# and the registry carries it as a multi-axis all-reduce
spec = C.get_collective("all_reduce", "hierarchical_chunked")
assert spec.multi_axis
print("ALL_OK")
"""


@pytest.mark.slow
def test_chunked_hierarchical_pipeline_matches_psum_oracle():
    assert "ALL_OK" in run_devices(CHUNKED, 8)
