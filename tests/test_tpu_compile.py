"""Compile-only checks for a described TPU v5e: the main path's kernels (the
fused flash attention, forward and backward) and the gradient codec at
smollm-135m's published widths.

Nothing runs: each test lowers and compiles for a chip that is described, not
attached, so a kernel the TPU compiler would refuse (block shapes off the
(8, 128) tiling, more VMEM than a kernel may use) fails here with no chip.
The kernels are built with ``interpret=False``, which is what a TPU process
resolves to.  The topology is described inside a fixture, so only the worker
that runs this file loads the TPU compiler.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro import telemetry
from repro.analysis import hlo_trace
from repro.configs import get_config
from repro.core.commplan import DEFAULT_BUCKET_BYTES
from repro.kernels import bucket_codec as bc
from repro.kernels import flash_attention as fa
from repro.models import build_model
from repro.models.layers import attention
from repro.models.sharding import Sharder

#: the ZeRO shard geometry of the four-chip explicit-DP step
N_DP = 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def smollm_leaves():
    """smollm-135m's parameter leaves (full width, shapes only) and the codec
    table at the plan's default bucket size."""
    model = build_model(get_config("smollm-135m"))
    leaves = jax.tree.leaves(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    table = bc.make_table([x.size for x in leaves], DEFAULT_BUCKET_BYTES // 4)
    return leaves, table


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _hlo(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("wire", ["fp32", "int8"])
def test_codec_pack_compiles_full_width(one_chip, smollm_leaves, wire):
    """pack lowers to XLA copies (no Pallas kernel, see bucket_codec)."""
    leaves, table = smollm_leaves
    assert max(x.size for x in leaves) > 28_000_000  # the tied embedding
    flat = [_spec(x.shape, jnp.float32, one_chip) for x in leaves]
    err = _spec((table.n_buckets, table.bucket_elems), jnp.float32, one_chip)
    text = _hlo(lambda f, e: bc.pack(table, f, scale=0.25, wire=wire, err=e),
                flat, err)
    assert "tpu_custom_call" not in text


@pytest.mark.parametrize("wire", ["fp32", "int8"])
def test_codec_unpack_compiles_full_width(one_chip, smollm_leaves, wire):
    leaves, table = smollm_leaves
    like = [_spec(x.shape, jnp.float32, one_chip) for x in leaves]
    carrier = _spec((table.n_buckets, table.bucket_elems),
                    bc.WIRE_DTYPES[wire], one_chip)
    scales = (_spec((table.n_buckets,), jnp.float32, one_chip)
              if wire == "int8" else None)
    text = _hlo(lambda c, s: bc.unpack(table, c, like, scales=s),
                carrier, scales)
    assert "tpu_custom_call" not in text


@pytest.mark.parametrize("n_dp", [1, N_DP])
@pytest.mark.parametrize("p_dtype", [jnp.float32, jnp.bfloat16])
def test_adamw_shard_kernel_compiles_full_width(one_chip, smollm_leaves,
                                                n_dp, p_dtype):
    """The ZeRO shard update kernel over smollm's carrier shard: every bucket
    row, the shard's columns (a whole row on one chip, a quarter on four)."""
    _, table = smollm_leaves
    shape = (table.n_buckets, table.bucket_elems // n_dp)
    g, p, m, v = (_spec(shape, jnp.float32, one_chip) for _ in range(4))
    scalars = _spec((4,), jnp.float32, one_chip)
    text = _hlo(lambda *a: bc._adamw_shard_pallas(
        *a, b1=0.9, b2=0.95, eps=1e-8, wd=0.1, p_dtype=p_dtype,
        interpret=False), g, p, m, v, scalars)
    assert "tpu_custom_call" in text


@pytest.fixture
def compiled_kernels(monkeypatch):
    """The flash kernel built as a TPU process builds it (never interpreted)."""
    monkeypatch.setattr(fa, "interpret_mode", lambda requested=None: False)


def _kernel_scopes(text):
    """The named scope of each Pallas kernel in a compiled module's text."""
    names = hlo_trace.op_names(text)
    calls = [ln.split("=")[0].replace("ROOT", "").strip().lstrip("%")
             for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    return {c: telemetry.scope_of(names.get(c, "")) for c in calls}


def _smollm_qkv(sharding, batch=4):
    """q, k, v at smollm-135m's train_4k widths: S=4096, 9 / 3 heads of 64."""
    cfg = get_config("smollm-135m")
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (9, 3, 64)
    return tuple(_spec((batch, 4096, n, cfg.head_dim), jnp.bfloat16, sharding)
                 for n in (cfg.n_heads, cfg.n_kv_heads, cfg.n_kv_heads))


def _attention_loss(q, k, v, shd=None):
    with telemetry.scope("attention"):
        o = attention(q, k, v, impl="pallas", shd=shd)
    return jnp.sum(o.astype(jnp.float32))


@pytest.mark.parametrize("pass_", ["forward", "backward"])
def test_flash_attention_compiles_smollm_train_4k(one_chip, compiled_kernels,
                                                  pass_):
    """The fused kernel at the training cell's shapes, forward alone and
    forward with backward; every kernel lies under the `attention` scope, so
    the scope's device time counts it."""
    args = _smollm_qkv(one_chip)
    fn = (_attention_loss if pass_ == "forward"
          else jax.grad(_attention_loss, argnums=(0, 1, 2)))
    kernels = _kernel_scopes(_hlo(fn, *args))
    assert len(kernels) == (1 if pass_ == "forward" else 2), kernels
    assert set(kernels.values()) == {"attention"}, kernels


def test_flash_attention_per_shard_compiles_four_chips(topo, compiled_kernels):
    """Per shard on four described chips, batch over the data axis: forward
    and backward with no collective."""
    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))
    shd = Sharder(mesh)
    args = _smollm_qkv(NamedSharding(mesh, P("data")), batch=8)
    text = _hlo(jax.grad(lambda q, k, v: _attention_loss(q, k, v, shd),
                         argnums=(0, 1, 2)), *args)
    assert set(_kernel_scopes(text).values()) == {"attention"}
    for collective in ("all-reduce", "all-gather", "all-to-all",
                       "collective-permute"):
        assert collective not in text, collective


def _zamba2_cut():
    import dataclasses
    return dataclasses.replace(get_config("zamba2-7b"), n_layers=24,
                               hybrid_layer_ids=(6, 11, 17, 23))


def test_zamba2_prefill_compiles_on_one_chip(one_chip, compiled_kernels, monkeypatch):
    """zamba2-7b's benchmark cut (24 layers, shared blocks before layers 6,
    11, 17, 23) prefilling 4 prompts of 3584 into a 3848-position cache on
    one described v5e: the default attention takes the fused kernel at head
    dim 224 (one call per application, each under the `attention` scope),
    and the program's arguments, outputs and temporaries fit the chip's 16 GB."""
    from repro.configs.base import ShapeConfig
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = build_model(_zamba2_cut())
    on_chip = lambda t: jax.tree.map(
        lambda a: _spec(a.shape, a.dtype, one_chip), t)
    params = on_chip(model.abstract_params())
    cache = on_chip(model.abstract_cache(ShapeConfig("serve", 3848, 4, "decode"), 4))
    tokens = _spec((4, 3584), jnp.int32, one_chip)
    telemetry.ATTENTION_PATHS.clear()
    compiled = jax.jit(model.prefill).lower(params, {"tokens": tokens}, cache).compile()
    assert dict(telemetry.ATTENTION_PATHS) == {"fused": 4}
    kernels = _kernel_scopes(compiled.as_text())
    assert len(kernels) == 4 and set(kernels.values()) == {"attention"}, kernels
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    assert total < 16e9, total


#: the served programs at full width: config, batch, cache length, prompt
#: length (the zamba2 and mamba2 cells' shapes; smollm-135m, served by no
#: cell, stands for the dense family)
SERVED = {"zamba2-7b": (_zamba2_cut, 4, 3848, 3584),
          "mamba2-2.7b": (lambda: get_config("mamba2-2.7b"), 16, 776, 512),
          "smollm-135m": (lambda: get_config("smollm-135m"), 16, 2048, 1792)}


def _served_program(arch, kind, one_chip):
    """``BatchedServer``'s own program ``kind`` (``serve.prefill`` or
    ``serve.decode``) for ``arch``, compiled for one described v5e at full
    width.  Returns (compiled, the cache's keys and values, its recurrent
    state, the keys' and values' bytes on the chip)."""
    from repro.configs.base import ShapeConfig
    from repro.runtime.serve import BatchedServer, split_cache

    make, B, S, P = SERVED[arch]
    on_chip = lambda t: jax.tree.map(
        lambda a: _spec(a.shape, a.dtype, one_chip), t)
    model = build_model(make())
    srv = BatchedServer(model.cfg, max_seq=S, batch_size=B,
                        params=on_chip(model.abstract_params()))
    kv, state = split_cache(on_chip(
        model.abstract_cache(ShapeConfig("serve", S, B, "decode"), B)))
    if kind == "serve.prefill":
        args = (srv.params, {"tokens": _spec((B, P), jnp.int32, one_chip)}, kv, state)
    else:
        args = (srv.params, kv, state, _spec((B,), jnp.int32, one_chip),
                _spec((), jnp.int32, one_chip))
    nbytes = jax.jit(lambda c: c).lower(kv).compile() \
        .memory_analysis().argument_size_in_bytes
    return srv._lower[kind](*args).compile(), kv, state, nbytes


COPY_RE = re.compile(r"%([\w.-]+) = (\w+\[([\d,]*)\]\{[^}]*\}) copy\(%([\w.-]+)\)")


def _whole_copies(text, leaves):
    """The copies in a compiled module of an array shaped as one of
    ``leaves`` or as one layer's slice of one, that keep its layout: a second
    buffer of the same array (a copy that changes the layout is a relayout)."""
    shapes = {a.shape for a in leaves} | {a.shape[1:] for a in leaves}
    defs = dict(re.findall(r"%([\w.-]+) = (\w+\[[\d,]*\]\{[^}]*\})", text))
    return [f"{name} = {res} copy({arg})"
            for name, res, dims, arg in COPY_RE.findall(text)
            if tuple(int(d) for d in dims.split(",") if d) in shapes
            and defs.get(arg) == res]


@pytest.mark.parametrize("arch, kind, temp_limit, state_copies", [
    ("zamba2-7b", "serve.decode", 1.2e9, 1),    # its weight prefetches count
    ("mamba2-2.7b", "serve.decode", 64e6, 0),
    ("smollm-135m", "serve.decode", 64e6, 0),
    ("zamba2-7b", "serve.prefill", None, 0),
])
def test_served_cache_is_updated_in_place(one_chip, compiled_kernels, monkeypatch,
                                          arch, kind, temp_limit, state_copies):
    """The server's programs take the keys and values donated and every
    family's layer loop carries them, so the compiled program aliases all of
    them to its output and holds no second copy of any key or value array.
    The recurrent state is not donated: the ssm family reads it as a scan's
    input and writes a fresh one, with no copy; the hybrid's decode, which
    carries it, copies it once."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled, kv, state, nbytes = _served_program(arch, kind, one_chip)
    text = compiled.as_text()
    assert _whole_copies(text, jax.tree.leaves(kv)) == []
    assert len(_whole_copies(text, jax.tree.leaves(state))) == state_copies
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == nbytes, (mem.alias_size_in_bytes, nbytes)
    if temp_limit is not None:
        assert mem.temp_size_in_bytes < temp_limit, mem.temp_size_in_bytes
