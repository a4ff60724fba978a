"""Distributed integration: explicit-DP shard_map trainer vs XLA SPMD trainer,
sharded checkpoint resharding, and a reduced-config dry-run compile."""
import pytest

from .helpers import run_devices

EXPLICIT_DP = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.models import build_model
from repro.optim import adamw
from repro.runtime import steps as rsteps

cfg = get_config("smollm-135m").reduced()
shape = ShapeConfig("t", 32, 4, "train")
mesh = jax.make_mesh((4,), ("data",), axis_types=(AxisType.Auto,))
model = build_model(cfg)          # no constraints; replicated params
opt = adamw.OptConfig(peak_lr=1e-3, warmup_steps=1, decay_steps=20)
params = model.init(jax.random.PRNGKey(0))
ostate = adamw.init_opt_state(params)
batch = model.make_batch(shape)

# reference: single-program step (the *CCL/XLA analog)
ref_step = jax.jit(rsteps.build_train_step(model, opt))
rp, ro, rm = ref_step(params, ostate, batch)

# explicit shard_map DP with our ring collectives (the GPU-aware-MPI analog)
step = rsteps.build_explicit_dp_step(model, opt, mesh, "data")
err = rsteps.init_error_state(params)
ep, eo, em, err = step(params, ostate, batch, err)
print("ref loss", float(rm["loss"]), "explicit loss", float(em["loss"]))
assert abs(float(rm["loss"]) - float(em["loss"])) < 1e-3
# parameters after one step must agree (same grads modulo fp error)
d = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
        for a, b in zip(jax.tree.leaves(rp), jax.tree.leaves(ep)))
print("max param delta:", d)
assert d < 5e-2  # bf16 params, ring-sum reassociation

# compressed variant still trains (loss finite, params move)
step_c = rsteps.build_explicit_dp_step(model, opt, mesh, "data", compress_bits=8)
cp, co, cm, err = step_c(params, ostate, batch, rsteps.init_error_state(params))
assert np.isfinite(float(cm["loss"]))
moved = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
            for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(cp)))
assert moved > 0
print("OK")
"""


@pytest.mark.slow
def test_explicit_dp_matches_xla_spmd():
    assert "OK" in run_devices(EXPLICIT_DP, 4, timeout=560)


RESHARD = r"""
import jax, jax.numpy as jnp, numpy as np, tempfile
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
from repro.checkpoint import CheckpointManager

# save on a (4,) mesh, restore on a (2,2) mesh — the elastic-restart path
mesh_a = jax.make_mesh((4,), ("data",), axis_types=(AxisType.Auto,))
tree = {"w": jax.device_put(jnp.arange(64.0).reshape(8, 8),
                            NamedSharding(mesh_a, P("data", None)))}
d = tempfile.mkdtemp()
cm = CheckpointManager(d)
cm.save(3, tree)
mesh_b = jax.make_mesh((2, 2), ("data", "model"), axis_types=(AxisType.Auto,)*2)
target_sh = {"w": NamedSharding(mesh_b, P("data", "model"))}
got, _ = cm.restore({"w": jax.ShapeDtypeStruct((8, 8), jnp.float32)},
                    shardings=target_sh)
np.testing.assert_array_equal(np.asarray(got["w"]), np.arange(64.0).reshape(8, 8))
assert got["w"].sharding.spec == P("data", "model")
print("OK")
"""


@pytest.mark.slow
def test_checkpoint_reshard_across_meshes():
    assert "OK" in run_devices(RESHARD, 4)


DRYRUN_SMOKE = r"""
import jax
from repro.launch.dryrun import run_cell, summarize
from pathlib import Path
import tempfile
out = Path(tempfile.mkdtemp())
# reduced configs through the full production-mesh lower+compile path
for arch, shape in [("smollm-135m-reduced", "train_4k"),
                    ("mamba2-2.7b-reduced", "decode_32k"),
                    ("deepseek-moe-16b-reduced", "train_4k")]:
    cell = run_cell(arch, shape, multi_pod=True, out_dir=out)
    print(summarize(cell))
    assert cell["status"] == "ok", cell.get("error")
    assert cell["roofline"]["step_time_bound_s"] > 0
print("OK")
"""


@pytest.mark.slow
def test_dryrun_compiles_reduced_configs_multipod():
    assert "OK" in run_devices(DRYRUN_SMOKE, 512, timeout=560)


LAUNCH_MICROBATCHED = r"""
import tempfile
from repro.launch import train

for extra in (["--overlap"], ["--zero", "--overlap"]):
    rc = train.main(["--arch", "smollm-135m", "--reduced", "--shape",
                     "train_4k", "--mesh", "2x1", "--microbatches", "2",
                     "--steps", "2", "--ckpt-dir", tempfile.mkdtemp()]
                    + extra)
    assert rc == 0, extra
print("LAUNCH_OK")
"""


def test_launch_explicit_dp_programs_with_microbatches():
    """`launch.train --overlap/--zero --microbatches N` reaches the trainer
    as a StepProgram with a MicrobatchLoop (the path chip_smoke.py --chips 4
    runs at full width)."""
    out = run_devices(LAUNCH_MICROBATCHED, 2, timeout=420)
    assert "LAUNCH_OK" in out
    assert out.count("program: overlap_mb2") == 1
    assert out.count("program: zero_mb2") == 1
    assert out.count("done: step 2") == 2
