"""Runtime: optimizer math, train loop, checkpoint/restart, data, compression."""
import dataclasses
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.data import SyntheticLM, DataConfig, PrefetchIterator
from repro.optim import adamw
from repro.runtime.train import Trainer, TrainConfig
from repro.runtime.serve import BatchedServer, ServeConfig

SHAPE = ShapeConfig("t", 64, 4, "train")


def test_adamw_single_step_math():
    """One AdamW step vs hand-computed reference."""
    cfg = adamw.OptConfig(peak_lr=0.1, min_lr=0.1, warmup_steps=0, decay_steps=1,
                          b1=0.9, b2=0.99, eps=1e-8, weight_decay=0.0,
                          clip_norm=1e9)
    p = {"w": jnp.array([1.0, 2.0], jnp.float32)}
    g = {"w": jnp.array([0.5, -0.5], jnp.float32)}
    st = adamw.init_opt_state(p)
    new_p, new_st, _ = adamw.apply_updates(p, g, st, cfg)
    m = 0.1 * np.array([0.5, -0.5])
    v = 0.01 * np.array([0.25, 0.25])
    mhat = m / (1 - 0.9)
    vhat = v / (1 - 0.99)
    want = np.array([1.0, 2.0]) - 0.1 * mhat / (np.sqrt(vhat) + 1e-8)
    np.testing.assert_allclose(np.asarray(new_p["w"]), want, rtol=1e-5)
    assert int(new_st["step"]) == 1


def test_grad_clip_scales_update():
    cfg = adamw.OptConfig(clip_norm=0.1, warmup_steps=0, weight_decay=0.0)
    p = {"w": jnp.ones((4,), jnp.float32)}
    g = {"w": jnp.full((4,), 100.0, jnp.float32)}
    st = adamw.init_opt_state(p)
    _, _, metrics = adamw.apply_updates(p, g, st, cfg)
    assert float(metrics["grad_norm"]) == pytest.approx(200.0, rel=1e-3)


def test_schedule_warmup_and_decay():
    cfg = adamw.OptConfig(peak_lr=1.0, min_lr=0.1, warmup_steps=10, decay_steps=100)
    assert float(adamw.schedule(jnp.array(5), cfg)) == pytest.approx(0.5)
    assert float(adamw.schedule(jnp.array(10), cfg)) == pytest.approx(1.0)
    assert float(adamw.schedule(jnp.array(100), cfg)) == pytest.approx(0.1)


def test_loss_decreases_on_tiny_model(tmp_path):
    cfg = get_config("smollm-135m").reduced()
    # overfit one repeated batch => loss must fall
    class OneBatch(SyntheticLM):
        def batch_at(self, step):
            return super().batch_at(0)
    tr = Trainer(cfg, SHAPE, adamw.OptConfig(peak_lr=3e-3, warmup_steps=2, decay_steps=50),
                 TrainConfig(steps=12, ckpt_every=0, ckpt_dir=str(tmp_path), log_every=100),
                 data=OneBatch(cfg, SHAPE))
    res = tr.run()
    losses = [m["loss"] for m in res["metrics"]]
    assert losses[-1] < losses[0] - 0.2


def test_checkpoint_roundtrip(tmp_path):
    cm = CheckpointManager(str(tmp_path), keep=2)
    tree = {"a": jnp.arange(6, dtype=jnp.bfloat16).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.float32)}}
    cm.save(10, tree, extra={"step": 10})
    got, extra = cm.restore(tree)
    assert extra["step"] == 10
    np.testing.assert_array_equal(np.asarray(got["a"], np.float32),
                                  np.asarray(tree["a"], np.float32))
    assert got["a"].dtype == jnp.bfloat16


def test_checkpoint_retention_and_latest(tmp_path):
    cm = CheckpointManager(str(tmp_path), keep=2)
    tree = {"a": jnp.zeros((2,))}
    for s in (1, 2, 3, 4):
        cm.save(s, tree)
    assert cm.latest_step() == 4
    steps = sorted(int(p.name.split("_")[1]) for p in tmp_path.glob("step_*"))
    assert steps == [3, 4]


def test_checkpoint_async_and_atomicity(tmp_path):
    cm = CheckpointManager(str(tmp_path))
    tree = {"a": jnp.ones((64, 64))}
    cm.save(1, tree, blocking=False)
    cm.wait()
    assert cm.latest_step() == 1
    # a stale tmp dir must be ignored
    (tmp_path / "step_9.tmp").mkdir()
    assert cm.latest_step() == 1


def test_train_restart_replays_determinism(tmp_path):
    """Fault tolerance: run 8 steps straight vs 4 + crash + resume: same loss."""
    cfg = get_config("smollm-135m").reduced()
    opt = adamw.OptConfig(peak_lr=1e-3, warmup_steps=2, decay_steps=50)
    t1 = Trainer(cfg, SHAPE, opt, TrainConfig(steps=8, ckpt_every=100,
                 ckpt_dir=str(tmp_path / "a"), log_every=100, ckpt_async=False))
    r1 = t1.run()
    t2 = Trainer(cfg, SHAPE, opt, TrainConfig(steps=8, ckpt_every=4,
                 ckpt_dir=str(tmp_path / "b"), log_every=100, ckpt_async=False))
    r2 = t2.run(inject_failure_at=6)   # crash at 6 -> restore from 4 -> replay
    l1 = {m["step"]: m["loss"] for m in r1["metrics"]}
    l2 = {m["step"]: m["loss"] for m in r2["metrics"]}
    for s in (6, 7):
        assert l2[s] == pytest.approx(l1[s], rel=1e-5), f"step {s} diverged after restart"


def test_data_determinism_and_host_slicing():
    cfg = get_config("smollm-135m").reduced()
    d1 = SyntheticLM(cfg, SHAPE, DataConfig(seed=7))
    d2 = SyntheticLM(cfg, SHAPE, DataConfig(seed=7))
    np.testing.assert_array_equal(d1.batch_at(5)["tokens"], d2.batch_at(5)["tokens"])
    assert not np.array_equal(d1.batch_at(5)["tokens"], d1.batch_at(6)["tokens"])
    h0 = SyntheticLM(cfg, SHAPE, DataConfig(seed=7, host_index=0, host_count=2))
    h1 = SyntheticLM(cfg, SHAPE, DataConfig(seed=7, host_index=1, host_count=2))
    full = d1.batch_at(3)["tokens"]
    np.testing.assert_array_equal(np.concatenate([h0.batch_at(3)["tokens"],
                                                  h1.batch_at(3)["tokens"]]), full)


def test_prefetch_iterator():
    cfg = get_config("smollm-135m").reduced()
    src = SyntheticLM(cfg, SHAPE)
    it = PrefetchIterator(src, start_step=2)
    s, b = next(it)
    assert s == 2
    np.testing.assert_array_equal(b["tokens"], src.batch_at(2)["tokens"])
    it.close()


SERVED_ARCHS = ["mamba2-2.7b", "zamba2-7b", "smollm-135m"]   # ssm, hybrid, dense


@pytest.mark.parametrize("arch", SERVED_ARCHS)
def test_serve_greedy_deterministic(arch):
    """Two batches in a row on one server: the second reads no cache buffer
    that the first handed to a program."""
    cfg = get_config(arch).reduced()
    srv = BatchedServer(cfg, max_seq=48, batch_size=2)
    prompts = np.random.RandomState(0).randint(0, cfg.vocab, (2, 8)).astype(np.int32)
    a = srv.generate(prompts, ServeConfig(max_new_tokens=4))
    b = srv.generate(prompts, ServeConfig(max_new_tokens=4))
    np.testing.assert_array_equal(a, b)
    assert a.shape == (2, 4)


@pytest.mark.parametrize("arch", SERVED_ARCHS)
def test_serve_stops_at_eos(arch):
    """Rows that all reach the end token stop there; the tokens before it are
    those of a run without one."""
    cfg = get_config(arch).reduced()
    srv = BatchedServer(cfg, max_seq=48, batch_size=2)
    prompt = np.random.RandomState(1).randint(0, cfg.vocab, (1, 8)).astype(np.int32)
    prompts = np.repeat(prompt, 2, axis=0)
    full = srv.generate(prompts, ServeConfig(max_new_tokens=4))
    cut = srv.generate(prompts, ServeConfig(max_new_tokens=4, eos_id=int(full[0, 1])))
    stop = int(np.argmax(full[0] == full[0, 1])) + 1
    np.testing.assert_array_equal(cut, full[:, :stop])


def _undonated_greedy(model, params, prompts, max_seq, n):
    """Greedy tokens of a plain loop of undonated prefill and decode calls."""
    prefill, decode = jax.jit(model.prefill), jax.jit(model.decode)
    B, P = prompts.shape
    cache = model.init_cache(ShapeConfig("serve", max_seq, B, "decode"), B)
    logits, cache = prefill(params, {"tokens": jnp.asarray(prompts)}, cache)
    out = []
    for t in range(n):
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        out.append(np.asarray(tok))
        logits, cache = decode(params, cache, tok, jnp.array(P + t, jnp.int32))
    return np.stack(out, axis=1)


@pytest.mark.parametrize("arch", SERVED_ARCHS)
def test_donated_serve_matches_an_undonated_loop(arch):
    """The server's programs take the cache donated and write it in place;
    its tokens are those of undonated prefill and decode calls."""
    cfg = get_config(arch).reduced()
    srv = BatchedServer(cfg, max_seq=32, batch_size=2)
    prompts = np.random.RandomState(2).randint(0, cfg.vocab, (2, 8)).astype(np.int32)
    np.testing.assert_array_equal(
        srv.generate(prompts, ServeConfig(max_new_tokens=6)),
        _undonated_greedy(srv.model, srv.params, prompts, 32, 6))


def test_microbatch_indivisible_raises_named_error():
    """An indivisible microbatch split must name the batch size and count
    instead of surfacing an opaque reshape error."""
    from repro.runtime import steps as rsteps

    batch = {"tokens": np.zeros((10, 4), np.int32)}
    with pytest.raises(ValueError, match=r"10.*microbatches=3"):
        rsteps._microbatch(batch, 3)
    # divisible split unchanged
    out = rsteps._microbatch(batch, 2)
    assert out["tokens"].shape == (2, 5, 4)


def test_explicit_dp_jit_cache_keyed_on_tree_structure():
    """The jitted shard_map step must not reuse the first call's specs for a
    call with a different pytree structure (stale-spec regression)."""
    import jax
    from jax.sharding import AxisType
    from repro.runtime import steps as rsteps

    class ToyModel:
        @staticmethod
        def loss(params, batch):
            s = sum(jnp.sum(p) for p in jax.tree.leaves(params))
            return (s - 1.0) ** 2 + 0.0 * jnp.mean(batch["x"])

    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    opt = adamw.OptConfig(peak_lr=1e-2, warmup_steps=0, decay_steps=10)
    step = rsteps.build_explicit_dp_step(ToyModel(), opt, mesh, "data")

    p1 = {"w": jnp.ones((4,), jnp.float32)}
    b1 = {"x": jnp.ones((2,), jnp.float32)}
    out1 = step(p1, adamw.init_opt_state(p1), b1, rsteps.init_error_state(p1))
    assert np.isfinite(float(out1[2]["loss"]))
    assert len(step._cache) == 1

    # a different params structure must get fresh shard_map specs
    p2 = {"w": jnp.ones((4,), jnp.float32), "v": jnp.ones((3,), jnp.float32)}
    out2 = step(p2, adamw.init_opt_state(p2), b2 := {"x": jnp.ones((2,), jnp.float32)},
                rsteps.init_error_state(p2))
    assert np.isfinite(float(out2[2]["loss"]))
    assert set(out2[0]) == {"w", "v"}
    assert len(step._cache) == 2

    # repeat calls reuse the cached jit (no per-step retrace)
    step(p1, adamw.init_opt_state(p1), b1, rsteps.init_error_state(p1))
    assert len(step._cache) == 2


def test_gradient_compression_error_feedback():
    """int8 error-feedback quantization: accumulated error stays bounded and the
    running sum of dequantized grads tracks the true sum (convergence guarantee)."""
    rng = np.random.RandomState(0)
    true_sum = np.zeros(256, np.float32)
    deq_sum = np.zeros(256, np.float32)
    err = np.zeros(256, np.float32)
    for _ in range(200):
        g = rng.randn(256).astype(np.float32) * 0.01
        true_sum += g
        gq = g + err
        scale = max(np.abs(gq).max(), 1e-12) / 127.0
        q = np.clip(np.round(gq / scale), -127, 127)
        deq = q * scale
        err = gq - deq
        deq_sum += deq
    assert np.abs(deq_sum - true_sum).max() < 1e-3


def test_checkpoint_shard_spec_metadata_roundtrip(tmp_path):
    """ZeRO carrier-sharded leaves round-trip when save and restore agree on
    the shard spec, and every sharded<->replicated cross-restore fails loudly
    before any leaf is loaded."""
    spec = {"opt/m": "zero-carrier:data", "opt/v": "zero-carrier:data"}
    tree = {"opt": {"m": jnp.arange(8, dtype=jnp.float32).reshape(2, 4),
                    "v": jnp.ones((2, 4), jnp.float32)}}
    cm = CheckpointManager(str(tmp_path / "z"))
    cm.save(3, tree, extra={"step": 3}, specs=spec)
    got, extra = cm.restore(tree, specs=spec)
    assert extra["step"] == 3
    np.testing.assert_array_equal(np.asarray(got["opt"]["m"]),
                                  np.asarray(tree["opt"]["m"]))
    # sharded checkpoint -> replicated restore target
    with pytest.raises(ValueError, match="replicated trainer"):
        cm.restore(tree)
    # replicated checkpoint -> sharded restore target
    cm2 = CheckpointManager(str(tmp_path / "r"))
    cm2.save(3, tree, extra={"step": 3})
    with pytest.raises(ValueError, match="replicated checkpoint"):
        cm2.restore(tree, specs=spec)
    # both sharded, but under different carrier layouts
    other = {k: "zero-carrier:data,pod" for k in spec}
    with pytest.raises(ValueError, match="match exactly"):
        cm.restore(tree, specs=other)


def test_recovery_bounded_retry_exhaustion(tmp_path):
    """A fault that keeps firing exhausts max_retries and surfaces as a named
    persistent failure (the old loop retried forever)."""
    cfg = get_config("smollm-135m").reduced()
    tr = Trainer(cfg, SHAPE, adamw.OptConfig(),
                 TrainConfig(steps=8, ckpt_every=2, ckpt_async=False,
                             ckpt_dir=str(tmp_path), log_every=100,
                             max_retries=3, retry_backoff_s=0.0))
    with pytest.raises(RuntimeError, match="persistent failure"):
        tr.run(inject_failure_at=[4] * 10)
    assert [r["attempt"] for r in tr.retry_log] == [1, 2, 3, 4]


def test_recovery_repeated_transient_fault_completes(tmp_path):
    """Two distinct firings of the same fault step (a re-failure after the
    replay) both recover within the retry budget; the old cleared-before-raise
    bug made a repeated entry unreachable."""
    cfg = get_config("smollm-135m").reduced()
    tr = Trainer(cfg, SHAPE, adamw.OptConfig(),
                 TrainConfig(steps=8, ckpt_every=2, ckpt_async=False,
                             ckpt_dir=str(tmp_path), log_every=100,
                             max_retries=3, retry_backoff_s=0.0))
    res = tr.run(inject_failure_at=[4, 4])
    assert res["final_step"] == 8
    assert res["retries"] == 2


def test_recovery_without_checkpoint_surfaces_fault(tmp_path):
    """restored is None: nothing to restore into, the transient fault must
    propagate instead of looping on an unrecoverable state."""
    cfg = get_config("smollm-135m").reduced()
    tr = Trainer(cfg, SHAPE, adamw.OptConfig(),
                 TrainConfig(steps=8, ckpt_every=0, ckpt_async=False,
                             ckpt_dir=str(tmp_path), log_every=100))
    with pytest.raises(RuntimeError, match="injected device failure"):
        tr.run(inject_failure_at=2)
    assert tr.retry_log == []


def test_recovery_fatal_error_propagates_immediately(tmp_path):
    """A RuntimeError that does not look like a fabric/device fault is a bug:
    no restore, no retry (the old catch-all swallowed it)."""
    cfg = get_config("smollm-135m").reduced()
    tr = Trainer(cfg, SHAPE, adamw.OptConfig(),
                 TrainConfig(steps=8, ckpt_every=2, ckpt_async=False,
                             ckpt_dir=str(tmp_path), log_every=100))
    orig = tr.step_fn

    def buggy(params, opt_state, batch):
        if int(opt_state["step"]) == 4:
            raise RuntimeError("loss scaler misconfigured (a genuine bug)")
        return orig(params, opt_state, batch)

    tr.step_fn = buggy
    with pytest.raises(RuntimeError, match="genuine bug"):
        tr.run()
    assert tr.retry_log == []


@pytest.mark.parametrize("msg", [
    "RESOURCE_EXHAUSTED: Out of memory while trying to allocate 12.50G",
    "INTERNAL: Mosaic failed to compile TPU kernel: Slice shape along "
    "dimension 0 must be aligned to tiling (1024)",
])
def test_recovery_device_oom_and_compile_errors_are_fatal(tmp_path, msg):
    """A device runtime error is restored and replayed, except out of memory
    and a refused compile: a replay would only repeat them, so they stop the
    run at once."""
    from repro.runtime.train import _is_transient

    assert _is_transient(jax.errors.JaxRuntimeError("UNAVAILABLE: link down"))
    cfg = get_config("smollm-135m").reduced()
    tr = Trainer(cfg, SHAPE, adamw.OptConfig(),
                 TrainConfig(steps=6, ckpt_every=2, ckpt_async=False,
                             ckpt_dir=str(tmp_path), log_every=100))
    orig = tr.step_fn

    def failing(params, opt_state, batch):
        if int(opt_state["step"]) == 4:
            raise jax.errors.JaxRuntimeError(msg)
        return orig(params, opt_state, batch)

    tr.step_fn = failing
    with pytest.raises(jax.errors.JaxRuntimeError, match=msg.split(":")[0]):
        tr.run()
    assert tr.retry_log == []


def test_straggler_skip_reverts_step(tmp_path):
    """'skip' drops the straggler step's update: the run records the skips
    and the final state is reachable without them (loss stays finite)."""
    from jax.sharding import AxisType
    from repro.core.faults import FaultEvent, FaultPlan

    cfg = get_config("smollm-135m").reduced()
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    plan = FaultPlan(events=(FaultEvent(step=7, kind="straggler", severity=6.0),
                             FaultEvent(step=9, kind="straggler", severity=6.0)))
    tr = Trainer(cfg, SHAPE, adamw.OptConfig(),
                 TrainConfig(steps=12, ckpt_every=0, ckpt_async=False,
                             ckpt_dir=str(tmp_path), log_every=100,
                             explicit_dp=True, bucket_bytes=1 << 16,
                             straggler_threshold=2.0, straggler_action="skip",
                             faults=plan),
                 mesh=mesh)
    res = tr.run()
    assert res["final_step"] == 12
    # the two injected episodes must be caught, and every detected straggler
    # (injected or wall-clock) skipped — on CPU real timing jitter can add one
    assert res["straggler_events"] >= 2
    assert res["skipped_steps"] == res["straggler_events"]
    skipped = {m["step"] for m in res["metrics"] if m["straggler"]}
    assert {7, 9} <= skipped
    assert all(np.isfinite(m["loss"]) for m in res["metrics"])


def test_straggler_skip_rejected_under_zero(tmp_path):
    cfg = get_config("smollm-135m").reduced()
    with pytest.raises(ValueError, match="unsound with zero"):
        Trainer(cfg, SHAPE, adamw.OptConfig(),
                TrainConfig(steps=1, ckpt_dir=str(tmp_path), zero=True,
                            explicit_dp=True, straggler_action="skip"))


def test_mid_run_plan_swap_bit_parity(tmp_path):
    """_swap_policy on the fp32 wire is numerically transparent: checkpoint at
    6, swap the policy, resume to 12 — bitwise the same losses as an
    uninterrupted 12-step run."""
    from jax.sharding import AxisType
    from repro.core.autotune import CollectivePolicy

    cfg = get_config("smollm-135m").reduced()
    opt = adamw.OptConfig(peak_lr=1e-3, warmup_steps=2, decay_steps=50)

    def make(ckpt_dir, steps):
        mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
        return Trainer(cfg, SHAPE, opt,
                       TrainConfig(steps=steps, ckpt_every=6, ckpt_async=False,
                                   ckpt_dir=str(ckpt_dir), log_every=100,
                                   explicit_dp=True, bucket_bytes=1 << 16),
                       mesh=mesh)

    straight = make(tmp_path / "a", 12).run()
    tr = make(tmp_path / "b", 6)
    tr.run()
    tr._swap_policy(CollectivePolicy.from_model())   # what a replan commits
    tr.cfg.steps = 12
    tr.run(resume=True)
    l1 = {m["step"]: m["loss"] for m in straight["metrics"]}
    l2 = {m["step"]: m["loss"] for m in tr.metrics_log}
    for s in range(6, 12):
        assert l2[s] == l1[s], f"step {s}: {l2[s]} != {l1[s]} (bitwise)"


def test_trainer_zero_requires_explicit_dp():
    cfg = get_config("smollm-135m").reduced()
    with pytest.raises(ValueError, match="explicit-DP"):
        Trainer(cfg, SHAPE, adamw.OptConfig(),
                TrainConfig(steps=1, ckpt_every=0, zero=True))


def test_trainer_zero_save_restore_and_cross_mode(tmp_path):
    """End-to-end ZeRO trainer: carrier-shaped opt state, checkpoint carries
    the shard spec, resume replays deterministically, and restoring across
    zero<->replicated trainer modes raises instead of misreading m/v."""
    from jax.sharding import AxisType

    cfg = get_config("smollm-135m").reduced()
    opt = adamw.OptConfig(peak_lr=1e-3, warmup_steps=2, decay_steps=50)
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))

    def make(ckpt_dir, steps, **kw):
        return Trainer(cfg, SHAPE, opt,
                       TrainConfig(steps=steps, ckpt_every=4,
                                   ckpt_dir=str(ckpt_dir), log_every=100,
                                   ckpt_async=False, explicit_dp=True,
                                   bucket_bytes=1 << 16, **kw),
                       mesh=mesh)

    r1 = make(tmp_path / "a", 8, zero=True).run()
    assert all(np.isfinite(m["loss"]) for m in r1["metrics"])
    # the opt state the trainer built is the carrier, not per-leaf moments
    t2 = make(tmp_path / "a", 8, zero=True)
    _, opt_state = t2.init_state()
    assert set(opt_state) == {"m", "v", "step"} and opt_state["m"].ndim == 2
    # resume from step 8's checkpoint and replay nothing (already done)
    r2 = t2.run(resume=True)
    assert r2["final_step"] == 8
    # crash/resume replay determinism through the sharded checkpoint
    t3 = make(tmp_path / "c", 8, zero=True)
    r3 = t3.run(inject_failure_at=6)
    l1 = {m["step"]: m["loss"] for m in r1["metrics"]}
    l3 = {m["step"]: m["loss"] for m in r3["metrics"]}
    assert l3[7] == pytest.approx(l1[7], rel=1e-5)
    # a replicated explicit-DP trainer must refuse the ZeRO checkpoint
    with pytest.raises(ValueError, match="replicated trainer"):
        make(tmp_path / "a", 8).restore()
    # and the ZeRO trainer must refuse a replicated checkpoint
    make(tmp_path / "r", 4).run()
    with pytest.raises(ValueError, match="replicated checkpoint"):
        make(tmp_path / "r", 4, zero=True).restore()


def test_compile_cache_follows_env_else_checkout(monkeypatch):
    """The entry points' compile cache: JAX_COMPILATION_CACHE_DIR when set
    (nothing is set in code), else one fixed directory in the checkout."""
    from pathlib import Path
    from repro.launch.compile_cache import REPO_CACHE, use_compile_cache

    old = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        use_compile_cache()
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        use_compile_cache()
        assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE)
        assert REPO_CACHE == Path(__file__).resolve().parents[1] / ".jax_cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
