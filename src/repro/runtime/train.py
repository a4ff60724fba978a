"""Training loop: checkpoint/restart, straggler mitigation, elastic re-meshing,
fault injection, and drift-guarded online re-planning.

The loop composes:
  * steps.train_step_bundle       — jitted step with FSDP+TP shardings
  * checkpoint.CheckpointManager  — async atomic saves, reshard-on-restore
  * data.SyntheticLM/TokenFile    — step-keyed deterministic batches (replay)
  * core.noise.StragglerMitigator — per-step time tracking + action (Sec. VI):
                                    'log', 'sync' (barrier), 'skip' (drop the
                                    step's update — rejected under ZeRO, where
                                    sharded optimizer state makes it unsound)
  * core.faults.FaultInjector     — seeded fault schedule wrapped around the
                                    step: transient failures / node loss raise,
                                    degradation windows perturb the measured
                                    step time (the simulated messy fabric)
  * guard.DriftGuard              — EWMA drift band around the calibrated
                                    step-time reference; sustained drift runs
                                    the probe -> refit -> re-rank -> lint-gate
                                    -> swap pipeline (`_replan`) mid-run
  * recovery                      — classified errors (transient vs fatal),
                                    bounded retry with exponential backoff,
                                    elastic re-mesh on node loss rebuilding on
                                    the surviving device set

On failure injection (tests) or a runtime error from the device, `run()`
re-enters through `_build()`; data replays from the restored step.  Fatal
errors propagate immediately: anything that does not look like a fabric/device
fault, and out-of-memory or compile/lowering failures, which a replay would
only repeat.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, Optional, Sequence, Tuple, Union

import jax
import numpy as np

from ..checkpoint.manager import CheckpointManager
from ..configs.base import ModelConfig, ShapeConfig
from ..core.faults import FaultInjector, NodeLossFault, TransientFault
from ..core.noise import StragglerMitigator
from ..data.pipeline import SyntheticLM, DataConfig
from ..models.model import build_model
from ..models.sharding import tree_shardings_shaped
from ..optim import adamw
from . import steps as rsteps
from .guard import DriftGuard, GuardConfig

# substrings that mark a RuntimeError as a fabric/device fault worth the
# restore-and-retry path; anything else is a genuine bug and propagates
_TRANSIENT_MARKERS = ("injected device failure", "injected transient",
                      "device", "communicator", "nccl", "collective",
                      "data_loss", "unavailable", "deadline", "xla runtime")
# ...except these, which a replay would only repeat: out of device memory,
# and a step (or one of its kernels) the compiler refused
_FATAL_MARKERS = ("resource_exhausted", "out of memory", "compil", "lowering",
                  "mosaic")


def _is_transient(e: BaseException) -> bool:
    if isinstance(e, (TransientFault, NodeLossFault)):
        return True
    if not isinstance(e, RuntimeError):  # JaxRuntimeError is one
        return False
    msg = str(e).lower()
    if any(m in msg for m in _FATAL_MARKERS):
        return False
    return (isinstance(e, jax.errors.JaxRuntimeError)
            or any(m in msg for m in _TRANSIENT_MARKERS))


@dataclasses.dataclass
class TrainConfig:
    steps: int = 50
    microbatches: int = 1
    ckpt_every: int = 20
    ckpt_dir: str = "artifacts/ckpt"
    ckpt_async: bool = True
    log_every: int = 10
    straggler_threshold: float = 2.5
    straggler_action: str = "log"
    # explicit-DP path (shard_map + our collectives, paper Obs. 1/4): params
    # replicated, batch sharded on dp_axis (and dcn_axis on a two-pod mesh)
    explicit_dp: bool = False
    dp_axis: str = "data"
    dcn_axis: Optional[str] = None
    policy: Optional[object] = None       # core.autotune.CollectivePolicy
    bucket_bytes: Optional[int] = None    # None = plan crossover, 0 = per-tensor
    # int8 error-feedback wire compression (0 = fp32 wire).  Composes with
    # bucketing and overlap: the codec quantizes per bucket and the error
    # state becomes the carrier-shaped buffer (see runtime.steps)
    compress_bits: int = 0
    # overlap-aware execution (core.overlap): reverse-layer-order buckets on a
    # scan-carried issue schedule; with microbatches > 1 each bucket's
    # reduction overlaps the next microbatch's backward, and on a two-level
    # mesh buckets run the chunked hierarchical pipeline
    overlap: bool = False
    chunks: Optional[int] = None          # None = plan's per-tier alpha-beta fit
    # ZeRO-style sharded optimizer (runtime.steps): reduce-scatter the packed
    # carrier, AdamW over each device's shard (fp32 m/v carrier-sharded, so
    # optimizer memory drops by the DP degree), all-gather updated params at
    # the wire dtype.  Implies explicit_dp + bucketed carrier.
    zero: bool = False
    # StepProgram (core.program): the declarative schedule the step compiles
    # from.  When set it supersedes the boolean knobs above (which become a
    # legacy shim — launch.train.resolve_step_program builds the program from
    # the flags); its name is stamped into checkpoint metadata.
    program: Optional[object] = None
    # fault injection (core.faults): a FaultPlan (or prebuilt FaultInjector)
    # replayed deterministically around the step loop
    faults: Optional[object] = None
    # drift guard (runtime.guard): watch measured step time against the
    # reference band; sustained drift probes, refits, re-ranks, and lint-gates
    # a plan swap mid-run
    guard: bool = False
    guard_cfg: Optional[object] = None    # runtime.guard.GuardConfig
    # recovery: classified transient errors get at most max_retries
    # consecutive restore-and-replay attempts with exponential backoff
    max_retries: int = 3
    retry_backoff_s: float = 0.05


class Trainer:
    def __init__(self, model_cfg: ModelConfig, shape: ShapeConfig,
                 opt: Optional[adamw.OptConfig] = None,
                 train_cfg: Optional[TrainConfig] = None,
                 mesh=None, data=None):
        self.model_cfg = model_cfg
        self.shape = shape
        self.opt = opt or adamw.OptConfig()
        self.cfg = train_cfg or TrainConfig()
        self.mesh = mesh
        self.data = data or SyntheticLM(model_cfg, shape)
        if self.cfg.straggler_action == "skip" and self.cfg.zero:
            raise ValueError(
                "straggler_action='skip' is unsound with zero=True: dropping "
                "a step after the reduce-scatter leaves the carrier-sharded "
                "optimizer moments half-advanced across devices; use 'sync' "
                "or 'log' under ZeRO")
        self.ckpt = CheckpointManager(self.cfg.ckpt_dir)
        self.straggler = StragglerMitigator(threshold=self.cfg.straggler_threshold,
                                            action=self.cfg.straggler_action)
        self.metrics_log: list = []
        self.injector: Optional[FaultInjector] = None
        if self.cfg.faults is not None:
            self.injector = (self.cfg.faults
                             if isinstance(self.cfg.faults, FaultInjector)
                             else FaultInjector(self.cfg.faults))
        self.guard: Optional[DriftGuard] = None
        if self.cfg.guard:
            gcfg = self.cfg.guard_cfg or GuardConfig()
            self.guard = DriftGuard(gcfg, replanner=self._replan)
        self.skipped_steps = 0
        self.retry_log: list = []
        self._build(self.mesh)

    # ----------------------------------------------------------------- build
    def _build(self, mesh):
        if self.cfg.explicit_dp:
            if mesh is None:
                raise ValueError("explicit_dp requires a multi-device mesh; "
                                 "got mesh=None (single-device host?)")
            self._build_explicit_dp(mesh)
            return
        if self.cfg.zero:
            raise ValueError("zero=True requires the explicit-DP path "
                             "(explicit_dp=True / launch.train --zero)")
        self._dp_step = None
        self.model = build_model(self.model_cfg, mesh)
        self.bundle = rsteps.train_step_bundle(self.model, self.shape, self.opt,
                                               microbatches=self.cfg.microbatches)
        if mesh is not None:
            self.step_fn = jax.jit(self.bundle.fn, in_shardings=self.bundle.in_shardings,
                                   out_shardings=self.bundle.out_shardings,
                                   donate_argnums=self.bundle.donate_argnums)
        else:
            self.step_fn = jax.jit(self.bundle.fn, donate_argnums=self.bundle.donate_argnums)

    def _build_explicit_dp(self, mesh):
        """Explicit-DP: replicated params (model built without mesh constraints),
        gradients reduced by our CommPlan-dispatched collectives with bucketing.
        Error-feedback state lives on the trainer, initialized at first step."""
        c = self.cfg
        for ax, size in mesh.shape.items():
            if ax not in (c.dp_axis, c.dcn_axis) and size > 1:
                raise ValueError(f"explicit_dp needs a pure-DP mesh; axis {ax!r} "
                                 f"has size {size}")
        # a program carries its own MicrobatchLoop, which validates that it
        # rides the overlap schedule; the flag path is checked here
        if c.program is None and c.microbatches > 1 and not c.overlap:
            raise ValueError("explicit-DP gradient accumulation is implemented "
                             "by the overlap schedule; pass overlap=True "
                             "(launch.train --overlap) with microbatches "
                             f"({c.microbatches} requested)")
        self.model = build_model(self.model_cfg)
        if c.program is not None:
            dp_step = rsteps.build_program_step(
                self.model, self.opt, mesh, c.program, axis=c.dp_axis,
                policy=c.policy, dcn_axis=c.dcn_axis)
        else:
            dp_step = rsteps.build_explicit_dp_step(
                self.model, self.opt, mesh, c.dp_axis, policy=c.policy,
                bucket_bytes=c.bucket_bytes, dcn_axis=c.dcn_axis,
                overlap=c.overlap, chunks=c.chunks,
                microbatches=c.microbatches, compress_bits=c.compress_bits,
                zero=c.zero)
        self._dp_step = dp_step
        self._dp_err = None

        def step_fn(params, opt_state, batch):
            if self._dp_err is None:
                # carrier-shaped under bucketed compression, per-leaf otherwise
                self._dp_err = dp_step.init_error_state(params)
            params, opt_state, metrics, self._dp_err = dp_step(
                params, opt_state, batch, self._dp_err)
            return params, opt_state, metrics

        self.step_fn = step_fn

    def init_state(self, seed: int = 0):
        params = self.model.init(jax.random.PRNGKey(seed))
        if self._dp_step is not None and getattr(self._dp_step, "zero", False):
            # carrier-sharded m/v: (n_buckets, padded_elems) fp32, laid out by
            # the step's codec table (runtime.steps.make_opt_state)
            opt_state = self._dp_step.init_opt_state(params)
        else:
            opt_state = adamw.init_opt_state(params)
        if self.model.shd.mesh is not None:
            p_sh = tree_shardings_shaped(self.model.shd, self.model.param_logical(),
                                         params)
            params = jax.tree.map(jax.device_put, params, p_sh)
        return params, opt_state

    # ------------------------------------------------------------------ run
    def run(self, params=None, opt_state=None, start_step: int = 0,
            resume: bool = False,
            inject_failure_at: Union[int, Sequence[int], None] = None) -> Dict:
        """Run the training loop with the recovery/guard machinery.

        `inject_failure_at` takes a step index or a sequence of them; each
        entry raises one recoverable failure at that step (a repeated entry
        exercises a repeated fault — each firing consumes one entry, so the
        replayed steps after a restore do not re-raise an already-fired one).
        """
        if resume and self.ckpt.latest_step() is not None:
            params, opt_state, start_step = self.restore()
        if params is None:
            params, opt_state = self.init_state()
        if inject_failure_at is None:
            pending_inject = []
        elif isinstance(inject_failure_at, (list, tuple)):
            pending_inject = sorted(inject_failure_at)
        else:
            pending_inject = [inject_failure_at]
        step = start_step
        retries = 0
        skip = self.cfg.straggler_action == "skip"
        while step < self.cfg.steps:
            batch = {k: jax.numpy.asarray(v) for k, v in self.data.batch_at(step).items()}
            # 'skip' reverts to the pre-step state after the fact, so it needs
            # copies taken before the step (the step may donate its inputs)
            prev = None
            if skip:
                prev = (jax.tree.map(jax.numpy.copy, params),
                        jax.tree.map(jax.numpy.copy, opt_state))
            t0 = time.perf_counter()
            try:
                if pending_inject and step == pending_inject[0]:
                    pending_inject.pop(0)
                    raise RuntimeError("injected device failure (test)")
                if self.injector is not None:
                    self.injector.before_step(step)
                params, opt_state, metrics = self.step_fn(params, opt_state, batch)
                jax.block_until_ready(metrics["loss"])
            except NodeLossFault as e:
                # elastic re-mesh: rebuild on the surviving device set, then
                # restore the last checkpoint onto the shrunk mesh
                self.ckpt.wait()
                if self.ckpt.latest_step() is None:
                    raise
                self.mesh = self._surviving_mesh(e.lost)
                self._build(self.mesh)
                params, opt_state, step = self.restore()
                self.straggler.reset_baseline()
                retries = 0
                continue
            except (RuntimeError, jax.errors.JaxRuntimeError) as e:
                if not _is_transient(e):
                    raise  # a genuine bug, not a fabric fault: propagate
                self.ckpt.wait()
                restored = self.ckpt.latest_step()
                if restored is None:
                    raise  # nothing to restore into: surface the fault
                retries += 1
                self.retry_log.append({"step": step, "attempt": retries,
                                       "error": str(e)[:200]})
                if retries > self.cfg.max_retries:
                    raise RuntimeError(
                        f"persistent failure: {retries - 1} consecutive "
                        f"restore-and-replay attempts failed at step {step} "
                        f"(last error: {e})") from e
                time.sleep(self.cfg.retry_backoff_s * 2 ** (retries - 1))
                self._build(self.mesh)
                params, opt_state, step = self.restore()
                continue
            retries = 0
            dt = time.perf_counter() - t0
            if self.injector is not None:
                # the simulated messy fabric: degradation windows perturb the
                # measured step time (deterministically, per the FaultPlan)
                dt = self.injector.perturb(step, dt)
            ev = self.straggler.observe(step, dt)
            if ev is not None:
                if self.cfg.straggler_action == "sync":
                    jax.block_until_ready(params)
                elif skip:
                    # drop the straggler step's update entirely (and its
                    # error-feedback contribution): the replicated state
                    # reverts to the pre-step snapshot
                    params, opt_state = prev
                    self._dp_err = None
                    self.skipped_steps += 1
            if self.guard is not None:
                gev = self.guard.observe(step, dt)
                if gev is not None and gev.kind == "replan":
                    # the swap changed the step-time population on both
                    # trackers; the injector models the re-ranked plan's
                    # partial recovery on simulated fabrics
                    self.straggler.reset_baseline()
                    if self.injector is not None:
                        self.injector.on_replan(
                            self.guard.cfg.recovered_fraction)
            row = {"step": step, "loss": float(metrics["loss"]),
                   "grad_norm": float(metrics["grad_norm"]),
                   "lr": float(metrics["lr"]), "time_s": dt,
                   "straggler": ev is not None}
            self.metrics_log.append(row)
            if step % self.cfg.log_every == 0:
                print(f"step {step:5d} loss {row['loss']:.4f} "
                      f"gnorm {row['grad_norm']:.3f} {dt*1e3:.0f}ms", flush=True)
            step += 1
            if self.cfg.ckpt_every and step % self.cfg.ckpt_every == 0:
                self.save(step, params, opt_state)
        self.save(step, params, opt_state)
        self.ckpt.wait()
        out = {"final_step": step, "metrics": self.metrics_log,
               "straggler_events": len(self.straggler.events),
               "skipped_steps": self.skipped_steps,
               "retries": len(self.retry_log),
               "final_devices": (int(np.prod(list(self.mesh.shape.values())))
                                 if self.mesh is not None else 1)}
        if self.guard is not None:
            out["guard"] = self.guard.report()
        if self.injector is not None:
            out["fault_log"] = list(self.injector.log)
        return out

    # ----------------------------------------------------------- re-planning
    def _replan(self, step: int):
        """The guard's probe -> refit -> re-rank -> lint-gate -> swap pipeline.

        Returns ``(committed, detail)``.  The probe is the cheap per-tier p2p
        sweep on the live mesh; its records refit the affected tiers and the
        re-ranked plan (new tables, bucket size, chunk depth, wire decision)
        comes back through the same `CommPlan.from_topology(calibration=)`
        path a launch-time --calibration run uses.  The swapped plan must
        lint clean against the step's program before it is allowed to run.
        """
        from ..core.autotune import CollectivePolicy
        from ..core.calibrate import fit_profile
        from ..core.characterize import inter_tier_p2p_sweep, pairwise_p2p_sweep
        from ..core.costmodel import make_comm_model
        from ..core.topology import make_paper_fabrics

        gcfg = self.guard.cfg if self.guard is not None else GuardConfig()
        detail: Dict = {"step": step}
        n_dev = (int(np.prod(list(self.mesh.shape.values())))
                 if self.mesh is not None else 1)
        axis = self.cfg.dp_axis if self.cfg.explicit_dp else None
        profile = None
        if (self.mesh is not None and axis in self.mesh.shape
                and self.mesh.shape[axis] >= 2):
            records = inter_tier_p2p_sweep(self.mesh, axis=axis,
                                           fabric=make_paper_fabrics()["tpu_v5e"],
                                           sizes=gcfg.probe_sizes,
                                           iters=gcfg.probe_iters)
            if not records:
                # the mesh fits inside one tier: fall back to the concurrent
                # pairwise exchange (congestion-aware, untier-qualified fits)
                records = pairwise_p2p_sweep(self.mesh, axis=axis,
                                             sizes=gcfg.probe_sizes,
                                             iters=gcfg.probe_iters)
            profile = fit_profile(records, system="tpu_v5e",
                                  n_endpoints=n_dev,
                                  meta={"source": "guard_replan",
                                        "step": step})
            detail["probe"] = {"records": len(records),
                               "fitted_keys": len(profile.params)}
        policy = CollectivePolicy.from_model(
            make_comm_model("tpu_v5e", calibration=profile),
            calibration=profile)
        detail["bucket_bytes"] = policy.bucket_bytes
        detail["wire"] = policy.wire.to_dict()
        program = self.cfg.program if self.cfg.program is not None \
            else policy.program
        if gcfg.lint and program is not None:
            from ..launch.lint import lint_program_on_mesh
            n_pod = self.mesh.shape.get("pod", 1) if self.mesh is not None else 1
            rep = lint_program_on_mesh(program, n_devices=n_dev,
                                       policy=policy, dcn=n_pod)
            detail["lint"] = {"program": rep["program"],
                              "findings": rep["findings"],
                              "records": rep["records"],
                              "seconds": round(rep["seconds"], 3)}
            if rep["findings"]:
                return False, detail  # keep the old plan: swap rejected
        self._swap_policy(policy)
        detail["swapped"] = True
        return True, detail

    def _swap_policy(self, policy) -> None:
        """Rebuild the compiled step under a new collective policy mid-run.

        Params/opt state are untouched (the swap is a dispatch-table change,
        not a state change); the error-feedback carrier is re-initialized by
        the rebuilt step.  On the fp32 wire the swap is numerically
        transparent — bit parity with an uninterrupted run (tested)."""
        self.cfg.policy = policy
        self._build(self.mesh)

    # --------------------------------------------------------- elastic mesh
    def _surviving_mesh(self, lost: Sequence[int]):
        """Rebuild the mesh on the devices that survived a node loss.

        The DP degree shrinks to the largest survivor count that divides the
        global batch (explicit-DP shards the batch over the dp axis); a
        two-level (pod) mesh collapses to single-level — the lost node broke
        the pod symmetry.  ZeRO state is carrier-sharded by the DP degree, so
        a shrink under zero=True cannot reinterpret the checkpoint and raises.
        """
        from jax.sharding import Mesh

        gone = set(int(d) for d in lost)
        survivors = [d for d in self.mesh.devices.flat if d.id not in gone]
        if not survivors:
            raise RuntimeError("node loss left no surviving devices")
        model_dim = self.mesh.shape.get("model", 1)
        n = max(len(survivors) // model_dim, 1)
        batch = self.shape.global_batch
        while n > 1 and batch % n:
            n -= 1
        old_dp = self.mesh.shape.get(self.cfg.dp_axis, 1)
        if self.cfg.zero and n != old_dp:
            raise RuntimeError(
                f"elastic re-mesh {old_dp} -> {n} devices with zero=True: the "
                f"carrier-sharded optimizer moments are laid out by the DP "
                f"degree; restore the ZeRO checkpoint on an equal-size mesh "
                f"or re-save replicated before shrinking")
        if model_dim > 1:
            devs = np.array(survivors[: n * model_dim]).reshape(n, model_dim)
            return Mesh(devs, ("data", "model"))
        self.cfg.dcn_axis = None  # a lost node collapses the two-level mesh
        return Mesh(np.array(survivors[:n]), (self.cfg.dp_axis,))

    # ------------------------------------------------------------ checkpoint
    def _zero_specs(self) -> Optional[Dict[str, str]]:
        """Per-leaf shard-spec metadata for the ZeRO carrier-sharded m/v (the
        checkpoint refuses a sharded<->replicated cross-restore on them)."""
        if self._dp_step is None or not getattr(self._dp_step, "zero", False):
            return None
        spec = self._dp_step.opt_shard_spec
        return {"opt/m": spec, "opt/v": spec}

    def save(self, step: int, params, opt_state):
        extra = {"step": step}
        program = getattr(self._dp_step, "program", None)
        if program is not None:
            # the schedule that produced this state, auditable from the
            # checkpoint alone (and the ZeRO shard specs below it)
            extra["program"] = program.to_dict()
        self.ckpt.save(step, {"params": params, "opt": opt_state},
                       extra=extra, specs=self._zero_specs(),
                       blocking=not self.cfg.ckpt_async)

    def restore(self, step: Optional[int] = None):
        specs = self._zero_specs()
        if specs is not None:
            abs_p = self.model.abstract_params()
            like = {"params": abs_p,
                    "opt": self._dp_step.abstract_opt_state(abs_p)}
            state, extra = self.ckpt.restore(like, step=step, specs=specs)
            return state["params"], state["opt"], int(extra["step"])
        like = {"params": self.model.abstract_params(),
                "opt": adamw.abstract_opt_state(self.model.abstract_params())}
        shardings = None
        if self.model.shd.mesh is not None:
            p_log = self.model.param_logical()
            shardings = {"params": tree_shardings_shaped(self.model.shd, p_log, like["params"]),
                         "opt": tree_shardings_shaped(self.model.shd,
                                                      adamw.opt_state_logical(p_log),
                                                      like["opt"])}
        state, extra = self.ckpt.restore(like, step=step, shardings=shardings)
        return state["params"], state["opt"], int(extra["step"])
