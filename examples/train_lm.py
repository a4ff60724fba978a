"""End-to-end training driver: ~100M-class model for a few hundred steps.

  PYTHONPATH=src python examples/train_lm.py --arch smollm-135m --steps 300 \
      --seq 128 --batch 8 [--full]   # --full trains the real config (slow on CPU)

Demonstrates: config selection (--arch works for all 10), deterministic data,
async checkpointing + resume, straggler logging, cosine schedule.

MoE quickstart (--moe): an expert-parallel step compiled from the StepProgram
IR — token dispatch/combine run as *planned* alltoalls through the plan's
per-tier tables (set XLA_FLAGS=--xla_force_host_platform_device_count=4 to
watch the exchange cross 4 fake devices):

  PYTHONPATH=src python examples/train_lm.py --moe --steps 20
"""
import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

from repro.configs import get_config, list_configs
from repro.configs.base import ShapeConfig
from repro.optim import OptConfig
from repro.runtime.train import Trainer, TrainConfig


def run_moe(args):
    """Expert-parallel MoE quickstart: build the `moe_alltoall` StepProgram,
    compile it with `build_program_step`, and train the EP layer directly.
    The DP axis doubles as the expert axis; the plan's stats show which
    alltoall algorithm the per-tier tables dispatched."""
    import jax
    from jax.sharding import AxisType

    from repro.core import program as prg
    from repro.core.autotune import CollectivePolicy
    from repro.optim import adamw
    from repro.runtime import moe_step as ms
    from repro.runtime import steps as rsteps

    cfg = get_config("deepseek-moe-16b").reduced()
    # the EP axis must divide the expert count; wider hosts use the first
    # n_experts devices for the exchange
    n = min(jax.device_count(), cfg.n_experts)
    mesh = jax.make_mesh((n,), ("data",), axis_types=(AxisType.Auto,),
                         devices=jax.devices()[:n])
    policy = CollectivePolicy.from_model()
    program = prg.moe_step_program()
    step = rsteps.build_program_step(cfg, adamw.OptConfig(peak_lr=args.lr),
                                     mesh, program, policy=policy)
    print(f"program: {program.name} "
          f"({' -> '.join(nd.kind for nd in program.nodes)}) on {n} device(s)")

    params = ms.moe_ep_params(cfg, jax.random.PRNGKey(0))
    batch = ms.moe_ep_batch(cfg, jax.random.PRNGKey(1), max(args.batch, n), 32)
    opt_state = adamw.init_opt_state(params)
    err = step.init_error_state(params)
    for i in range(args.steps):
        params, opt_state, metrics, err = step(params, opt_state, batch, err)
        if i % 10 == 0 or i == args.steps - 1:
            print(f"step {i:4d} loss {float(metrics['loss']):.4f} "
                  f"aux {float(metrics['aux_loss']):.4f}")
    print("plan stats:", policy._as_plan().stats)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m", choices=list_configs())
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--full", action="store_true",
                    help="use the full config (default: reduced)")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--ckpt", default="artifacts/train_lm_ckpt")
    ap.add_argument("--moe", action="store_true",
                    help="expert-parallel MoE quickstart: the moe_alltoall "
                         "StepProgram with planned token dispatch/combine")
    args = ap.parse_args()
    if args.moe:
        return run_moe(args)

    cfg = get_config(args.arch) if args.full else get_config(args.arch).reduced()
    shape = ShapeConfig("train", args.seq, args.batch, "train")
    print(f"training {cfg.name}: {cfg.param_count()/1e6:.1f}M params, "
          f"{args.steps} steps @ batch {args.batch} x seq {args.seq}")

    trainer = Trainer(
        cfg, shape,
        OptConfig(peak_lr=args.lr, warmup_steps=min(50, args.steps // 10 + 1),
                  decay_steps=args.steps),
        TrainConfig(steps=args.steps, ckpt_every=max(args.steps // 5, 1),
                    ckpt_dir=args.ckpt, log_every=10, ckpt_async=True,
                    straggler_threshold=2.5),
    )
    t0 = time.time()
    result = trainer.run(resume=args.resume)
    dt = time.time() - t0
    losses = [m["loss"] for m in result["metrics"]]
    toks = len(losses) * args.batch * args.seq
    print(f"\ndone: {result['final_step']} steps in {dt:.0f}s "
          f"({toks/dt:.0f} tok/s); loss {losses[0]:.3f} -> {losses[-1]:.3f}; "
          f"stragglers observed: {result['straggler_events']}")


if __name__ == "__main__":
    main()
