"""Training cells: the program's ``runtime.train.Trainer`` built as
``launch.train`` builds it for the traffic file's ``launch`` flags, fed
seeded rows through its ``data`` argument.

Set-up makes the weights from the seed, builds the Trainer and drives its
first ``CHECK_STEPS`` steps through ``Trainer.run`` (every compile happens
there).  The window hands the same Trainer, with the state those steps left,
back to ``Trainer.run`` until ``--seconds`` have passed.  A phase ends when the
feed is asked for a batch after its end and raises `PhaseEnd`, so every
completed step's host work is inside the window and no checkpoint is written.

``correct`` compares the first three steps with the plain reference
(`reference`), which reruns them from the same seed in float32.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import math
import shutil
import statistics
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from . import check, traffic as T, tracing

CHECK_STEPS = 3


class PhaseEnd(Exception):
    """Raised by the feed before a step that falls after a phase's end."""


class Feed:
    """The Trainer's data source: seeded rows for each step."""

    def __init__(self, seed: int, rows: int, seq: int, vocab: int):
        self.seed, self.rows, self.seq, self.vocab = seed, rows, seq, vocab
        self.stop_step: Optional[int] = None
        self.deadline: Optional[float] = None
        self.t_stop: Optional[float] = None

    def batch_at(self, step: int) -> Dict[str, np.ndarray]:
        now = time.perf_counter()
        if ((self.stop_step is not None and step >= self.stop_step)
                or (self.deadline is not None and now >= self.deadline)):
            self.t_stop = now
            raise PhaseEnd
        with tracing.span("bench.batch"):
            return {"tokens": T.token_rows(self.seed, step, self.rows,
                                           self.seq, self.vocab)}


def weights_key(seed: int):
    import jax

    return jax.random.fold_in(T.jax_key(seed), 1)


def job(traffic: Dict[str, Any]) -> argparse.Namespace:
    """The traffic file's ``launch`` flags, read as ``launch.train`` reads
    them (the flags this harness supports, with the launcher's defaults)."""
    ap = argparse.ArgumentParser(prog="launch.train")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--mesh", default=None)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=50)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--explicit-dp", action="store_true")
    ap.add_argument("--overlap", action="store_true")
    ap.add_argument("--zero", action="store_true")
    ap.add_argument("--compress-bits", default="0")
    ap.add_argument("--chunks", type=int, default=None)
    ap.add_argument("--bucket-bytes", type=int, default=None)
    return ap.parse_args(traffic["launch"])


def opt_values(traffic) -> Dict[str, float]:
    """AdamW's settings: the launcher's flags, the rest at OptConfig's
    defaults as the traffic file states them."""
    a = job(traffic)
    return {"peak_lr": a.lr, "warmup_steps": a.warmup, "decay_steps": a.steps,
            **traffic["optimizer"]}


# ----------------------------------------------------------------- program
@dataclasses.dataclass
class Program:
    trainer: Any
    feed: Feed
    params: Any
    opt_state: Any
    ckpt_dir: str


def build(cell, fam, seed: int) -> Program:
    """The Trainer as ``launch.train`` builds it, and its initial state made
    from the seed (placed as ``Trainer.init_state`` places its own)."""
    import jax

    from repro.configs.base import SHAPES
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import parse_mesh, resolve_step_program
    from repro.models.sharding import tree_shardings_shaped
    from repro.optim import OptConfig, adamw
    from repro.runtime.train import Trainer, TrainConfig

    t = cell.traffic
    a = job(t)
    cfg = fam.program_config(cell.config)
    shape = dataclasses.replace(SHAPES[a.shape], seq_len=t["seq"],
                                global_batch=t["global_batch"])
    explicit = a.explicit_dp or a.overlap or a.zero
    mesh = parse_mesh(a.mesh) if a.mesh else make_host_mesh(model=1 if explicit else 0)
    program, dcn_axis = resolve_step_program(a, mesh, None)
    feed = Feed(seed, t["global_batch"], t["seq"], cfg.vocab)
    ckpt_dir = tempfile.mkdtemp(prefix="chipbench-ckpt-")
    trainer = Trainer(
        cfg, shape, OptConfig(**opt_values(t)),
        TrainConfig(steps=a.steps, microbatches=a.microbatches, ckpt_every=0,
                    ckpt_dir=ckpt_dir, log_every=10,
                    explicit_dp=a.explicit_dp, dcn_axis=dcn_axis,
                    program=program),
        mesh=mesh, data=feed)
    params = fam.to_program(jax.jit(lambda k: fam.make_weights(k, cell.config))(
        weights_key(seed)))
    dp = trainer._dp_step
    if dp is not None and getattr(dp, "zero", False):
        opt_state = dp.init_opt_state(params)
    else:
        opt_state = adamw.init_opt_state(params)
    if trainer.model.shd.mesh is not None:
        sh = tree_shardings_shaped(trainer.model.shd, trainer.model.param_logical(),
                                   params)
        params = jax.tree.map(jax.device_put, params, sh)
    return Program(trainer, feed, params, opt_state, ckpt_dir)


def named(tree, rename: Callable[[str], str] = lambda n: n) -> Dict[str, Any]:
    """{leaf path: leaf}, paths renamed to the reference's names."""
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {rename("/".join(str(getattr(p, "key", p)) for p in path)): leaf
            for path, leaf in flat}


def leaf_norms(tree, rename: Callable[[str], str]) -> Dict[str, Any]:
    """{leaf path: float32 norm} (device scalars)."""
    import jax.numpy as jnp

    return {k: jnp.sqrt(jnp.sum(jnp.square(jnp.asarray(v, jnp.float32))))
            for k, v in named(tree, rename).items()}


def _floats(d):
    return {k: float(v) for k, v in d.items()}


class Recorder:
    """Wraps ``trainer.step_fn``: spans around each call, and after steps 1
    and 3 the readings the check compares (first gradient from AdamW's first
    moment, parameter change, replica agreement)."""

    def __init__(self, fn, fam, p0, b1: float):
        self.fn, self.fam, self.p0, self.b1 = fn, fam, p0, b1
        self.calls = 0
        self.last = None
        self.grad_norms = None
        self.grads = None
        self.change_norms = None
        self.replica_diff = None

    def __call__(self, params, opt_state, batch):
        import jax
        import jax.numpy as jnp

        with tracing.span("bench.step_call"):
            out = self.fn(params, opt_state, batch)
        self.calls += 1
        self.last = out
        if self.calls == 1:
            m = jax.tree.map(lambda x: x / (1.0 - self.b1), out[1]["m"])
            self.grad_norms = leaf_norms(m, self.fam.leaf_name)
            self.grads = named(jax.device_get(m), self.fam.leaf_name)
        if self.calls == CHECK_STEPS:
            delta = jax.tree.map(lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
                                 out[0], self.p0)
            self.change_norms = leaf_norms(delta, self.fam.leaf_name)
            self.replica_diff = replica_max_diff(out[0])
            self.p0 = None
        return out


def replica_max_diff(params) -> float:
    """Largest |difference| between any chip's copy of a leaf and chip 0's
    (0 where the leaf lives on one chip or is split, not copied)."""
    import jax
    import jax.numpy as jnp

    worst = 0.0
    for leaf in jax.tree.leaves(params):
        shards = leaf.addressable_shards
        if len(shards) < 2 or any(s.index != shards[0].index for s in shards):
            continue
        d0 = shards[0].data.device
        ref = shards[0].data.astype(jnp.float32)
        for s in shards[1:]:
            other = jax.device_put(s.data, d0).astype(jnp.float32)
            worst = max(worst, float(jnp.max(jnp.abs(other - ref))))
    return worst


def first_steps(prog: Program, fam, traffic) -> Dict[str, Any]:
    """Drive the first CHECK_STEPS steps through ``Trainer.run``; return the
    program's readings.  The Trainer keeps its wrapped step for the window."""
    import jax

    rec = Recorder(prog.trainer.step_fn, fam,
                   jax.tree.map(lambda a: a.copy(), prog.params),
                   traffic["optimizer"]["b1"])
    prog.trainer.step_fn = rec
    prog.feed.stop_step = CHECK_STEPS
    try:
        prog.trainer.run(prog.params, prog.opt_state)
    except PhaseEnd:
        pass
    prog.feed.stop_step = None
    if rec.calls != CHECK_STEPS:
        raise RuntimeError(f"{rec.calls} steps ran, want {CHECK_STEPS}")
    prog.params, prog.opt_state = rec.last[0], rec.last[1]
    rec.last = None
    losses = [row["loss"] for row in prog.trainer.metrics_log[:CHECK_STEPS]]
    return {"losses": losses, "grad_norms": _floats(rec.grad_norms),
            "grads": rec.grads, "change_norms": _floats(rec.change_norms),
            "replica_diff": rec.replica_diff}


def window(prog: Program, seconds: float, trace: bool) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    feed = prog.feed
    n0 = len(prog.trainer.metrics_log)
    with tracing.capture(trace, out):
        with tracing.span(tracing.WINDOW):
            t0 = time.perf_counter()
            feed.deadline = t0 + seconds
            try:
                prog.trainer.run(prog.params, prog.opt_state, start_step=n0)
            except PhaseEnd:
                pass
    rows = prog.trainer.metrics_log[n0:]
    out.update(steps=len(rows), window_s=feed.t_stop - t0,
               step_times=[r["time_s"] for r in rows],
               losses=[r["loss"] for r in rows])
    return out


def release(prog: Program) -> None:
    shutil.rmtree(prog.ckpt_dir, ignore_errors=True)
    prog.trainer = prog.params = prog.opt_state = None
    gc.collect()


# --------------------------------------------------------------- reference
def reference(fam, config, traffic, seed: int, devices, rnd=None,
              steps: int = CHECK_STEPS) -> Dict[str, Any]:
    """The first ``steps`` steps of plain float32 AdamW training from the same
    seed: weights as the configuration stores them (``dtype``), every
    sequence's gradient at HIGHEST precision (sequences spread over
    ``devices``), the mean over the global batch, global-norm clipping, then
    the update in float32, stored back in ``dtype``."""
    import jax
    import jax.numpy as jnp

    rnd = rnd or fam.identity
    f32 = jnp.float32
    store = jnp.dtype(config["dtype"])
    o = opt_values(traffic)
    B, S, V = traffic["global_batch"], traffic["seq"], fam.sizes(config)["V"]
    t0 = time.perf_counter()
    # the weights as stored (a program of their own, so the rounding to
    # ``store`` happens), then widened
    w = jax.jit(lambda k: fam.make_weights(k, config, store))(weights_key(seed))
    w = jax.device_put(jax.tree.map(lambda a: a.astype(f32), w), devices[0])
    fi = jnp.finfo(store)
    vg = jax.jit(jax.value_and_grad(lambda w, t: fam.loss(w, t, config, rnd)))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))

    def lr_at(step):
        warm = o["peak_lr"] * jnp.minimum(step / max(o["warmup_steps"], 1), 1.0)
        frac = jnp.clip((step - o["warmup_steps"])
                        / max(o["decay_steps"] - o["warmup_steps"], 1), 0.0, 1.0)
        cos = o["min_lr"] + 0.5 * (o["peak_lr"] - o["min_lr"]) * (1 + jnp.cos(jnp.pi * frac))
        return jnp.where(step < o["warmup_steps"], warm, cos)

    @jax.jit
    def adamw(w, g, m, v, step):
        step = step.astype(f32)
        gn = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        scale = jnp.minimum(1.0, o["clip_norm"] / (gn + 1e-9))
        g = jax.tree.map(lambda x: x * scale, g)
        b1, b2 = o["b1"], o["b2"]
        m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
        v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
        c1, c2, lr = 1 - b1 ** step, 1 - b2 ** step, lr_at(step)

        def upd(w, m, v):
            d = (m / c1) / (jnp.sqrt(v / c2) + o["eps"]) + o["weight_decay"] * w
            new = w - lr * d
            if store == f32:
                return new
            # stored as the configuration stores it; reduce_precision is kept
            # by the compiler where a cast down and back up may be dropped
            return jax.lax.reduce_precision(new, exponent_bits=fi.nexp,
                                            mantissa_bits=fi.nmant)

        return jax.tree.map(upd, w, m, v), m, v, g

    zeros = jax.tree.map(jnp.zeros_like, w)
    m, v, w0 = zeros, zeros, w
    losses, grad_norms, grads = [], None, None
    for step in range(steps):
        rows = T.token_rows(seed, step, B, S, V)
        reps = [jax.device_put(w, d) for d in devices]
        acc: List[Any] = [None] * len(devices)
        lsum: List[Any] = [0.0] * len(devices)
        for i in range(B):
            d = i % len(devices)
            l, g = vg(reps[d], jax.device_put(rows[i], devices[d]))
            acc[d] = g if acc[d] is None else add(acc[d], g)
            lsum[d] = lsum[d] + l
        total = acc[0]
        for a in acc[1:]:
            if a is not None:
                total = add(total, jax.device_put(a, devices[0]))
        losses.append(sum(float(x) for x in lsum) / B)
        g = jax.tree.map(lambda x: x / B, total)
        w, m, v, gs = adamw(w, g, m, v, jnp.asarray(step + 1))
        if step == 0:
            grad_norms = _floats(leaf_norms(gs, lambda n: n))
            grads = named(gs)
        del reps, acc, total, g
    change = jax.tree.map(lambda a, b: a - b, w, w0)
    return {"losses": losses, "grad_norms": grad_norms, "grads": grads,
            "change_norms": _floats(leaf_norms(change, lambda n: n)),
            "seconds": time.perf_counter() - t0}


def grad_rel_err(prog: Dict[str, Any], ref: Dict[str, Any]) -> float:
    """Worst leaf of ‖g_prog − g_ref‖ over the larger of ‖g_ref‖ and the
    median leaf's norm: the first gradient's error, which rounding moves in
    first order (a gap of norms moves only in second)."""
    import jax
    import jax.numpy as jnp

    dev = next(iter(ref["grads"].values())).devices().pop()
    diff = {}
    for k, g in ref["grads"].items():
        p = jax.device_put(jnp.asarray(prog["grads"][k]), dev).astype(jnp.float32)
        diff[k] = float(jnp.sqrt(jnp.sum(jnp.square(p - g))))
    return check.leaf_norm_gap(diff, ref["grad_norms"], zero_prog=True)


def numbers(prog: Dict[str, Any], ref: Dict[str, Any], chips: int) -> Dict[str, float]:
    """What ``correct`` holds against the limits."""
    moving = check.moving_leaves(ref["grad_norms"])
    out = {"loss_rel_gap": check.rel_gap(prog["losses"], ref["losses"]),
           "grad_norm_gap": check.leaf_norm_gap(prog["grad_norms"], ref["grad_norms"]),
           "change_norm_gap": check.leaf_norm_gap(prog["change_norms"],
                                                  ref["change_norms"], moving),
           "grad_rel_err": grad_rel_err(prog, ref)}
    if chips > 1:
        out["replica_max_abs_diff"] = prog["replica_diff"]
    return out


# --------------------------------------------------------------------- cell
def run_cell(cell, fam, seed: int, seconds: float, trace: bool, devs,
             t_start: float) -> Dict[str, Any]:
    from . import device

    prog = build(cell, fam, seed)
    first = first_steps(prog, fam, cell.traffic)
    setup_s = time.perf_counter() - t_start
    win = window(prog, seconds, trace)
    peak = device.peak_bytes(devs)
    release(prog)
    ref = reference(fam, cell.config, cell.traffic, seed, devs)
    nums = numbers(first, ref, cell.chips)
    tokens = win["steps"] * cell.traffic["global_batch"] * cell.traffic["seq"]
    rate = tokens / win["window_s"] / cell.chips
    st = win["step_times"]
    info = {"window_steps": win["steps"], "window_s": win["window_s"],
            "step_s_median": statistics.median(st) if st else math.nan,
            "step_s_min": min(st) if st else math.nan,
            "step_s_max": max(st) if st else math.nan,
            "first_losses": first["losses"], "ref_losses": ref["losses"],
            "reference_s": ref["seconds"]}
    ctx = {"kind": "train", "trace": win.get("trace"), "steps": win["steps"],
           "trace_read_s": win.get("trace_read_s"),
           "window_s": win["window_s"], "tokens_per_s_per_chip": rate,
           "chips": cell.chips, "config": cell.config, "family": fam,
           "traffic": cell.traffic}
    return {"end_to_end": {"train_tokens_per_s_per_chip": rate, "setup_s": setup_s},
            "ctx": ctx, "numbers": nums, "attempted": win["steps"],
            "failed": sum(1 for x in win["losses"] if not math.isfinite(x)),
            "peak_bytes": peak, "info": info}
