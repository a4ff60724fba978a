"""Serving cells: the program's ``runtime.serve.BatchedServer`` built as
``launch.serve`` builds it, driven by a closed loop of fixed batches.

Each batch is ``batch`` seeded prompts of ``prompt`` tokens, generated for
``new_tokens`` greedy tokens with no end token; the next batch is sent when
``generate`` returns.  Set-up makes the weights from the seed and runs one
short ``generate`` on the cell's shapes, which compiles prefill and decode.

``correct``: once the window has closed and the server is freed, a sample of
the finished requests, drawn from the seed, is run through the plain
reference with the tokens the server returned; the number compared is the
widest gap by which a served token's reference logit lies below the
reference's best at its position.
"""
from __future__ import annotations

import gc
import math
import statistics
import time
from typing import Any, Dict, List

import numpy as np

from . import traffic as T, tracing
from .train import weights_key

WARM_INDEX = 2**40   # the warm-up batch's traffic index, apart from the window's


class Counted:
    """Wraps one of the server's jitted programs: a span and a call count."""

    def __init__(self, fn, name: str):
        self.fn, self.name, self.calls = fn, name, 0

    def __call__(self, *a):
        self.calls += 1
        with tracing.span(self.name):
            return self.fn(*a)


def build(cell, fam, seed: int):
    import jax

    from repro.launch.mesh import make_host_mesh
    from repro.runtime.serve import BatchedServer

    t = cell.traffic
    cfg = fam.program_config(cell.config)
    params = fam.to_program(jax.jit(lambda k: fam.make_weights(k, cell.config))(
        weights_key(seed)))
    server = BatchedServer(cfg, max_seq=t["prompt"] + t["new_tokens"] + 8,
                           batch_size=t["batch"], mesh=make_host_mesh(),
                           params=params)
    server._prefill = Counted(server._prefill, "bench.prefill_call")
    server._decode = Counted(server._decode, "bench.decode_call")
    return server


def prompts(seed: int, index: int, t, vocab: int) -> np.ndarray:
    return T.token_rows(seed, index, t["batch"], t["prompt"], vocab)


def warm(server, seed: int, t, vocab: int) -> None:
    from repro.runtime.serve import ServeConfig

    server.generate(prompts(seed, WARM_INDEX, t, vocab), ServeConfig(max_new_tokens=2))
    server._prefill.calls = server._decode.calls = 0


def window(server, seed: int, seconds: float, trace: bool, t, vocab: int):
    from repro.runtime.serve import ServeConfig

    out: Dict[str, Any] = {}
    served: List[np.ndarray] = []
    lat: List[float] = []
    with tracing.capture(trace, out):
        with tracing.span(tracing.WINDOW):
            t0 = time.perf_counter()
            now = t0
            while now - t0 < seconds:
                p = prompts(seed, len(served), t, vocab)
                ids = server.generate(p, ServeConfig(max_new_tokens=t["new_tokens"]))
                served.append(np.asarray(ids))
                last, now = now, time.perf_counter()
                lat.append(now - last)
    out.update(window_s=now - t0, served=served, batch_s=lat,
               calls={"prefill": server._prefill.calls,
                      "decode": server._decode.calls})
    return out


def reference_gaps(fam, config, seed: int, t, served, sample: np.ndarray,
                   rnd=None) -> Dict[str, Any]:
    """Reference logits over each sampled request's prompt and served tokens;
    ``gap`` per served token (reference best minus the served token's logit)
    and the tokens the reference itself ranks first."""
    import jax
    import jax.numpy as jnp

    P, G = t["prompt"], t["new_tokens"]
    V = fam.sizes(config)["V"]
    B = t["batch"]
    rows_p, rows_s = [], []
    for r in sample:
        b, i = divmod(int(r), B)
        rows_p.append(prompts(seed, b, t, V)[i])
        rows_s.append(served[b][i])
    seqs = np.concatenate([np.stack(rows_p), np.stack(rows_s)[:, :-1]], axis=1)
    positions = np.arange(P - 1, P + G - 1)
    t0 = time.perf_counter()
    fn = jax.jit(lambda k, s: fam.logits(k, config, s, positions,
                                         rnd or fam.identity,
                                         jnp.dtype(config["dtype"])))
    lg = fn(weights_key(seed), jnp.asarray(seqs, jnp.int32))
    tok = jnp.asarray(np.stack(rows_s), jnp.int32)
    gold = jnp.take_along_axis(lg, tok[..., None], axis=-1)[..., 0]
    gaps = np.asarray(jnp.max(lg, axis=-1) - gold)
    return {"gaps": gaps, "top": np.asarray(jnp.argmax(lg, axis=-1)),
            "logits": lg, "tokens": np.stack(rows_s),
            "seconds": time.perf_counter() - t0}


def run_cell(cell, fam, seed: int, seconds: float, trace: bool, devs,
             t_start: float) -> Dict[str, Any]:
    from . import device

    t = cell.traffic
    V = fam.sizes(cell.config)["V"]
    server = build(cell, fam, seed)
    warm(server, seed, t, V)
    setup_s = time.perf_counter() - t_start
    win = window(server, seed, seconds, trace, t, V)
    peak = device.peak_bytes(devs)
    server.params = server.model = None
    del server
    gc.collect()
    n_req = len(win["served"]) * t["batch"]
    want = (t["batch"], t["new_tokens"])
    failed = sum(t["batch"] for s in win["served"] if s.shape != want)
    sample = T.sample(seed, n_req, t["sample_requests"])
    ref = reference_gaps(fam, cell.config, seed, t, win["served"], sample)
    gap = float(np.max(ref["gaps"])) if ref["gaps"].size else math.inf
    tokens = sum(int(s.size) for s in win["served"])
    rate = tokens / win["window_s"]
    info = {"batches": len(win["served"]), "window_s": win["window_s"],
            "batch_s": win["batch_s"],
            "batch_s_median": statistics.median(win["batch_s"]),
            "sampled_requests": [int(x) for x in sample],
            "served_tokens_compared": int(ref["gaps"].size),
            "reference_s": ref["seconds"]}
    ctx = {"kind": "serve", "trace": win.get("trace"), "calls": win["calls"],
           "trace_read_s": win.get("trace_read_s"),
           "window_s": win["window_s"], "chips": cell.chips,
           "config": cell.config, "family": fam, "traffic": t}
    return {"end_to_end": {"serve_tokens_per_s": rate, "setup_s": setup_s},
            "ctx": ctx, "numbers": {"served_gap": gap}, "attempted": n_req,
            "failed": failed, "peak_bytes": peak, "info": info}
