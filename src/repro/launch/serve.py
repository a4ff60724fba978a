"""Production serving launcher (batched prefill + sequence-sharded decode).

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m [--reduced] \
      --batch 4 --prompt-len 16 --new-tokens 32 [--mesh 2x4] [--seq-axes model,data]
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np

from ..configs.base import get_config, list_configs
from ..models.model import build_model
from .. import telemetry
from ..runtime.serve import BatchedServer, ServeConfig
from .compile_cache import use_compile_cache
from .mesh import make_host_mesh
from .train import parse_mesh


def main(argv=None):
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_configs())
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--mesh", default=None)
    ap.add_argument("--seq-axes", default=None,
                    help='comma list remapping the KV-cache "seq" sharding, '
                         'e.g. "model,data" for batch=1 long-context decode')
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    mesh = parse_mesh(args.mesh) if args.mesh else make_host_mesh()
    max_seq = args.prompt_len + args.new_tokens + 8
    server = BatchedServer(cfg, max_seq=max_seq, batch_size=args.batch, mesh=mesh)
    if args.seq_axes:
        server.model = build_model(cfg, mesh, seq_axes=tuple(args.seq_axes.split(",")))
    rng = np.random.RandomState(0)
    shape = (args.batch, args.prompt_len) + \
        ((cfg.n_codebooks,) if cfg.n_codebooks else ())
    prompts = rng.randint(0, cfg.vocab, shape).astype(np.int32)
    sc = ServeConfig(max_new_tokens=args.new_tokens)
    server.generate(prompts, sc)               # warm-up: compiles prefill and decode
    built = telemetry.COMPILES.total
    t0 = time.perf_counter()
    out = server.generate(prompts, sc)
    wall = time.perf_counter() - t0
    print(f"{cfg.name}: {out.shape[0] * out.shape[1] / wall:.1f} tok/s "
          f"(batch {out.shape[0]}, {out.shape[1]} new, {wall:.2f}s, "
          f"{telemetry.COMPILES.total - built} compiles)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
