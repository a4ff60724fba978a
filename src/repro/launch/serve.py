"""Production serving launcher (batched prefill + sequence-sharded decode).

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m [--reduced] \
      --batch 4 --prompt-len 16 --new-tokens 32 [--mesh 2x4] [--seq-axes model,data]
"""
from __future__ import annotations

import argparse
import sys

import numpy as np

from ..configs.base import get_config, list_configs
from ..models.model import build_model
from ..runtime.serve import BatchedServer, ServeConfig, throughput_report
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
    rep = throughput_report(server, prompt_len=args.prompt_len,
                            new_tokens=args.new_tokens)
    print(f"{cfg.name}: {rep['tokens_per_s']:.1f} tok/s "
          f"(batch {rep['batch']}, {rep['new_tokens']} new, {rep['wall_s']:.1f}s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
