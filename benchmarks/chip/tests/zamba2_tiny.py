"""A tiny copy of the zamba2-7b configuration for CPU tests: 12 layers with
shared blocks before layers 6 and 11 (blocks 0 and 1), D 64, 4 heads of 32
over the 128-wide concat, 2 groups, adapter rank 8, and its serving cell.
``setup(tmp)`` writes it beside ``chipbench_tiny``'s cells and returns the
BENCHMARK dict that points at them."""
from __future__ import annotations

import json
from pathlib import Path

import chipbench_tiny

CHIP = chipbench_tiny.CHIP


def config():
    c = json.loads((CHIP / "configs" / "zamba2-7b.json").read_text())
    ids = [6, 11]
    c.update(hidden_size=64, attention_hidden_size=128, attention_head_dim=32,
             num_attention_heads=4, num_key_value_heads=4, num_query_groups=4,
             kv_channels=16, intermediate_size=128, ffn_hidden_size=128,
             mamba_d_state=16, mamba_headdim=16, n_mamba_heads=8, chunk_size=8,
             adapter_rank=8, num_hidden_layers=12, hybrid_layer_ids=ids,
             layers_block_type=["hybrid" if i in ids else "mamba" for i in range(12)],
             vocab_size=256, max_position_embeddings=64,
             # each projection keeps the output scale it has at the published
             # widths (0.02 * sqrt(3584) = 1.2 = 0.15 * sqrt(64)), so the
             # shared blocks move the result as much as there
             initializer_range=0.15,
             program={"arch": "zamba2-7b", "overrides": {
                 "n_layers": 12, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
                 "attn_head_dim": 32, "d_ff": 128, "vocab": 256, "ssm_state": 16,
                 "ssm_headdim": 16, "ssm_chunk": 8, "adapter_rank": 8,
                 "hybrid_layer_ids": ids, "q_block": 16}})
    return c


#: set from this tiny cell's own readings on the CPU: sound runs read 0 to
#: 0.077 over six seeds (the bf16 program against the float32 reference), the
#: float8 control 1.9, an altered token 5.3, a decode that keeps its state 5.2
TRAFFIC = {"kind": "serve", "batch": 2, "prompt": 16, "new_tokens": 8,
           "sample_requests": 2, "limits": {"served_gap": 0.3}}


def setup(tmp: Path):
    """chipbench_tiny's cells plus ``zamba2-serve-b4`` on the tiny copy."""
    bench, traffic = chipbench_tiny.setup(tmp)
    (Path(tmp) / "zamba2.json").write_text(json.dumps(config()))
    (traffic / "z1.json").write_text(json.dumps(TRAFFIC))
    bench["configs"].append({"name": "zamba2-7b", "file": str(Path(tmp) / "zamba2.json")})
    bench["workloads"].append({"name": "zamba2-serve-b4", "config": "zamba2-7b",
                               "traffic": "z1", "chips": 1, "why": "tiny"})
    full = json.loads((chipbench_tiny.REPO / "BENCHMARK.json").read_text())
    named = {m["name"]: m for m in full["end_to_end"] + full["per_layer"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "zamba2-serve-b4" in named[m["name"]].get("workloads", []):
            m["workloads"].append("zamba2-serve-b4")
    return bench, traffic
