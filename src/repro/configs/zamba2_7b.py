"""zamba2-7b [hybrid] — Zyphra/Zamba2-7B-Instruct (config.json; arXiv:2411.15242).

81 Mamba2 layers (2 groups, d_state 64, headdim 64, 112 heads); before each of
the 13 layers in ``hybrid_layer_ids`` one of two shared transformer blocks,
in turn, reads [x, x_embed] (2 x 3584 wide): attention with 32 heads of 224,
scaled by (224 / 2) ** -0.5, then a gated exact-GELU MLP (14336) with a
rank-128 LoRA per application; a per-application 3584 x 3584 linear feeds
the block's output into that Mamba layer's input.  Tied embeddings.  Every
width is the published one (models/hybrid.py has the equations)."""
from .base import ModelConfig, register

CONFIG = register(ModelConfig(
    name="zamba2-7b", family="hybrid",
    n_layers=81, d_model=3584, n_heads=32, n_kv_heads=32, d_ff=14336, vocab=32000,
    mlp="geglu", rope_theta=10000.0, tie_embeddings=True,
    ssm_state=64, ssm_expand=2, ssm_headdim=64, ssm_conv=4, ssm_chunk=256,
    ssm_ngroups=2, ssm_dt_min=0.001,
    hybrid_layer_ids=(6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77),
    n_mem_blocks=2, adapter_rank=128, attn_head_dim=224, sub_quadratic=True,
))
