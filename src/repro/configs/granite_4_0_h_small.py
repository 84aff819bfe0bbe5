"""Granite-4.0-H-Small (32B-A9B) [hf:ibm-granite/granite-4.0-h-small].

40 layers: 36 Mamba-2 mixers and 4 GQA attention mixers with no
positional embedding, each followed by a MoE block of 72 routed SiLU-gated
experts (top-10, softmax over the chosen ten) beside one shared MLP.
Published widths throughout; the config holds one chip's share of an
8-way expert-parallel deployment: experts 0..8 of each layer's 72.
"""
from repro.configs.base import ArchConfig, register

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

CONFIG = register(ArchConfig(
    name="granite-4.0-h-small", family="hybrid_moe",
    n_layers=40, d_model=4096, n_heads=32, n_kv_heads=8, head_dim=128,
    d_ff=768, vocab=100352,
    activation="silu", gated_mlp=True,
    n_experts=72, top_k=10, shared_ff=1536,
    experts_held=9, expert_offset=0,
    ssm_state=128, ssm_headdim=64, ssm_expand=2, ssm_chunk=256,
    conv_width=4, ssm_gated_norm=True,
    layer_types=_PERIOD * 4,
    embedding_multiplier=12.0, residual_multiplier=0.22,
    attention_multiplier=0.0078125, logits_scaling=16.0,
    use_rope=False, norm_eps=1e-5,
    subquadratic=False,
    notes="granitemoehybrid: Mamba-2 (128 heads of 64, d_state 128) and "
          "NoPE GQA at layers 5/15/25/35; 72 experts of width 768, top-10, "
          "shared MLP 1536; 9 experts held of 72 (one chip of 8).",
))
