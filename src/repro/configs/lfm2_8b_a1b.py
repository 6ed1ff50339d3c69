"""lfm2-8b-a1b — LiquidAI LFM2-8B-A1B (``model_type: lfm2_moe``)
[https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json].

24 layers: 18 gated short convolutions (3 taps) and 6 GQA attention
layers (32 query / 8 KV heads of 64, QK-norm) in the published order;
the first 2 layers have a dense SwiGLU FFN of 7168, the other 22 an MoE of
32 experts of 1792, top-4, sigmoid scores with an expert bias that only
selects, gates normalised over the top-4 (``routed_scaling_factor`` 1).
No shared expert, no auxiliary loss.  ``head_dim`` is 2048 / 32 (the config gives none).
"""
from .base import ArchConfig, MoEConfig

LAYER_TYPES = ("conv", "conv", "full_attention", "conv", "conv", "conv",
               "full_attention", "conv", "conv", "conv", "full_attention",
               "conv", "conv", "conv", "full_attention", "conv", "conv",
               "conv", "full_attention", "conv", "conv", "full_attention",
               "conv", "conv")

CONFIG = ArchConfig(
    name="lfm2-8b-a1b", family="moe",
    n_layers=24, d_model=2048, n_heads=32, n_kv_heads=8, d_ff=7168,
    vocab_size=65536, head_dim=64, rope_theta=1e6, norm_eps=1e-5,
    qk_norm=True, layer_types=LAYER_TYPES, n_dense_layers=2,
    moe=MoEConfig(n_experts=32, top_k=4, d_expert=1792, router="sigmoid",
                  router_aux_coef=0.0),
    source="https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json",
)
