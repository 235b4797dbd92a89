"""Mellum2-12B-A2.5B: 28 layers of 3 sliding-window (1,024 keys) to 1
full attention layer, each with a 64-expert top-8 SwiGLU MLP of width
896 and no shared expert [hf:JetBrains/Mellum2-12B-A2.5B-Instruct].

``d_ff`` is the expert width (``moe_intermediate_size``): every entry of
``mlp_layer_types`` is sparse, so the dense ``intermediate_size`` (7,168)
is never used.  Full layers take YaRN (factor 16 over 8,192 positions),
sliding layers plain RoPE, both at theta 500,000.
"""
from .base import ArchConfig, YarnRope

CONFIG = ArchConfig(
    name="mellum2-12b-a2.5b", family="swa_moe",
    n_layers=28, d_model=2304, n_heads=32, n_kv_heads=4,
    d_ff=896, vocab=98304, head_dim=128, rope_theta=500_000.0,
    n_experts=64, top_k=8, window=1024,
    layer_types=(("sliding_attention",) * 3 + ("full_attention",)) * 7,
    full_rope_yarn=YarnRope(factor=16.0, original_max_position=8192,
                            beta_fast=32.0, beta_slow=1.0,
                            attention_factor=1.2772588722239782),
    param_dtype="bfloat16",
)
