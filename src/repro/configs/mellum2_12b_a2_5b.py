"""mellum2-12b-a2.5b [moe] — JetBrains' code model: 64 experts top-8 on
every layer, three 1024-token sliding-window layers then one full layer
with YaRN RoPE, repeated 7 times
[hf:JetBrains/Mellum2-12B-A2.5B-Instruct].

As held here: one chip's share of a 4-chip expert-parallel deployment,
experts 0-15 of every layer's 64; the router scores all 64."""
from .base import ModelConfig, YarnRope, register

CONFIG = register(
    ModelConfig(
        name="mellum2-12b-a2.5b",
        family="moe",
        source="hf:JetBrains/Mellum2-12B-A2.5B-Instruct",
        num_layers=28,
        d_model=2304,
        num_heads=32,
        num_kv_heads=4,  # GQA
        head_dim=128,  # decoupled from d_model / num_heads (72)
        d_ff=896,  # moe_intermediate_size: every layer is sparse
        vocab_size=98304,
        rope_theta=500000.0,
        layer_windows=(1024, 1024, 1024, 0),
        rope_yarn=YarnRope(factor=16.0, original_max_position=8192,
                           beta_fast=32.0, beta_slow=1.0,
                           attention_factor=1.2772588722239782),
        tie_embeddings=False,
        num_experts=64,
        experts_per_token=8,
        experts_held=(0, 16),
    )
)
