"""mimo-v2-flash [moe]: 48L d_model=4096, 64 query heads, q/k 192 and v 128
per head; 39 sliding-window layers (window 128, 8 kv heads, a per-head
sink logit) and 9 full-attention layers (4 kv heads) by the published
hybrid_layer_pattern; layer 0 a dense FFN of 16384, layers 1-47 MoE with
256 routed experts of 2048, 8 per token, sigmoid routing, no shared
expert; vocab 152576, untied head.
[hf:XiaomiMiMo/MiMo-V2-Flash config.json; 309B-A15B]

The 3 multi-token-prediction layers are left out: a graph is one plain
decode step.  The deployment is stated for decode_32k on 64 v5e chips:
2 pipeline stages of 24 layers, 32 chips a stage; experts 32-way
expert-parallel (8 of 256 a chip); attention, layer 0's FFN, the
embedding and the head 4-way tensor-parallel by kv-head group (16 query
heads, 1 full and 2 sliding kv heads a chip); 8 data-parallel groups
over the 128 sequences, 16 a chip.  One micro-batch: a stage's expert
group routes all 128 tokens of a step.
"""
from repro.configs.base import AttnKind, Deployment, ModelConfig, MoECfg

# 0 = full attention, 1 = sliding window (hybrid_layer_pattern)
PATTERN = (0, 1, 1, 1, 1, 0, 1, 1, 1, 1, 1, 0, 1, 1, 1, 1, 1, 0, 1, 1, 1, 1,
           1, 0, 1, 1, 1, 1, 1, 0, 1, 1, 1, 1, 1, 0, 1, 1, 1, 1, 1, 0, 1, 1,
           1, 1, 1, 0)

CONFIG = ModelConfig(
    name="mimo-v2-flash",
    family="moe",
    n_layers=48,
    d_model=4096,
    n_heads=64,
    n_kv_heads=4,
    head_dim=192,
    v_head_dim=128,
    d_ff=16384,
    vocab_size=152576,
    rope_theta=5_000_000.0,
    swa=AttnKind(n_heads=64, n_kv_heads=8, head_dim=192, v_head_dim=128,
                 window=128, sink=True),
    layer_pattern=PATTERN,
    dense_layers=1,
    moe=MoECfg(n_experts=256, top_k=8, d_ff_expert=2048, every=1),
    deployment=Deployment(shape="decode_32k", pipeline_stages=2,
                          tensor_parallel=4, expert_parallel=32,
                          data_parallel=8, expert_tokens=128),
)
