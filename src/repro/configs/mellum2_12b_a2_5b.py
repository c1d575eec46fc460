"""Mellum2 12B-A2.5B — sparse MoE with windowed and YaRN-full GQA layers
[https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct].

28L d_model=2304 32H (GQA kv=4, head_dim 128) vocab=98304, untied.
Layers repeat [sliding, sliding, sliding, full] x 7: sliding layers attend
to a 1024-token window with default rope (theta 5e5); full layers attend
causally with YaRN rope (theta 5e5, factor 16 over 8192 original
positions, beta_fast 32, beta_slow 1, attention factor 1 + 0.1 ln 16).
Every layer is sparse: 64 SwiGLU experts of width 896, top-8 with
renormalized gates (softmax over 64, then top-8), no shared expert.
RMSNorm eps 1e-6.

As served here (expert parallelism over 8 devices, EP-8): this device
holds experts 0-7 of every layer and replicates attention, the router,
the norms and the embeddings; `n_held=None` gives the whole model.
"""
from repro.configs import AttnKind, ModelConfig, MoESpec, RopeSpec

SLIDING = AttnKind("sliding", window=1024, rope=RopeSpec(theta=500000.0))
FULL = AttnKind("full", rope=RopeSpec(
    theta=500000.0, kind="yarn", factor=16.0, original_max_positions=8192,
    beta_fast=32.0, beta_slow=1.0, attention_factor=1.2772588722239782))

CONFIG = ModelConfig(
    name="mellum2-12b-a2.5b",
    family="moe",
    n_layers=28,
    d_model=2304,
    n_heads=32,
    n_kv_heads=4,
    head_dim=128,
    d_ff=896,
    vocab_size=98304,
    rope_theta=500000.0,
    norm_eps=1e-6,
    moe=MoESpec(n_experts=64, top_k=8, d_ff_expert=896, n_dense_layers=0,
                n_held=8, first_held=0),
    layer_kinds=(SLIDING, SLIDING, SLIDING, FULL),
    param_dtype="bfloat16",
    source="https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct",
)
