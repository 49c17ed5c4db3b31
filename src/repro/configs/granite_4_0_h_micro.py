"""Granite-4.0-H-Micro [hf:ibm-granite/granite-4.0-h-micro]: a Mamba-2 /
GQA hybrid, no MoE; published in bf16.

40 layers in a 10-layer period: Mamba-2 mixers at offsets 0-4 and 6-9
and a GQA attention layer without position embedding (NoPE) at offset
5, so 36 mixers and 4 attention layers (5, 15, 25, 35); a SwiGLU MLP of
8,192 after every layer.  Mixers: 64 heads of 64, state 128, one group,
conv 4 with bias, projections without bias.  muP scalars: embeddings
x12, each residual branch x0.22, softmax scale 1/64, logits / 8.
"""
from repro.configs.base import ModelConfig, SSMConfig

_GROUP = tuple(("attn" if i == 5 else "ssm", "dense") for i in range(10))

CONFIG = ModelConfig(
    name="granite-4.0-h-micro", family="hybrid",
    num_layers=40, d_model=2048, num_heads=32, num_kv_heads=8, head_dim=64,
    d_ff=8192, vocab_size=100352, norm_eps=1e-5,
    rope=False, attn_scale=0.015625, embedding_multiplier=12.0,
    residual_multiplier=0.22, logits_scaling=8.0, tie_embeddings=True,
    ssm=SSMConfig(state_dim=128, head_dim=64, expand=2, ngroups=1,
                  conv_width=4),
    group=_GROUP, param_dtype="bfloat16",
)
