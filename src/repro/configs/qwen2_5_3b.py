"""Qwen2.5-3B [hf:Qwen]: GQA kv=2, QKV bias; published in bf16."""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="qwen2.5-3b", family="dense",
    num_layers=36, d_model=2048, num_heads=16, num_kv_heads=2,
    d_ff=11008, vocab_size=151936,
    qkv_bias=True, rope_theta=1e6, param_dtype="bfloat16",
)
