"""mellum2-12b [moe]: 28L d_model=2304 32H (GQA kv=4, head_dim 128)
vocab=98304 untied; every MLP sparse: 64 SwiGLU experts of width 896, top-8,
softmax over the chosen logits, no shared expert; layers in periods of 4:
three sliding-window (1024) layers with plain rope θ=500000, then one full
layer with YaRN rope (factor 16 over 8192 positions, β 32/1, attention
factor 1.2772588722239782); RMSNorm eps 1e-6, no biases
[hf: JetBrains/Mellum2-12B-A2.5B-Instruct, config.json].

The published ``intermediate_size`` (7168) is kept as ``d_ff``; no dense
MLP layer reads it.
"""
from repro.config import Config, ModelConfig

_YARN = dict(yarn_factor=16.0, yarn_original_max=8192, yarn_beta_fast=32.0,
             yarn_beta_slow=1.0, yarn_attention_factor=1.2772588722239782)


def config() -> Config:
    return Config(arch="mellum2-12b", model=ModelConfig(
        name="mellum2-12b", family="moe", num_layers=28, d_model=2304,
        num_heads=32, num_kv_heads=4, head_dim=128, d_ff=7168,
        vocab_size=98304, num_experts=64, experts_per_token=8,
        moe_d_ff=896, attn_pattern=("local", "local", "local", "global"),
        window_size=1024, rope_theta=500000.0, norm_eps=1e-6, **_YARN))


def smoke() -> Config:
    return Config(arch="mellum2-12b", model=ModelConfig(
        name="mellum2-12b-smoke", family="moe", num_layers=4, d_model=64,
        num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128, vocab_size=256,
        num_experts=8, experts_per_token=2, moe_d_ff=32,
        attn_pattern=("local", "local", "local", "global"), window_size=8,
        rope_theta=500000.0, norm_eps=1e-6, **_YARN))
