"""glm4-9b [dense] — partial rotary, extreme GQA (kv=2).

40L, d_model=4096, 32H of 128 (GQA kv=2), d_ff=13696 (SwiGLU),
vocab=151552, q/k/v bias, RMSNorm eps 1.5625e-7, untied output head.
Rotary on the first half of each head's dims (partial_rotary_factor 0.5),
as interleaved pairs (2i, 2i+1), frequencies theta^(-2i/64) with theta
10000 (rope_ratio 1 in the base model); seq_length 8192.
[hf:THUDM/glm-4-9b]
"""
from repro.configs.base import ATTN, ModelConfig

CONFIG = ModelConfig(
    name="glm4-9b",
    arch_type="dense",
    num_layers=40,
    d_model=4096,
    num_heads=32,
    num_kv_heads=2,
    head_dim=128,
    d_ff=13696,
    vocab_size=151552,
    period=(ATTN,),
    rope_theta=10000.0,
    rope_fraction=0.5,
    rope_interleaved=True,
    qkv_bias=True,
    rms_eps=1.5625e-7,
    sub_quadratic=False,
    source="hf:THUDM/glm-4-9b",
)
