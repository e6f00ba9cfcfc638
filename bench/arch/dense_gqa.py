"""A dense decoder with grouped-query attention (Qwen2, GLM-4 shapes).

Pre-norm blocks of grouped-query attention (optional q/k/v bias, rotary
positions on every dimension of each head, rotate-half form) and a SwiGLU
feed-forward, a final RMSNorm and an untied output head, all linears W4A8
with an int8 head.

An arch module supplies what the benchmark knows of one kind of model:
``model_config`` (the program's ``ModelConfig`` for a configuration file),
``DRAW`` (the draw rule of each leaf of the serving tree that is not a
packed linear, by leaf name), ``logical_layers`` (the drawn tree as plain
arrays for the configuration's reference), and the model's operation and
byte counts that ``bench/costs.py`` hands on to the readers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import costs
from bench import model


def model_config(spec: dict):
    from repro.configs.base import ATTN, ModelConfig
    from repro.core.quantization import QuantConfig
    qz = spec["quantization"]
    return ModelConfig(
        name=spec["name"], arch_type="dense",
        num_layers=spec["num_hidden_layers"], d_model=spec["hidden_size"],
        num_heads=spec["num_attention_heads"],
        num_kv_heads=spec["num_key_value_heads"],
        head_dim=spec["head_dim"], d_ff=spec["intermediate_size"],
        vocab_size=spec["vocab_size"], period=(ATTN,),
        qkv_bias=spec["qkv_bias"], rope_theta=float(spec["rope_theta"]),
        rms_eps=float(spec["rms_norm_eps"]), act="swiglu",
        quant=QuantConfig(weight_bits=qz["weight_bits"],
                          act_bits=qz["act_bits"],
                          lm_head_bits=qz["lm_head_bits"],
                          kv_key_bits=qz["kv_key_bits"],
                          kv_value_fp8=qz["kv_value_fp8"]),
        source=spec["source"])


def _gain(key, ab):
    return jax.random.uniform(key, ab.shape, ab.dtype, 0.8, 1.2)


def _bias(key, ab):
    return (0.1 * jax.random.normal(key, ab.shape, jnp.float32)
            ).astype(ab.dtype)


DRAW = {"ln1": _gain, "ln2": _gain, "final_norm": _gain,
        "bq": _bias, "bk": _bias, "bv": _bias}


def logical_layers(cfg, params) -> dict:
    """The packed tree as plain arrays for the reference: per linear its
    int4/int8 codes as stored plus f32 scale and zero rows; biases and norm
    gains as they are.  Stacked layers keep their leading layer axis."""
    lin = model.plain_linear
    (layer,) = params["stacks"][0]
    out = {"final_norm": params["final_norm"],
           "lm_head": lin(params["lm_head"]["w"]),
           "ln1": layer["ln1"], "ln2": layer["ln2"]}
    att, ffn = layer["attn"], layer["ffn"]
    for k in ("wq", "wk", "wv", "wo"):
        out[k] = lin(att[k]["w"])
    for k in ("bq", "bk", "bv"):
        if k in att:
            out[k] = att[k]
    for k in ("w_gate", "w_up", "w_down"):
        out[k] = lin(ffn[k]["w"])
    return out


# --- operations and bytes ---------------------------------------------------

def linears(cfg):
    """(name, fan_in, fan_out, bits) of one layer's linears."""
    hd = cfg.resolved_head_dim
    q, kv = cfg.num_heads * hd, cfg.num_kv_heads * hd
    d, f, b = cfg.d_model, cfg.d_ff, cfg.quant.weight_bits
    return [("wq", d, q, b), ("wk", d, kv, b), ("wv", d, kv, b),
            ("wo", q, d, b), ("w_gate", d, f, b), ("w_up", d, f, b),
            ("w_down", f, d, b)]


def weight_bytes(cfg) -> int:
    """Bytes of the packed serving tree on the device: every packed linear
    with its scales and zero points, the int8 lm_head, f32 norm gains and
    bf16 q/k/v biases."""
    hd = cfg.resolved_head_dim
    per_layer = sum(costs.packed_linear_bytes(k, n, b)
                    for _, k, n, b in linears(cfg))
    per_layer += 2 * 4 * cfg.d_model                       # ln1, ln2
    if cfg.qkv_bias:
        per_layer += 2 * (cfg.num_heads + 2 * cfg.num_kv_heads) * hd
    head = costs.packed_linear_bytes(cfg.d_model, cfg.vocab_size,
                                     cfg.quant.lm_head_bits)
    return cfg.num_layers * per_layer + head + 4 * cfg.d_model


def token_linear_ops(cfg) -> int:
    """Multiply-adds x 2 of every layer's linears for one token."""
    return 2 * cfg.num_layers * sum(k * n for _, k, n, _ in linears(cfg))


def head_ops(cfg) -> int:
    return 2 * cfg.d_model * cfg.vocab_size


def attention_ops(cfg, keys: int) -> int:
    """Q.K and P.V over ``keys`` attended positions, all layers."""
    return 4 * cfg.num_layers * cfg.num_heads * cfg.resolved_head_dim * keys


def kv_bytes_per_token(cfg) -> int:
    """One token's cache in one layer: int8 keys, fp8 values, an f32 key
    scale and zero point per kv head."""
    return cfg.num_kv_heads * (2 * cfg.resolved_head_dim + 8)


def decode_attention_bytes(cfg, context_tokens: int, rows: int) -> int:
    """Bytes one paged decode-attention pass needs, all layers: each row's
    context of cache plus its f32 query and output."""
    q = 2 * 4 * cfg.num_heads * cfg.resolved_head_dim
    return cfg.num_layers * (context_tokens * kv_bytes_per_token(cfg)
                             + rows * q)
