"""GLM-4-9B: a dense decoder with grouped-query attention and partial,
interleaved rotary positions.

Everything but the rotary is ``dense_gqa``'s: pre-norm blocks of
grouped-query attention with q/k/v bias, a SwiGLU feed-forward, a final
RMSNorm and an untied output head, all linears W4A8 with an int8 head; the
same draw rules, and the same operation and byte counts (the rotary adds
no linear operation).  The rotary turns the first ``partial_rotary_factor``
of each head's dimensions as interleaved pairs (2i, 2i + 1), with
frequencies over the rotated width; the rest pass through.
"""
from __future__ import annotations

import dataclasses

from bench.arch import dense_gqa
from bench.arch.dense_gqa import (  # noqa: F401
    DRAW, attention_ops, decode_attention_bytes, head_ops,
    kv_bytes_per_token, linears, logical_layers, token_linear_ops,
    weight_bytes)


def model_config(spec: dict):
    base = dense_gqa.model_config(dict(spec, qkv_bias=spec["attention_bias"]))
    return dataclasses.replace(
        base, rope_fraction=float(spec["partial_rotary_factor"]),
        rope_interleaved=bool(spec["rope_interleaved"]))
