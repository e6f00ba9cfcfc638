"""Operations and bytes of the model and of each kernel call, from shapes.

Kept with the benchmark so that every PR counts them the same way.  The
packed layout pads a linear's reduction dim to 128 and its output dim to
256; int4 weights take half a byte per value, int8 one byte, and each
output column carries an f32 scale and an f32 zero point.
"""
from __future__ import annotations

import re
from typing import List, Tuple

K_ALIGN, N_ALIGN = 128, 256


def _ceil(x: int, m: int) -> int:
    return -(-x // m) * m


def linears(cfg) -> List[Tuple[str, int, int, int]]:
    """(name, fan_in, fan_out, bits) of one layer's linears."""
    hd = cfg.resolved_head_dim
    q, kv = cfg.num_heads * hd, cfg.num_kv_heads * hd
    d, f, b = cfg.d_model, cfg.d_ff, cfg.quant.weight_bits
    return [("wq", d, q, b), ("wk", d, kv, b), ("wv", d, kv, b),
            ("wo", q, d, b), ("w_gate", d, f, b), ("w_up", d, f, b),
            ("w_down", f, d, b)]


def packed_linear_bytes(k: int, n: int, bits: int) -> int:
    kp, np_ = _ceil(k, K_ALIGN), _ceil(n, N_ALIGN)
    return kp * np_ * bits // 8 + 2 * 4 * np_


def weight_bytes(cfg) -> int:
    """Bytes of the packed serving tree on the device: every packed linear
    with its scales and zero points, the int8 lm_head, f32 norm gains and
    bf16 q/k/v biases."""
    hd = cfg.resolved_head_dim
    per_layer = sum(packed_linear_bytes(k, n, b) for _, k, n, b in
                    linears(cfg))
    per_layer += 2 * 4 * cfg.d_model                       # ln1, ln2
    if cfg.qkv_bias:
        per_layer += 2 * (cfg.num_heads + 2 * cfg.num_kv_heads) * hd
    head = packed_linear_bytes(cfg.d_model, cfg.vocab_size,
                               cfg.quant.lm_head_bits)
    return cfg.num_layers * per_layer + head + 4 * cfg.d_model


def layer_linear_params(cfg) -> int:
    return sum(k * n for _, k, n, _ in linears(cfg))


def token_linear_ops(cfg) -> int:
    """Multiply-adds x 2 of every layer's linears for one token."""
    return 2 * cfg.num_layers * layer_linear_params(cfg)


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


def least_time(ops: float, nbytes: float, ops_peak: float,
               bytes_peak: float) -> float:
    return max(ops / ops_peak, nbytes / bytes_peak)


# --- one call of the W4A8 kernel, from the shapes the trace names ----------

_SHAPE = re.compile(r"\b([a-z]\w*)\[([\d,]*)\]")


def shapes(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    return [(dt, tuple(int(x) for x in dims.split(",") if x))
            for dt, dims in _SHAPE.findall(text)]


def w4a8_call(m: int, k: int, n: int, bits: int) -> Tuple[int, int]:
    """(int8 ops, bytes) of one ``w4a8_matmul`` call: int8 activations
    [m, k] and their f32 row scales, the packed weight [k, n] with its f32
    scale and zero rows, and the f32 output [m, n]."""
    ops = 2 * m * k * n
    nbytes = m * k + k * n * bits // 8 + 4 * m + 2 * 4 * n + 4 * m * n
    return ops, nbytes


def w4a8_call_from_operands(operands, result) -> Tuple[int, int]:
    """The same from the call's operand and result shapes:
    (xq [m,k], w [k, n/2 or n], sx [m,1], scale [1,n], zero [1,n]) -> [m,n]."""
    (_, (m, k)), (_, (_, cols)) = operands[0], operands[1]
    n = result[1][-1]
    bits = 4 if 2 * cols == n else 8
    return w4a8_call(m, k, n, bits)


def w4a8_roofline(calls, kind: str, peaks: dict):
    """Roofline share, in percent, of the ``w4a8_matmul`` calls of one kind
    of step program: the sum of each call's least time (its int8 operations
    over the int8 peak or its bytes over the HBM bandwidth, whichever is
    larger, from the shapes the call names) over their device time."""
    least = spent = 0.0
    for kernel, k, text, secs in calls:
        if kernel != "w4a8_matmul" or k != kind:
            continue
        head, _, rest = text.partition(" custom-call(")
        operands = shapes(rest.split("operand_layout_constraints=")[-1])
        ops, nbytes = w4a8_call_from_operands(operands, shapes(head)[0])
        least += least_time(ops, nbytes, peaks["int8_ops"],
                            peaks["hbm_bytes_per_s"])
        spent += secs
    return 100.0 * least / spent if spent > 0 else None
